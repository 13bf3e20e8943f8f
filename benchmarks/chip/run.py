"""Chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 benchmarks/chip/run.py --workload wiki768.search --seed 7 \\
        --seconds 30 --trace 0

Loads the cell's deployment with data made from ``--seed``, warms up the
shapes its traffic uses, measures for ``--seconds`` seconds, checks every
answer against a plain exact reference, and prints one JSON object as the
last line of standard output: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics from a profiler trace of the window) and ``device``.
Everything else goes to standard error; its last lines are the numbers
compared, each with its limit.

It runs on the chips only: where JAX finds no accelerator, or fewer chips
than the cell asks for, it exits non-zero and prints no result. JAX's
compilation cache is ``JAX_COMPILATION_CACHE_DIR`` where that is set, and
``benchmarks/chip/.jax_cache`` in this checkout otherwise.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

CACHE_DIR = HERE / ".jax_cache"


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def require_chips(n: int):
    """The devices, or exit non-zero when JAX sees no TPU or fewer
    than ``n`` of them."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"run.py: no accelerator (JAX sees {devs[0].platform}); "
                 "nothing was run")
    if len(devs) < n:
        sys.exit(f"run.py: the cell needs {n} chips, JAX sees {len(devs)}")
    return devs


def enable_cache() -> None:
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def main(argv=None) -> int:
    args = parse(argv)
    from chipbench.spec import load_cell

    cell = load_cell(args.workload)
    require_chips(cell.chips)
    enable_cache()
    from chipbench.cell import run

    result = run(cell, args.seed, args.seconds, bool(args.trace), T_PROCESS)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
