"""Find a configuration's knee: one set-up, then a ladder of offered
query rates through the same open loop as the benchmark's window.

    python3 benchmarks/chip/sweep.py --workload wiki768.search \\
        --rates 200,300,400,500,600 --step-seconds 8 --seed 1

Queries only (the cell's write stream is left out), on the cell's own
deployment and query mix; with ``--churn`` the cell's write stream runs
under every step, as in its window. For each rate it prints the offered and
completed rate, how much later than due the last quarter of queries was
handed to the engine than the first quarter (a backlog that grows over
the step shows here), and the p50 and p95 latency. The knee is the
highest rate whose backlog does not grow. Runs on the chip only.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from run import enable_cache, require_chips  # noqa: E402


def step(setup, cell, seed: int, rate: float, seconds: float,
         churn: bool) -> dict:
    import numpy as np

    from chipbench.cell import percentile, window_queries
    from chipbench.window import drive

    tr = dict(cell.traffic, query_rate_qps=rate)
    q, offsets = window_queries(dataclasses.replace(cell, traffic=tr),
                                setup, seed, seconds)
    w = drive(setup.svc, q, offsets, seconds, setup.k, setup.L,
              n_live=setup.corpus.n_load,
              stream=setup.stream if churn else None,
              predicate=setup.predicate)
    ok = w.status == 200
    lag = w.sent - w.due
    quarter = max(1, len(lag) // 4)
    span = w.done[ok].max() - w.t0 if ok.any() else float("nan")
    return {
        "offered_qps": rate, "queries": int(len(q)),
        "completed_qps": float(ok.sum() / span),
        "backlog_growth_ms": float((np.mean(lag[-quarter:])
                                    - np.mean(lag[:quarter])) * 1e3),
        "p50_ms": percentile(w.latency_ms[ok], 50),
        "p95_ms": percentile(w.latency_ms[ok], 95),
        "mean_batch": float(np.mean(w.batch[ok])),
        "write_ops_per_s": w.write_ops / (w.writes_end - w.t0),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--step-seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--churn", action="store_true",
                    help="keep the cell's write stream running")
    ap.add_argument("--out", type=Path, default=None)
    args = ap.parse_args(argv)
    from chipbench.spec import load_cell

    cell = load_cell(args.workload)
    require_chips(cell.chips)
    enable_cache()
    from chipbench.cell import log, set_up

    if not args.churn:
        cell = dataclasses.replace(cell,
                                   traffic=dict(cell.traffic, writes=None))
    setup = set_up(cell, args.seed)
    log(f"sweep {cell.name}: set-up {time.perf_counter() - T_PROCESS:.1f}s")
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        row = dict(step(setup, cell, args.seed + 1 + i, rate,
                        args.step_seconds, args.churn),
                   workload=cell.name, churn=args.churn)
        print(json.dumps(row), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
