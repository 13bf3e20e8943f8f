"""Readings that the limits of the check are set from.

    python3 benchmarks/chip/control.py --workload wiki768.search \\
        --seeds 1,2,3,4 --control-seeds 1,2,3 --fault-L 10,20,40 \\
        --fault-rerank --seconds 30

For each seed, in one process: the cell's set-up and a window at its own
load, then the check's numbers for the program's answers (the lower
readings). For each control seed also:

* the numbers for the control's answers: the plain reference put in the
  program's place with the vectors in bfloat16, one step below the
  float32 the configuration states (the upper reading of ``dist_gap``);
* after the window, the first ``--replay`` window queries asked again
  through the same entry points with the search list cut from the
  configuration's ``L_search`` to each ``--fault-L`` (a search that
  stops early; the program floors the list at its rerank width k' = 5k),
  with ``--fault-rerank`` once more with the full-precision rerank cut
  to the k best by ADC (the search's ``rerank_multiplier`` 1: a coarser
  ranking), and once at ``L_search`` for comparison, each against the
  exact reference over the docs live then: the upper readings of
  ``recall_miss``.

One JSON line per seed and side goes to standard output, and to ``--out``
when given. Runs on the chip only; the benchmark's own runs never run the
control.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from run import enable_cache, require_chips  # noqa: E402


def control_check(o):
    """The check's numbers for the control's answers to the window's
    queries, at the live set each query was dispatched against."""
    from chipbench import check as chk

    import numpy as np

    w = o.window
    pos, dists = chk.bf16_answers(o.queries, o.corpus.vectors, w.live_lo,
                                  w.live_hi, o.k, eligible=o.eligible)
    n = len(o.queries)
    return chk.compare(o.queries, o.corpus.vectors, pos, dists,
                       np.full(n, 200), np.ones(n, bool), w.live_lo,
                       w.live_hi, o.k, o.cell.limits["recall_miss"],
                       eligible=o.eligible)


@contextlib.contextmanager
def rerank_cut():
    """The program's search with its rerank cut to the top k by ADC."""
    from repro.core.index import DiskANNIndex

    orig = DiskANNIndex.search

    def cut(self, queries, k, L=None, rerank_multiplier=None, **kw):
        return orig(self, queries, k, L, 1.0, **kw)

    DiskANNIndex.search = cut
    try:
        yield
    finally:
        DiskANNIndex.search = orig


def replays(L_values, n: int, rows: list, cut_rerank: bool = False):
    """``after`` for ``execute``: the first ``n`` window queries asked again
    at each search list length in ``L_values`` (and, with ``cut_rerank``,
    at the first of them with the rerank cut); appends (side,
    recall_miss, unanswered) to ``rows``."""

    def after(setup, queries):
        from chipbench import check as chk
        from chipbench.cell import positions
        from chipbench.window import serve_batches

        import numpy as np

        q = queries[:n]
        lo, hi = (setup.stream.live_range() if setup.stream
                  else (0, setup.corpus.n_load))
        ref = chk.exact_topk(q, setup.corpus.vectors, np.full(len(q), lo),
                             np.full(len(q), hi), setup.k,
                             eligible=setup.eligible)
        def ask(side, L):
            ids, status, _ = serve_batches(setup.svc, q, setup.k, L,
                                           predicate=setup.predicate)
            miss = 1.0 - chk.recall(positions(setup.corpus, ids), ref.truth)
            rows.append((side, miss, int((status != 200).sum())))

        for L in L_values:
            ask(f"replay_L{L}", L)
        if cut_rerank:
            with rerank_cut():
                ask("replay_rerank_k", L_values[0])

    return after


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-L", default="")
    ap.add_argument("--fault-rerank", action="store_true")
    ap.add_argument("--replay", type=int, default=2048)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", type=Path, default=None)
    args = ap.parse_args(argv)
    from chipbench.spec import load_cell

    cell = load_cell(args.workload)
    require_chips(cell.chips)
    enable_cache()
    from chipbench.cell import CompileCounter, compare, execute

    counter = CompileCounter()
    ctrl = {int(s) for s in args.control_seeds.split(",") if s}
    t_start = T_PROCESS
    L_search = int(cell.config["graph"]["L_search"])
    faults = [int(x) for x in args.fault_L.split(",") if x]
    for seed in (int(s) for s in args.seeds.split(",")):
        replayed: list = []
        after = (replays([L_search] + faults, args.replay, replayed,
                         args.fault_rerank)
                 if seed in ctrl else None)
        o = execute(cell, seed, args.seconds, False, t_start, counter, after)
        rows = [("program", compare(o))]
        if seed in ctrl:
            rows.append(("control_bf16", control_check(o)))
        lines = [{"side": side, "correct": c.correct, "recall": c.recall,
                  "numbers": {k: v for k, (v, _lim) in c.numbers.items()}}
                 for side, c in rows]
        lines += [{"side": side, "queries": min(args.replay, len(o.queries)),
                   "numbers": {"recall_miss": miss, "unanswered": bad}}
                  for side, miss, bad in replayed]
        for row in lines:
            line = json.dumps({"workload": cell.name, "seed": seed, **row,
                               "setup_s": o.setup_s,
                               "window_queries": len(o.queries)})
            print(line, flush=True)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(line + "\n")
        t_start = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
