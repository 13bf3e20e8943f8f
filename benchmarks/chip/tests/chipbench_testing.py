"""Shared by the chip benchmark's own tests (CPU only, at small sizes):
importing it puts the benchmark and the program on ``sys.path``."""
import copy
import dataclasses
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parents[1] / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def tiny_cell(workload: str, rate: float = 40.0):
    """``workload`` from BENCHMARK.json at a size the CPU runs in seconds:
    every width and the traffic's shape kept, the scale and the 768-D / PQ
    widths cut."""
    from chipbench import spec

    cell = spec.load_cell(workload)
    cfg = copy.deepcopy(cell.config)
    cfg.update(dim=16, max_vectors_per_partition=1200, capacity_slack=64,
               docs_loaded=600)
    cfg["graph"].update(M=4, L_build=24, L_search=24, bootstrap_sample=200,
                        refine_sample=400)
    tr = copy.deepcopy(cell.traffic)
    tr["query_rate_qps"] = rate
    if tr.get("writes"):
        tr["writes"]["stream_docs"] = 512
    return dataclasses.replace(cell, config=cfg, traffic=tr)
