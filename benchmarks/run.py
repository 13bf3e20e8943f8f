"""Benchmark harness — one entry per paper table/figure.

``python -m benchmarks.run`` prints a human summary per benchmark and a
final machine-readable CSV: ``name,us_per_call,derived``.
`us_per_call` is the wall time of the benchmark's run on this CPU
container; `derived` is the benchmark's paper-comparable headline number
(see each module's docstring).
"""
from __future__ import annotations

import sys
import time
import traceback


def _entry(name, fn, derive):
    t0 = time.perf_counter()
    try:
        out = fn()
        elapsed = time.perf_counter() - t0
        return name, elapsed * 1e6, derive(out), None
    except Exception as e:  # noqa: BLE001
        traceback.print_exc()
        return name, 0.0, "", e


def main() -> None:
    from repro.launch.cache import enable_compile_cache

    from . import (bench_algo_compare, bench_cost, bench_filtered,
                   bench_ingest, bench_query, bench_runbooks, bench_scaleout,
                   bench_scaling, bench_serve, bench_sharded, bench_tiered)

    enable_compile_cache()
    jobs = [
        ("serve_engine", bench_serve.main,
         lambda out: (f"speedup={out['speedup_batch16']['speedup']:.1f}x;"
                      f"recompiles={out['speedup_batch16']['recompiles_after_warmup']};"
                      f"p99@{out['loads'][-1]['offered_qps']:.0f}qps="
                      f"{out['loads'][-1]['p99_ms']:.1f}ms;"
                      f"page_ru_min={out['pagination']['ru_min_page']:.1f};"
                      f"scaling_gain={out['dispatch']['scaling_gain_lanes4']:.2f}x;"
                      f"trace_ovh={100 * out['observability']['overhead_frac']:.1f}%;"
                      f"adaptive_slo={out['adaptive']['slo_compliance_adaptive']:.3f};"
                      f"adaptive_idle_ru_vs_w1="
                      f"{out['adaptive']['idle_ru_adaptive_vs_w1']:.2f}x;"
                      f"stage_breakdown="
                      + "|".join(
                          f"{s}:{st['mean_ms']:.2f}ms"
                          for s, st in sorted(
                              out["loads"][-1]["stages"].items())))),
        ("tiered_residency", bench_tiered.main,
         lambda out: (f"recall_dmax={out['recall_delta_max']:.3f};"
                      f"hit_rate@0.5={out['hit_rate_half']:.2f};"
                      f"p95@0.25={out['p95_ratio_quarter']:.2f}x;"
                      f"ru@0.1={out['ru_ratio_tenth']:.2f}x;"
                      f"ids_bit_identical={out['ids_bit_identical']}")),
        ("fig6_query_vs_L", bench_query.main,
         lambda out: (f"recall@L100={out[0][-1]['recall']:.3f};"
                      f"p50={out[0][-1]['p50_ms']:.2f}ms;"
                      f"hops_w4/w1={out[1][-1]['hops'] / out[1][0]['hops']:.2f}")),
        ("fig7_8_scaling", bench_scaling.main,
         lambda out: f"growth100x={out[1]:.2f};ru10m={out[2]:.0f}"),
        ("table1_2_cost", bench_cost.main,
         lambda out: (f"pinecone_ratio={out['query_ratios']['pinecone']:.0f}x;"
                      f"zilliz_ratio={out['query_ratios']['zilliz']:.0f}x")),
        ("fig9_filtered", bench_filtered.main,
         lambda out: f"beta_p99={out[('beta', 100)]['p99']:.2f}ms;"
                     f"post_p99={out[('post', 100)]['p99']:.2f}ms;"
                     f"batched={out['batched']['speedup']:.1f}x"),
        ("fig10_scaleout", bench_scaleout.main,
         lambda rows: f"ru_p1={rows[0]['ru']:.0f};ru_p8={rows[-1]['ru']:.0f}"),
        ("fig11_12_ingest", bench_ingest.main,
         lambda traj: f"ms_per_insert={traj[-1]['ms_per_insert']:.2f}"),
        ("fig13_runbooks", bench_runbooks.main, lambda _: "see_table"),
        ("table3_sharded", bench_sharded.main,
         lambda out: f"sharded_recall={out['sharded']['recall']:.2f};"
                     f"nonsharded={out['nonsharded_L50']['recall']:.2f}"),
        ("fig14_algo_compare", bench_algo_compare.main,
         lambda out: f"graph_best_recall={max(out[1])[0]:.2f}"),
    ]

    rows = []
    failed = 0
    for name, fn, derive in jobs:
        print(f"\n################ {name} ################", flush=True)
        n, us, d, err = _entry(name, fn, derive)
        rows.append((n, us, d))
        failed += err is not None

    # roofline summary appended when dry-run artifacts exist
    try:
        from . import roofline
        rl = roofline.analyse_dir()
        ok_rows = [r for r in rl if "t_compute" in r]
        if ok_rows:
            worst = min(ok_rows, key=lambda r: r["roofline_fraction"])
            rows.append(("roofline_cells", 0.0,
                         f"cells={len(ok_rows)};worst={worst['arch']}/{worst['shape']}"))
    except Exception:  # noqa: BLE001
        traceback.print_exc()

    print("\nname,us_per_call,derived")
    for n, us, d in rows:
        print(f"{n},{us:.0f},{d}")
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
