"""Observability plane: request records on the host clock, the labeled
cost-attribution registry, the bounded streaming histogram, flight
recorder retention, and the exporters.

The invariants under test are *conservation laws*, not smoke checks:

  * every served record's root-span stage durations sum to its measured
    host-clock latency (stages tile the request interval — a dashboard
    built on them can never silently leak time), in every dispatch mode,
    and its micro-batch's measured spans are in the ``batch`` record it
    names;
  * exactly one latency sample per admitted request, even when the lane
    plane hedges a duplicate or retries across faulted lanes;
  * the labeled registry's RU totals reconcile with the engine-global
    aggregates AND with every tenant governor's settled consumption,
    refund paths included.
"""
import json

import numpy as np
import pytest

from repro.core import GraphConfig
from repro.serve import (EngineConfig, ExactHistogram, Histogram,
                         VectorCollectionService, VectorQuery,
                         VectorServeEngine, validate_trace_record)
from repro.serve.trace import (ANOMALY_HEDGE, ANOMALY_THROTTLE,
                               FlightRecorder, Trace, Tracer)

from conftest import clustered_data


def make_multipart_service(n=360, dim=16, parts=3, seed=11, **engine_kw):
    """Small 3-physical-partition service — fan-out traces need >1 pid."""
    rng = np.random.RandomState(seed)
    g = GraphConfig(capacity=240, R=16, M=8, L_build=32, L_search=32,
                    bootstrap_sample=48, refine_sample=10**9, batch_size=64)
    svc = VectorCollectionService(dim=dim, graph=g,
                                  max_vectors_per_partition=200,
                                  initial_partitions=parts,
                                  engine_cfg=EngineConfig(**engine_kw))
    data = clustered_data(rng, n, dim)
    svc.upsert([{"id": i} for i in range(n)], data,
               partition_keys=[f"pk{i}" for i in range(n)])
    return svc, data, rng


# ---------------------------------------------------------------------------
# streaming histogram (satellite 1)
# ---------------------------------------------------------------------------

def test_streaming_histogram_parity_with_exact():
    """The bounded histogram must agree with the exact reference: count /
    sum / mean / min / max exactly, percentiles within the geometric-bin
    resolution (≤ √GROWTH−1 ≈ 3.4% relative, plus rank-vs-interpolation
    slack at the tails)."""
    rng = np.random.RandomState(5)
    for scale, n in ((1.0, 5000), (40.0, 2000), (0.002, 800)):
        h, ex = Histogram(), ExactHistogram()
        samples = rng.lognormal(mean=np.log(scale), sigma=1.2, size=n)
        for v in samples:
            h.observe(v)
            ex.observe(v)
        assert h.count == ex.count == n
        assert h.sum == pytest.approx(ex.sum, rel=1e-12)
        assert h.mean() == pytest.approx(ex.mean(), rel=1e-12)
        assert h.min == samples.min() and h.max == samples.max()
        srt = np.sort(samples)
        for p in (1, 10, 50, 90, 95, 99, 99.9):
            approx = h.percentile(p)
            # the exact order statistic at the streaming histogram's rank
            # convention (ceil(p% · n)): the geometric binning guarantees
            # the readout within ±(√GROWTH−1) ≈ 3.4% of THAT sample; the
            # np.percentile comparison below adds interpolation slack and
            # so only holds away from the sparse tails
            rank = min(max(1, int(np.ceil(p / 100.0 * n))), n)
            exact = srt[rank - 1]
            if exact <= Histogram.LO:
                # below the resolution floor the underflow bin clamps the
                # readout into [min, LO] — documented, not a parity breach
                assert h.min <= approx <= Histogram.LO
            else:
                assert approx == pytest.approx(exact, rel=0.04), \
                    f"p{p} @scale={scale}: {approx} vs {exact}"
        for p in (10, 50, 90, 95):
            if srt[int(np.ceil(p / 100.0 * n)) - 1] > Histogram.LO:
                assert h.percentile(p) == pytest.approx(ex.percentile(p),
                                                        rel=0.06)


def test_streaming_histogram_bounded_and_monotone():
    """O(1) memory regardless of samples; percentile(p) monotone in p and
    clamped to the exact observed range (incl. sub-LO underflow values)."""
    h = Histogram()
    rng = np.random.RandomState(7)
    for v in rng.exponential(3.0, size=50_000):
        h.observe(v)
    h.observe(1e-7)  # underflow bin
    h.observe(5e8)  # deep tail
    assert h._counts.size == Histogram.NBINS + 2  # never grows
    ps = [h.percentile(p) for p in (0.01, 1, 25, 50, 75, 95, 99, 99.99, 100)]
    assert ps == sorted(ps)
    assert ps[0] >= h.min and ps[-1] <= h.max
    empty = Histogram()
    assert (empty.percentile(50), empty.mean(), empty.count) == (0.0, 0.0, 0)


# ---------------------------------------------------------------------------
# trace reconciliation (tentpole acceptance)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["serial", "replica", "spmd"])
def test_stage_sums_reconcile_with_latency(mode):
    """Every admitted query produces a schema-valid record whose root-span
    stage times sum (within clock resolution) to its host-clock latency —
    across all three dispatch planes — and names the ``batch`` record
    that holds its micro-batch's measured spans. The replica plane runs
    with forced stragglers + hedging so the anomalous path reconciles
    too."""
    svc, data, rng = make_multipart_service()
    hedged = mode == "replica"
    eng = VectorServeEngine(
        svc.collection,
        cfg=EngineConfig(dispatch_mode=mode, lanes=4,
                         admission_control=False, flight_recorder=256,
                         straggler_p=0.5 if hedged else 0.0,
                         hedge_at_ms=0.05 if hedged else None,
                         dispatch_seed=3),
    )
    queries = data[rng.choice(len(data), 24, replace=False)] + 0.01
    rids = [eng.submit_query(q, k=5) for q in queries]
    eng.drain()
    assert all(eng.responses[r].status == 200 for r in rids)

    all_recs = eng.tracer.recorder.records()
    batches = {r["trace_id"]: r for r in all_recs if r["kind"] == "batch"}
    recs = [r for r in all_recs if r["kind"] == "query"]
    assert len(recs) == len(rids) == eng.metrics.queries_ok
    assert len(batches) == eng.metrics.batches
    for rec in recs:
        validate_trace_record(rec)  # raises on any stage-time leak
        stages = {s["stage"] for s in rec["spans"]}
        assert {"admission", "queue", "batch_form", "lane"} <= stages
        b = batches[rec["batch_id"]]
        # the query's answer was stored inside its micro-batch's dispatch
        assert b["t0_s"] <= rec["t1_s"] <= b["t1_s"]
    for b in batches.values():
        validate_trace_record(b)
        names = [s["name"] for s in b["spans"]]
        assert names[0] == "serve.dispatch" and b["spans"][0]["parent"] == -1
        assert {"serve.respond", "search.merge"} <= set(names)
        if mode != "spmd":  # spmd searches every partition in one program
            # fan-out decomposition: one measured span per partition,
            # each holding the index's search steps
            pids = {s["attrs"]["pid"] for s in b["spans"]
                    if s["name"] == "search.partition"}
            assert len(pids) == len(svc.collection.partitions)
            assert {"search.prepare", "search.graph", "search.rerank",
                    "search.finish"} <= set(names)
    if hedged:
        hedge_recs = [r for r in recs if ANOMALY_HEDGE in r["anomalies"]]
        assert eng.metrics.hedges > 0 and hedge_recs, \
            "replica run must exercise + capture the hedge path"
        assert all(any(s["stage"] == "hedge" for s in r["spans"])
                   for r in hedge_recs)

    # aggregate reconciliation: the per-stage histograms account for ALL
    # the latency the end-to-end histogram recorded
    lat_total = eng.metrics.latency_ms.sum
    stage_total = sum(h.sum for _, h in eng.obs.series("serve_stage_ms"))
    assert stage_total == pytest.approx(lat_total, rel=1e-9)


def test_single_latency_sample_per_request_under_hedge_and_fault():
    """Satellite 2: hedged duplicates and fault retries are lane-plane
    internals — one admitted request yields exactly one response and one
    latency/stage sample, never two (the double-observation bug would
    corrupt every percentile under exactly the loads that matter)."""
    svc, data, rng = make_multipart_service(seed=13)
    eng = VectorServeEngine(
        svc.collection,
        cfg=EngineConfig(dispatch_mode="replica", lanes=4,
                         admission_control=False, straggler_p=0.9,
                         hedge_at_ms=0.01, dispatch_seed=5),
    )
    eng.executor.inject_fault(0)  # a lane fault mid-workload as well
    n = 30
    queries = data[rng.choice(len(data), n, replace=False)] + 0.01
    rids = [eng.submit_query(q, k=5) for q in queries]
    eng.drain()

    assert eng.metrics.hedges > 0, "workload must actually hedge"
    assert eng.executor.retries > 0, "workload must actually retry a fault"
    assert len(rids) == len(set(rids)) == n
    assert sorted(eng.responses) == sorted(rids)
    assert eng.metrics.queries_ok == n
    assert eng.metrics.latency_ms.count == n  # exactly one sample each
    assert eng.metrics.wait_ms.count == n
    h = eng.obs.histogram("serve_latency_ms", tenant="default")
    assert h is not None and h.count == n
    assert eng.obs.total("serve_requests_total", kind="query",
                         status="200") == n


# ---------------------------------------------------------------------------
# RU conservation (satellite 3)
# ---------------------------------------------------------------------------

def test_ru_conservation_registry_engine_governors():
    """The labeled registry, the engine-global aggregates, and the tenant
    governors are three views of the same RU flow and must agree exactly:
    Σ{op=query|page} == ru_query_total, Σ{op=hedge} == hedge_ru_total,
    Σ{op=ingest} == ru_ingest_total, and each tenant's query+page+hedge
    == what its governor settled (reservation + reconciliation + EMA)."""
    svc, data, rng = make_multipart_service(
        seed=17, dispatch_mode="replica", lanes=4, admission_control=True,
        tenant_ru_s=10**9, straggler_p=0.4, hedge_at_ms=0.05,
    )
    eng = svc.engine
    queries = data[rng.choice(len(data), 20, replace=False)] + 0.01
    for i, q in enumerate(queries):
        eng.submit_query(q, k=5, tenant=f"t{i % 2}")
    eng.drain()
    # a paged query (host path) and interleaved ingest ride along
    token = None
    for _ in range(2):
        r = svc.query_page(VectorQuery(vector=data[3] + 0.01, tenant="t0"),
                           token, page_size=5)
        token = r.continuation
    extra = clustered_data(rng, 32, data.shape[1]) + 2.0
    svc.upsert_async([{"id": 10**6 + i} for i in range(len(extra))], extra,
                     tenant="t1")
    eng.flush_ingest()

    m, obs = eng.metrics, eng.obs
    assert obs.total("serve_ru_total", op="query") + \
        obs.total("serve_ru_total", op="page") == \
        pytest.approx(m.ru_query_total, rel=1e-9)
    assert obs.total("serve_ru_total", op="hedge") == \
        pytest.approx(m.hedge_ru_total, rel=1e-9)
    assert m.hedge_ru_total > 0, "conservation must cover the hedge path"
    assert obs.total("serve_ru_total", op="ingest") == \
        pytest.approx(m.ru_ingest_total, rel=1e-9)
    assert m.ru_ingest_total > 0
    # per-tenant attribution == governor settlement (ingest is not
    # governor-metered; refunds never enter the registry)
    for t, gov in eng.tenants.items():
        attributed = sum(obs.total("serve_ru_total", tenant=str(t), op=op)
                         for op in ("query", "page", "hedge"))
        assert attributed == pytest.approx(gov.consumed, rel=1e-9), \
            f"tenant {t}: registry {attributed} vs governor {gov.consumed}"
        assert gov.settlements > 0


def test_failed_dispatch_refunds_reservation():
    """When the lane plane cannot place the work (every lane faulted) the
    admission reservation is handed back: the tenant's settled consumption
    returns to its pre-submit level, the refund is visible in governor
    telemetry, and no RU enters the attribution registry."""
    svc, data, _ = make_multipart_service(
        seed=19, dispatch_mode="replica", lanes=1, admission_control=True,
        tenant_ru_s=10**6,
    )
    eng = svc.engine
    eng.executor.inject_fault(0)  # the only lane → dispatch must fail
    eng.submit_query(data[0] + 0.01, k=5, tenant="t-fail")
    gov = eng.tenant_governor("t-fail")
    reserved = gov.consumed
    assert reserved > 0  # admission reserved the estimate up front
    with pytest.raises(RuntimeError, match="no healthy lanes"):
        eng.drain()
    assert gov.consumed == pytest.approx(0.0, abs=1e-9)
    assert gov.refunded == pytest.approx(reserved, rel=1e-9)
    assert eng.obs.total("serve_ru_total", tenant="t-fail") == 0.0


# ---------------------------------------------------------------------------
# throttle + flight recorder retention
# ---------------------------------------------------------------------------

def test_throttle_traces_always_captured():
    """429s are anomalous by definition: traced (admission span carries
    retry_after), tagged, and counted per tenant in the registry."""
    svc, data, _ = make_multipart_service(
        seed=23, admission_control=True, tenant_ru_s=25.0,
        admission_estimate_ru=20.0,
    )
    eng = svc.engine
    statuses = []
    for i in range(6):  # budget admits the first; the burst throttles
        rid = eng.submit_query(data[i] + 0.01, k=5, tenant="small")
        resp = eng.responses.get(rid)
        statuses.append(429 if resp is not None and resp.status == 429
                        else 200)
    eng.drain()
    n_throttled = statuses.count(429)
    assert n_throttled > 0
    assert eng.metrics.queries_throttled == n_throttled
    assert eng.obs.counter_value("serve_throttled_total",
                                 tenant="small") == n_throttled
    recs = [r for r in eng.tracer.recorder.records() if r["status"] == 429]
    assert len(recs) == n_throttled
    for rec in recs:
        validate_trace_record(rec)
        assert ANOMALY_THROTTLE in rec["anomalies"]
        assert rec["spans"][0]["attrs"]["retry_after_s"] > 0
        assert rec["ru"] == 0.0  # a rejection is never billed


def test_flight_recorder_anomalies_survive_healthy_churn():
    """The healthy ring is bounded; the anomaly ring is separate — a long
    burst of healthy traffic can never evict the interesting evidence."""
    fr = FlightRecorder(capacity=8)

    def rec(tid, anomalies=()):
        return Trace(trace_id=tid, kind="query", tenant="t", rid=tid,
                     status=200, anomalies=list(anomalies))

    fr.record(rec(0, ["hedge"]))
    fr.record(rec(1, ["fault_retry"]))
    for tid in range(2, 500):
        fr.record(rec(tid))
    assert len(fr.ring) == 8 and fr.recorded == 500
    retained = {r["trace_id"] for r in fr.records()}
    assert {0, 1} <= retained, "anomalies evicted by healthy churn"
    assert retained >= set(range(492, 500))  # most recent always present
    assert fr.anomalies_seen == 2


def test_disabled_tracer_is_inert_and_result_identical():
    """cfg.trace=False: bit-identical serving results, nothing allocated,
    nothing retained — the zero-overhead contract."""
    svc, data, rng = make_multipart_service(seed=29)
    queries = data[rng.choice(len(data), 12, replace=False)] + 0.01

    def run(trace):
        eng = VectorServeEngine(
            svc.collection,
            cfg=EngineConfig(admission_control=False, trace=trace))
        rids = [eng.submit_query(q, k=5) for q in queries]
        eng.drain()
        return eng, [eng.responses[r] for r in rids]

    eng_off, r_off = run(False)
    eng_on, r_on = run(True)
    for a, b in zip(r_off, r_on):
        assert a.ids.tolist() == b.ids.tolist()
        assert a.dists.tolist() == b.dists.tolist()
        assert (a.ru, a.latency_ms, a.plan) == (b.ru, b.latency_ms, b.plan)
    assert eng_off.tracer.begin("query", "t", 0) is None
    s = eng_off.tracer.stats()
    assert (s["started"], s["recorded"], s["retained"]) == (0, 0, 0)
    # one record per query plus one per micro-batch
    assert eng_on.tracer.stats()["recorded"] == \
        len(queries) + eng_on.metrics.batches


# ---------------------------------------------------------------------------
# page + ingest traces, exporters, registry hygiene
# ---------------------------------------------------------------------------

def test_page_and_ingest_traces(tmp_path):
    """Paged queries record their per-partition fetch rounds as point
    events under the lane span; each ingest mini-batch gets a record
    rooted at its measured ``serve.ingest`` span with the write path's
    spans under it. The JSONL exporter round-trips the schema and the
    Prometheus exposition carries every family, span totals included."""
    svc, data, rng = make_multipart_service(seed=31)
    eng = svc.engine
    token, pages = None, 0
    while pages < 3:
        r = svc.query_page(VectorQuery(vector=data[7] + 0.01), token,
                           page_size=5)
        token, pages = r.continuation, pages + 1
        if token is None:
            break
    extra = clustered_data(rng, 16, data.shape[1]) + 2.0
    svc.upsert_async([{"id": 10**6 + i} for i in range(len(extra))], extra)
    eng.flush_ingest()

    recs = eng.tracer.recorder.records()
    page_recs = [r for r in recs if r["kind"] == "page"]
    ingest_recs = [r for r in recs if r["kind"] == "ingest"]
    assert len(page_recs) == pages and ingest_recs
    all_fetches = []
    for rec in page_recs:
        validate_trace_record(rec)
        all_fetches += [s for s in rec["spans"] if s["stage"] == "partition"]
    # a page served entirely from cursor buffers legitimately fetches
    # nothing, but the opening page must fan out to every partition
    first_pids = {s["attrs"]["pid"] for s in page_recs[0]["spans"]
                  if s["stage"] == "partition"}
    assert len(first_pids) == len(svc.collection.partitions)
    assert all(s["name"].startswith("page.fetch[") and
               "round" in s["attrs"] and s["attrs"]["ru"] > 0
               for s in all_fetches)
    for rec in ingest_recs:
        validate_trace_record(rec)
        root = rec["spans"][0]
        assert (root["name"], root["stage"]) == ("serve.ingest", "serve")
        assert root["attrs"]["kind"] == "upsert"
        assert root["attrs"]["ru"] == pytest.approx(rec["ru"])
        assert "write.insert" in {s["name"] for s in rec["spans"]}

    out = tmp_path / "traces.jsonl"
    n = eng.tracer.dump_jsonl(out)
    lines = out.read_text().splitlines()
    assert len(lines) == n == len(recs)
    for line in lines:
        validate_trace_record(json.loads(line))

    prom = eng.obs.to_prometheus_text(spans=eng.tracer.span_totals())
    for family in ("serve_requests_total", "serve_ru_total",
                   "serve_latency_ms_sum", "serve_stage_ms",
                   "serve_span_seconds_total", "serve_span_count",
                   "serve_span_max_seconds"):
        assert family in prom
    assert 'op="ingest"' in prom and 'quantile="0.95"' in prom
    assert 'serve_span_count{span="serve.ingest"}' in prom


def test_registry_locks_label_names_and_kinds():
    """A typo'd label key or kind mismatch fails loudly instead of
    silently forking a new series."""
    from repro.serve import MetricsRegistry

    reg = MetricsRegistry()
    reg.inc("x_total", 2.0, tenant="a")
    reg.inc("x_total", 3.0, tenant="b")
    assert reg.total("x_total") == 5.0
    with pytest.raises(ValueError, match="label names"):
        reg.inc("x_total", tenannt="a")
    with pytest.raises(ValueError, match="is a counter"):
        reg.observe("x_total", 1.0, tenant="a")


def test_tracer_slo_tagging_on_simclock():
    """SLO-violating records are tagged from their latency — now the
    host-clock latency, no longer SimClock's — the always-capture rule
    for slow requests."""
    tr_fast = Tracer(slo_ms=10.0)
    t = tr_fast.begin("query", "t", 1)
    t.span("queue", "queue", 0.0, 0.0)
    t.span("lane", "lane", 0.0, 0.02)
    tr_fast.finish(t, status=200, ru=1.0, latency_ms=20.0, t0_s=0.0,
                   t1_s=0.02)
    rec = tr_fast.recorder.records()[0]
    assert "slo_violation" in rec["anomalies"]
    validate_trace_record(rec)
