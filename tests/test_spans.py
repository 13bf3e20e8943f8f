"""Measured spans and host-path counters: the ``repro.core.spans``
primitive, its place in the profiler's trace, the engine's answer-hold
counter, the providers' upload counters, and the host-clock records built
from the spans."""
import time

import jax
import numpy as np
import pytest

from repro.core import GraphConfig
from repro.core.index import DiskANNIndex
from repro.core.spans import collect, span, totals
from repro.serve import (EngineConfig, VectorCollectionService,
                         validate_trace_record)

from conftest import clustered_data


def make_service(n=300, dim=16, seed=5, **engine_kw):
    rng = np.random.RandomState(seed)
    g = GraphConfig(capacity=n + 200, R=16, M=8, L_build=32, L_search=32,
                    bootstrap_sample=96, refine_sample=10**9, batch_size=64)
    svc = VectorCollectionService(dim=dim, graph=g,
                                  max_vectors_per_partition=n + 100,
                                  engine_cfg=EngineConfig(
                                      admission_control=False, **engine_kw))
    data = clustered_data(rng, n, dim)
    svc.upsert([{"id": i} for i in range(n)], data)
    return svc, data


@pytest.fixture(scope="module")
def service():
    return make_service()


# ---------------------------------------------------------------------------
# the primitive
# ---------------------------------------------------------------------------

def test_span_totals_are_per_name_and_monotone():
    """Each exit adds one to the name's count, its duration to the total,
    and raises the maximum if it is larger; nothing resets them."""
    before = totals().get("test.outer", (0, 0, 0))
    for sleep_s in (0.0, 0.003, 0.0):
        with span("test.outer", k=1):
            time.sleep(sleep_s)
    c, t, m = totals()["test.outer"]
    assert c == before[0] + 3
    assert t - before[1] >= 3_000_000  # the 3 ms sleep, in ns
    assert m >= 3_000_000 and m <= t


def test_collect_records_parents_and_meta():
    """Inside ``collect()`` every span is recorded in the order it opened,
    its parent the innermost span open then; outside, nothing is."""
    with span("test.before"):
        pass
    with collect() as recs:
        with span("test.a", x=1):
            with span("test.b"):
                with span("test.c", y="z"):
                    pass
            with span("test.d"):
                pass
        with span("test.e"):
            pass
    with span("test.after"):
        pass
    names = [r[0] for r in recs]
    assert names == ["test.a", "test.b", "test.c", "test.d", "test.e"]
    assert [r[3] for r in recs] == [-1, 0, 1, 0, -1]
    assert recs[0][4] == {"x": 1} and recs[2][4] == {"y": "z"}
    for name, t0, t1, parent, _ in recs:
        assert 0 < t0 <= t1
        if parent >= 0:  # a child lies inside its parent
            assert recs[parent][1] <= t0 and t1 <= recs[parent][2]


def test_collect_restores_on_error():
    """A collector that exits by an exception leaves no open state behind:
    the next collector starts from a clean stack."""
    with pytest.raises(RuntimeError):
        with collect():
            with span("test.fail"):
                raise RuntimeError("boom")
    with collect() as recs:
        with span("test.next"):
            pass
    assert [(r[0], r[3]) for r in recs] == [("test.next", -1)]


# ---------------------------------------------------------------------------
# the profiler's trace
# ---------------------------------------------------------------------------

def _profiled_events(log_dir):
    from jax.profiler import ProfileData

    found = sorted(log_dir.glob("plugins/profile/*/*.xplane.pb"))
    assert found, "the profiler wrote no trace"
    pd = ProfileData.from_file(str(found[-1]))
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                out.append((e.name, float(e.start_ns),
                            float(e.start_ns + e.duration_ns),
                            {k: v for k, v in e.stats}))
    return out


@pytest.mark.filterwarnings("ignore:builtin type event_stats")
def test_pump_spans_nest_in_the_profiler_trace(service, tmp_path):
    """A ``pump`` traced under ``jax.profiler`` inside a harness span
    shows engine.pump ⊃ serve.dispatch ⊃ search.partition ⊃ search.graph
    in the xplane, with each span's metadata as event stats."""
    svc, data = service
    eng = svc.engine
    for q in data[:4]:  # compile outside the trace
        eng.submit_query(q + 0.01, k=5)
    eng.pump(force=True)
    rids = [eng.submit_query(q + 0.02, k=5) for q in data[4:8]]
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("engine.pump"):
            eng.pump(force=True)
    finally:
        jax.profiler.stop_trace()
    assert all(eng.pop_response(r).status == 200 for r in rids)

    ev = _profiled_events(tmp_path)

    def one(name):
        hits = [e for e in ev if e[0] == name]
        assert len(hits) == 1, (name, len(hits))
        return hits[0]

    chain = [one(n) for n in ("engine.pump", "serve.dispatch",
                              "search.partition", "search.graph")]
    for (_, s0, e0, _), (_, s1, e1, _) in zip(chain, chain[1:]):
        assert s0 <= s1 and e1 <= e0
    assert chain[1][3]["batch"] == 4
    assert chain[2][3]["pid"] == svc.collection.partitions[0].pid
    assert chain[3][3]["L"] == 25  # the engine's L = 5 x k, k = 5


# ---------------------------------------------------------------------------
# host-path counters
# ---------------------------------------------------------------------------

def test_upload_bytes_count_what_materialize_reuploads():
    """``upload_bytes`` grows by exactly the ``nbytes`` of each array the
    next ``materialize`` copies to the device: after a delete (which
    writes ``live`` and then drops every device copy the repair kernel
    donated), the delete's own ``live`` and then all five arrays."""
    rng = np.random.RandomState(2)
    cfg = GraphConfig(capacity=256, R=12, M=8, L_build=24, L_search=24,
                      bootstrap_sample=64, refine_sample=10**9,
                      batch_size=32)
    idx = DiskANNIndex(cfg, 16)
    idx.insert(list(range(160)), clustered_data(rng, 160, 16))
    pv = idx.pv
    pv.materialize()  # every array on the device
    n0, b0 = pv.uploads, pv.upload_bytes
    pv.materialize()
    assert (pv.uploads, pv.upload_bytes) == (n0, b0)  # cached: no copy
    idx.delete([7])
    assert pv.uploads - n0 == 1
    assert pv.upload_bytes - b0 == pv.live.nbytes
    n1, b1 = pv.uploads, pv.upload_bytes
    pv.materialize()
    assert pv.uploads - n1 == len(pv.FIELDS)
    assert pv.upload_bytes - b1 == sum(getattr(pv, f).nbytes
                                       for f in pv.FIELDS)


def test_answer_hold_counts_only_interleaved_writes(service):
    """``answer_hold_ns`` is 0 when ``pump`` stores answers and applies no
    write, and at least answers × write time when it interleaves one; an
    idle pump that applies a write holds no answer."""
    svc, data = service
    eng = svc.engine
    m = eng.metrics
    a0, h0 = m.answers, m.answer_hold_ns
    for q in data[10:13]:
        eng.submit_query(q + 0.01, k=5)
    eng.pump(force=True)
    assert (m.answers - a0, m.answer_hold_ns - h0) == (3, 0)

    def slow_write():
        time.sleep(0.02)
        return 1.0

    eng.submit_ingest("upsert", slow_write, 1)
    eng.pump()  # idle: the write is applied, no answer waits for it
    assert (m.answers - a0, m.answer_hold_ns - h0) == (3, 0)

    eng.submit_ingest("upsert", slow_write, 1)
    for q in data[13:15]:
        eng.submit_query(q + 0.01, k=5)
    eng.pump(force=True)
    assert m.answers - a0 == 5
    assert m.answer_hold_ns - h0 >= 2 * 20_000_000
    snap = eng.snapshot()
    assert snap["answers"] == m.answers
    assert snap["answer_hold_ns"] == m.answer_hold_ns
    assert snap["upload_bytes"] == sum(
        p.providers.upload_bytes for p in svc.collection.partitions)


# ---------------------------------------------------------------------------
# records on the host clock
# ---------------------------------------------------------------------------

def test_batch_and_query_records_on_the_host_clock(service):
    """A served query's record tiles [submit, answer] on the host clock
    and names its ``batch`` record; the batch record is rooted at the
    measured ``serve.dispatch`` and validates; a child moved outside its
    parent, or a record without ``batch_id``, is refused."""
    svc, data = service
    eng = svc.engine
    eng.tracer.reset()
    t_before = time.perf_counter()
    rids = [eng.submit_query(q + 0.01, k=5) for q in data[20:26]]
    eng.pump(force=True)
    elapsed_ms = (time.perf_counter() - t_before) * 1e3
    for r in rids:
        eng.pop_response(r)
    recs = eng.tracer.recorder.records()
    queries = [r for r in recs if r["kind"] == "query"]
    (batch,) = [r for r in recs if r["kind"] == "batch"]
    assert len(queries) == 6
    for q in queries:
        validate_trace_record(q)
        assert q["batch_id"] == batch["trace_id"]
        assert 0 < q["latency_ms"] <= elapsed_ms
    validate_trace_record(batch)
    assert batch["spans"][0]["name"] == "serve.dispatch"
    assert batch["spans"][0]["attrs"] == {"batch": 6, "bucket": 8}
    assert batch["latency_ms"] == pytest.approx(batch["spans"][0]["dur_ms"])

    bad = dict(batch, spans=[dict(s) for s in batch["spans"]])
    child = next(i for i, s in enumerate(bad["spans"]) if s["parent"] == 0)
    bad["spans"][child]["t1_s"] = bad["spans"][0]["t1_s"] + 1.0
    with pytest.raises(ValueError, match="outside its parent"):
        validate_trace_record(bad)
    no_id = {k: v for k, v in queries[0].items() if k != "batch_id"}
    with pytest.raises(ValueError, match="batch_id"):
        validate_trace_record(no_id)
