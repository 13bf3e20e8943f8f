"""Fault-tolerance plane: kill-and-recover parity, WAL damage, deadlines,
partial-result degradation, replica rebuild LSN capture."""
import numpy as np
import pytest
from proptest import given, settings
from proptest import strategies as st

from repro.core import GraphConfig
from repro.partition import Collection, CollectionConfig, ReplicaSet
from repro.partition.fanout import AllPartitionsFailed, compile_partition_filter
from repro.partition.partitioner import PhysicalPartition, hash_key
from repro.serve import (DeadlineExceeded, EngineConfig, F, VectorQuery,
                         VectorCollectionService, validate_trace_record)
from repro.serve.vector_engine import VectorServeEngine
from repro.store.codec import WalCorruption
from repro.store.faults import (CrashError, FaultPlan, corrupt_record,
                                recovery_invariants, torn_tail)
from repro.store.provider import StoreProviderSet

UPSERT_BARRIERS = ("upsert:begin", "upsert:post_index", "upsert:pre_commit")
DELETE_BARRIERS = ("delete:begin", "delete:post_props", "delete:pre_commit")
SPLIT_BARRIERS = ("split:begin", "split:mid_rehome", "split:pre_commit")
MERGE_BARRIERS = ("merge:begin", "merge:mid", "merge:pre_commit")

DIM = 8


def _graph(cap=96):
    return GraphConfig(capacity=cap, R=8, M=4, L_build=16, L_search=24,
                       bootstrap_sample=16, refine_sample=10**9, batch_size=8)


def _partitions(seed, n_parts, n0=20):
    """``n_parts`` identically-constructed partitions holding the same n0
    docs (with property terms), plus the rng/data used to build them."""
    cc = CollectionConfig(dim=DIM, graph=_graph(),
                          max_vectors_per_partition=80)
    parts = [PhysicalPartition(cc, 0, 1 << 32, 0) for _ in range(n_parts)]
    rng = np.random.RandomState(seed)
    data = rng.randn(n0, DIM).astype(np.float32)
    ids = list(range(n0))
    hashes = [hash_key(i) for i in ids]
    props = [(("cat", i % 3),) for i in range(n0)]
    for p in parts:
        p.insert(ids, hashes, data, props=props)
    return parts, rng, data


def _fresh_like(pv) -> StoreProviderSet:
    return StoreProviderSet(pv.neighbors.shape[0], pv.neighbors.shape[1],
                            pv.codes.shape[1], pv.vectors.shape[1])


# ---------------------------------------------------------------------------
# kill-and-recover: crash at any barrier → durable state == uncrashed twin
# ---------------------------------------------------------------------------


@settings(max_examples=12, deadline=None)
@given(seed=st.integers(0, 10_000),
       barrier=st.sampled_from(list(UPSERT_BARRIERS + DELETE_BARRIERS)),
       extra=st.integers(1, 5))
def test_property_kill_and_recover_upsert_delete(seed, barrier, extra):
    """Crash an upsert/delete at a random barrier: recovery from the
    durable bytes (checkpoint + committed WAL) must equal a twin that
    never attempted the interrupted op — bit for bit, terms included."""
    parts, rng, _data = _partitions(seed, 2)
    subject, twin = parts
    snap = subject.providers.snapshot_bytes()  # checkpoint
    # committed post-checkpoint ops land on BOTH sides
    new_ids = list(range(20, 20 + extra))
    vecs = rng.randn(extra, DIM).astype(np.float32)
    for p in (subject, twin):
        p.insert(new_ids, [hash_key(i) for i in new_ids], vecs,
                 props=[(("cat", i % 3),) for i in new_ids])
    # the victim op runs ONLY on the subject, with a crash armed
    FaultPlan(seed=seed).arm(barrier).attach(subject.providers)
    with pytest.raises(CrashError):
        if barrier.startswith("upsert"):
            v = rng.randn(2, DIM).astype(np.float32)
            subject.insert([40, 41], [hash_key(40), hash_key(41)], v,
                           props=[(("cat", 0),), (("cat", 1),)])
        else:
            subject.delete([new_ids[0]])
    # the process died: only the durable bytes survive
    wal = subject.providers.wal_bytes()
    fresh = _fresh_like(subject.providers)
    applied = fresh.recover(snap, wal)
    assert applied == subject.providers.committed  # crashed op left no record
    recovery_invariants(fresh, twin.providers)


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 10_000),
       barrier=st.sampled_from(list(SPLIT_BARRIERS + MERGE_BARRIERS)))
def test_property_split_merge_crash_is_all_or_nothing(seed, barrier):
    """A crash anywhere inside split/merge (before the routing swap) must
    leave the collection untouched: same partitions, same durable state as
    a twin collection that never attempted the operation."""
    def build():
        g = _graph(160)
        cc = CollectionConfig(dim=DIM, graph=g,
                              max_vectors_per_partition=120,
                              initial_partitions=2)
        col = Collection(cc)
        rng = np.random.RandomState(seed)
        data = rng.randn(40, DIM).astype(np.float32)
        col.insert(list(range(40)), [f"pk{i}" for i in range(40)], data,
                   props=[(("cat", i % 2),) for i in range(40)])
        return col

    col, twin = build(), build()
    FaultPlan(seed=seed).arm(barrier).attach(col.partitions[0].providers)
    with pytest.raises(CrashError):
        if barrier.startswith("split"):
            col.split(0)
        else:
            col.merge(0)
    assert len(col.partitions) == 2
    assert col.splits == 0 and col.merges == 0
    assert col.num_docs == twin.num_docs
    for ps, pt in zip(col.partitions, twin.partitions):
        recovery_invariants(ps.providers, pt.providers)


def test_shard_rekey_crash_keeps_old_copy():
    """Re-homing a doc (re-upsert under a pk owned by another partition)
    starts with a delete in the old owner; a crash there must leave the
    committed copy intact — no partition ends up without the doc."""
    g = _graph(160)
    cc = CollectionConfig(dim=DIM, graph=g, max_vectors_per_partition=120,
                          initial_partitions=2)
    col = Collection(cc)
    rng = np.random.RandomState(3)
    data = rng.randn(10, DIM).astype(np.float32)
    pks = [f"pk{i}" for i in range(10)]
    col.insert(list(range(10)), pks, data)
    owner = col.owner_of(0)
    # find a pk the OTHER partition owns → the re-upsert must re-home
    other = next(p for p in col.partitions if p is not owner)
    new_pk = next(f"alt{i}" for i in range(1000)
                  if other.owns(hash_key(f"alt{i}")))
    snap = owner.providers.snapshot_bytes()
    FaultPlan().arm("delete:begin").attach(owner.providers)
    with pytest.raises(CrashError):
        col.insert([0], [new_pk], data[0][None, :])
    fresh = _fresh_like(owner.providers)
    fresh.recover(snap, owner.providers.wal_bytes())
    slot = owner.index.doc_to_slot[0]
    assert fresh.live[slot], "crashed re-key delete must not commit"
    np.testing.assert_array_equal(fresh.vectors[slot],
                                  owner.providers.vectors[slot])


def test_recovered_state_serves_identical_queries():
    """Query / pagination / filtered parity: a node restarted from the
    recovered durable state answers exactly like the uncrashed twin."""
    parts, rng, data = _partitions(17, 3)
    subject, twin, restarted = parts
    snap = subject.providers.snapshot_bytes()
    extra = rng.randn(4, DIM).astype(np.float32)
    ids = [30, 31, 32, 33]
    for p in parts:
        p.insert(ids, [hash_key(i) for i in ids], extra,
                 props=[(("cat", i % 3),) for i in ids])
        p.delete([2])
    FaultPlan().arm("upsert:post_index").attach(subject.providers)
    with pytest.raises(CrashError):
        subject.insert([50], [hash_key(50)],
                       rng.randn(1, DIM).astype(np.float32), props=[()])
    fresh = _fresh_like(subject.providers)
    fresh.recover(snap, subject.providers.wal_bytes())
    recovery_invariants(fresh, twin.providers)
    # graft the recovered durable state into the restarted node (its host
    # state was rebuilt from the same committed prefix)
    rp = restarted.providers
    rp.neighbors[:] = fresh.neighbors
    rp.codes[:] = fresh.codes
    rp.versions[:] = fresh.versions
    rp.live[:] = fresh.live
    rp.vectors[:] = fresh.vectors
    rp.tree = fresh.tree
    rp._dirty()
    q = data[:4] + 0.01
    ids_t, d_t, _, _ = twin.search_batch(q, 5)
    ids_r, d_r, _, _ = restarted.search_batch(q, 5)
    np.testing.assert_array_equal(ids_t, ids_r)
    np.testing.assert_allclose(d_t, d_r)
    # filtered parity
    pred = F.eq("cat", 1)
    mask_t, _, _ = compile_partition_filter(twin, pred)
    mask_r, _, _ = compile_partition_filter(restarted, pred)
    np.testing.assert_array_equal(mask_t, mask_r)
    fids_t, fd_t, _, _ = twin.filtered_search_batch(q, 5, mask_t)
    fids_r, fd_r, _, _ = restarted.filtered_search_batch(q, 5, mask_r)
    np.testing.assert_array_equal(fids_t, fids_r)
    # pagination parity
    st_t = twin.start_pagination(q[0])
    st_r = restarted.start_pagination(q[0])
    pids_t, pd_t, _, _, _ = twin.next_page(q[0], st_t, 5)
    pids_r, pd_r, _, _, _ = restarted.next_page(q[0], st_r, 5)
    np.testing.assert_array_equal(pids_t, pids_r)


# ---------------------------------------------------------------------------
# WAL damage: torn tails truncate, interior corruption is rejected
# ---------------------------------------------------------------------------


def _provider_with_records(n=6):
    pv = StoreProviderSet(64, 8, 4, DIM)
    from repro.core.providers import Context
    ctx = Context()
    snap = pv.snapshot_bytes()
    rng = np.random.RandomState(0)
    for i in range(n):  # each bare write auto-commits one WAL record
        pv.set_full(ctx, np.array([i]), rng.randn(1, DIM).astype(np.float32))
    return pv, snap


def test_torn_tail_truncates_to_last_whole_record():
    pv, snap = _provider_with_records(6)
    wal = pv.wal_bytes()
    torn = torn_tail(wal, np.random.RandomState(1), nbytes=3)
    fresh = _fresh_like(pv)
    applied = fresh.recover(snap, torn)
    assert fresh.recovered_torn_tail
    assert applied == pv.committed - 1
    # the truncated prefix equals a twin that only committed n-1 records
    twin, _ = _provider_with_records(5)
    recovery_invariants(fresh, twin)


def test_corrupted_final_record_is_torn_not_fatal():
    pv, snap = _provider_with_records(4)
    wal = corrupt_record(pv.wal_bytes(), np.random.RandomState(2), index=3)
    fresh = _fresh_like(pv)
    applied = fresh.recover(snap, wal)
    assert fresh.recovered_torn_tail and applied == pv.committed - 1


def test_corrupted_interior_record_raises():
    pv, snap = _provider_with_records(5)
    wal = corrupt_record(pv.wal_bytes(), np.random.RandomState(3), index=1)
    fresh = _fresh_like(pv)
    with pytest.raises(WalCorruption):
        fresh.recover(snap, wal)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(2, 8))
def test_property_torn_tail_always_recovers(seed, n):
    pv, snap = _provider_with_records(n)
    torn = torn_tail(pv.wal_bytes(), np.random.RandomState(seed))
    fresh = _fresh_like(pv)
    applied = fresh.recover(snap, torn)
    assert applied == pv.committed - 1  # at most the final record is lost
    twin, _ = _provider_with_records(n - 1)
    recovery_invariants(fresh, twin)


# ---------------------------------------------------------------------------
# replica rebuild: LSN captured with the bytes, not by fiat
# ---------------------------------------------------------------------------


def test_rebuild_applied_lsn_matches_capture():
    """A rebuild from an old capture must come back AT the capture's LSN —
    behind the set — not claim the set's current LSN by fiat."""
    cc = CollectionConfig(dim=DIM, graph=_graph(),
                          max_vectors_per_partition=80)
    part = PhysicalPartition(cc, 0, 1 << 32, 0)
    rs = ReplicaSet(part, num_replicas=3)
    rng = np.random.RandomState(5)
    rs.insert([0, 1], [hash_key(0), hash_key(1)],
              rng.randn(2, DIM).astype(np.float32))
    rs.insert([2], [hash_key(2)], rng.randn(1, DIM).astype(np.float32))
    cap = rs.capture()
    set_lsn_at_capture, store_lsn_at_capture = cap[2], cap[3]
    rs.insert([3], [hash_key(3)], rng.randn(1, DIM).astype(np.float32))
    rs.kill(1, now_s=0.0)
    fresh = rs.rebuild(1, capture=cap)
    assert rs.replicas[1].applied_lsn == set_lsn_at_capture == 2
    assert rs.replicas[1].applied_lsn < rs.lsn
    assert fresh.committed == store_lsn_at_capture
    assert not fresh.live[part.index.doc_to_slot[3]]  # post-capture write absent


def test_probe_dead_rebuild_matches_live_state():
    """The cooldown re-probe path rebuilds through real recovery; with no
    writes since capture the revived replica is bit-identical to live."""
    cc = CollectionConfig(dim=DIM, graph=_graph(),
                          max_vectors_per_partition=80)
    part = PhysicalPartition(cc, 0, 1 << 32, 0)
    rs = ReplicaSet(part, num_replicas=3, reprobe_after_s=1.0)
    rng = np.random.RandomState(6)
    rs.insert(list(range(8)), [hash_key(i) for i in range(8)],
              rng.randn(8, DIM).astype(np.float32))
    rs.kill(2, now_s=0.0)
    assert rs.probe_dead(now_s=5.0) == [2]
    assert rs.replicas[2].alive and rs.recoveries == 1
    fresh = rs.rebuild(2)
    recovery_invariants(fresh, part.providers)


# ---------------------------------------------------------------------------
# deadlines (408) and partial-result degradation through the engine
# ---------------------------------------------------------------------------


def _service(parts=2, replicas=2, n=60, deadline_ms=None,
             dispatch_mode="serial"):
    svc = VectorCollectionService(
        dim=DIM, graph=_graph(160), max_vectors_per_partition=200,
        initial_partitions=parts, replicas=replicas,
        engine_cfg=EngineConfig(max_batch=4, default_deadline_ms=deadline_ms,
                                dispatch_mode=dispatch_mode),
    )
    rng = np.random.RandomState(9)
    data = rng.randn(n, DIM).astype(np.float32)
    svc.upsert([{"id": i, "cat": i % 3} for i in range(n)], data)
    return svc, data


def test_deadline_expired_in_queue_is_408_with_refund():
    svc, data = _service()
    eng = svc.engine
    gov = eng.tenant_governor("t0")
    rid = eng.submit_query(data[0], k=5, tenant="t0", deadline_ms=5.0)
    consumed_reserved = gov.consumed
    assert consumed_reserved > 0  # reservation taken at admission
    eng.clock.advance(0.050)  # 50 ms > 5 ms budget, still queued
    eng.pump(force=True)
    resp = eng.pop_response(rid)
    assert resp.status == 408 and resp.ids is None
    assert resp.wait_ms >= 5.0 and resp.latency_ms == resp.wait_ms
    assert gov.consumed == 0.0  # reservation fully refunded
    assert gov.refunded == consumed_reserved
    assert eng.metrics.queries_deadline == 1
    assert eng.obs.counter_value("serve_deadline_total", tenant="t0") == 1
    assert eng.obs.counter_value("serve_requests_total", tenant="t0",
                                 kind="query", status="408") == 1
    # the 408 trace reconciles: root spans tile the waited interval
    recs = [r for r in eng.tracer.recorder.records() if r["status"] == 408]
    assert len(recs) == 1
    validate_trace_record(recs[0])
    assert "deadline_exceeded" in recs[0]["anomalies"]
    assert eng.observability_summary()["per_tenant"]["t0"][
        "deadline_exceeded"] == 1


def test_deadline_not_expired_serves_normally():
    svc, data = _service(deadline_ms=10_000.0)
    r = svc.query(VectorQuery(vector=data[1], k=5,
                              deadline_ms=5_000.0))
    assert r.complete and len(r.ids) == 5


def test_deadline_exceeded_raises_through_service():
    svc, data = _service()
    eng = svc.engine
    # arrival back-dated so the budget is already blown at submit+pump
    rid = eng.submit_query(data[2], k=5, arrival_s=eng.clock.now(),
                           deadline_ms=1.0)
    eng.clock.advance(0.01)
    eng.pump(force=True)
    assert eng.pop_response(rid).status == 408
    with pytest.raises(DeadlineExceeded):
        eng.clock.advance(0.01)
        q = VectorQuery(vector=data[2], k=5, deadline_ms=0.0)
        svc.query(q)


def test_degraded_fanout_merges_survivors():
    svc, data = _service(parts=2, replicas=2)
    eng = svc.engine
    down = svc.replica_sets[0]
    for rep in down.replicas:  # total loss of one partition's replica set
        rep.alive = False
    r = svc.query(VectorQuery(vector=data[3], k=5, tenant="t1"))
    assert not r.complete
    assert "+degraded[" in r.plan
    assert (np.asarray(r.ids) >= 0).any()  # survivors still answered
    # returned ids all live in the surviving partition
    up = svc.replica_sets[1].partition
    got = [int(i) for i in np.asarray(r.ids).ravel() if i >= 0]
    assert all(g in up.doc_pk for g in got)
    assert eng.metrics.queries_degraded >= 1
    assert eng.obs.counter_value("serve_degraded_total", tenant="t1") >= 1
    assert eng.observability_summary()["per_tenant"]["t1"]["degraded"] >= 1
    # degraded traces carry the anomaly tag + a failure span per lost pid
    recs = [r2 for r2 in eng.tracer.recorder.records()
            if "degraded" in r2.get("anomalies", ())]
    assert recs
    validate_trace_record(recs[-1])
    fail_spans = [s for s in recs[-1]["spans"]
                  if s["attrs"].get("failed")]
    assert fail_spans and fail_spans[0]["attrs"]["pid"] == down.partition.pid


def test_all_partitions_down_is_hard_error_with_refund():
    svc, data = _service(parts=2, replicas=2)
    eng = svc.engine
    for rs in svc.replica_sets:
        for rep in rs.replicas:
            rep.alive = False
    gov = eng.tenant_governor("t2")
    before = gov.consumed
    with pytest.raises(AllPartitionsFailed):
        svc.query(VectorQuery(vector=data[4], k=5, tenant="t2"))
    assert gov.consumed == before  # reservation refunded on hard failure


def test_degraded_exact_scan():
    svc, data = _service(parts=2, replicas=2)
    for rep in svc.replica_sets[0].replicas:
        rep.alive = False
    r = svc.query(VectorQuery(vector=data[5], k=5, exact=True))
    assert not r.complete and "+degraded[" in r.plan


@pytest.mark.parametrize("plan", ["graph", "filtered", "exact", "spmd"])
@pytest.mark.parametrize("fault", ["device", "partition"])
def test_device_fault_propagates_partition_fault_degrades(monkeypatch, plan,
                                                          fault):
    """A device fault (``JaxRuntimeError``: a compile failure, HBM
    exhausted) inside one partition's search is raised to the caller by
    every fan-out handler; an ordinary partition fault still degrades the
    answer to the survivors."""
    import jax

    from repro.serve import vector_engine

    svc, data = _service(parts=2, replicas=2,
                         dispatch_mode="spmd" if plan == "spmd" else "serial")
    part = svc.collection.partitions[0]
    err = (jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: injected")
           if fault == "device" else RuntimeError("injected"))

    def boom(*_a, **_kw):
        raise err

    if plan == "exact":
        real = vector_engine.fmod.brute_force
        calls = []

        def first_partition_fails(*a, **kw):  # partition 0 scans first
            calls.append(1)
            if len(calls) == 1:
                raise err
            return real(*a, **kw)

        first_partition_fails._cache_size = real._cache_size  # telemetry
        monkeypatch.setattr(vector_engine.fmod, "brute_force",
                            first_partition_fails)
    elif plan == "filtered":
        monkeypatch.setattr(part, "filtered_search_batch", boom)
    else:
        monkeypatch.setattr(part, "search_batch", boom)
        if plan == "spmd":  # unbuilt graphs take the spmd host fallback
            monkeypatch.setattr(part.index, "_graph_built", False)
    q = VectorQuery(vector=data[6], k=5, exact=plan == "exact",
                    filter=F.eq("cat", 1) if plan == "filtered" else None)
    if fault == "device":
        with pytest.raises(jax.errors.JaxRuntimeError):
            svc.query(q)
    else:
        r = svc.query(q)
        assert not r.complete and f"+degraded[{part.pid}]" in r.plan
