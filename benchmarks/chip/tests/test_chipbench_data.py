"""The generator, exact percentiles, and open-loop timing from due time."""
import time
from types import SimpleNamespace

import numpy as np
import pytest

import chipbench_testing  # noqa: F401  (sys.path)
from chipbench import data as dat
from chipbench.cell import percentile
from chipbench.window import Profile, drive

DATA = {"latent": 16, "clusters": 64, "geometry_seed": 0}
BIG_SEED = 2**31 + 987_654_321


@pytest.mark.parametrize("cluster_order", [False, True])
def test_corpus_is_the_same_docs_in_a_seeded_order(cluster_order):
    a = dat.make_corpus(BIG_SEED, 48, DATA, 300, 100, cluster_order)
    b = dat.make_corpus(BIG_SEED, 48, DATA, 300, 100, cluster_order)
    c = dat.make_corpus(BIG_SEED + 1, 48, DATA, 300, 100, cluster_order)
    assert np.array_equal(a.vectors, b.vectors)
    assert np.array_equal(a.clusters, b.clusters)
    assert not np.array_equal(a.vectors, c.vectors)
    assert a.vectors.dtype == np.float32 and a.vectors.shape == (400, 48)
    # every seed writes the same docs, in another order
    assert np.array_equal(np.unique(a.vectors, axis=0),
                          np.unique(c.vectors, axis=0))
    if cluster_order:
        assert np.all(np.diff(a.clusters) >= 0)
        # the loaded docs are the same set too: whole clusters in order
        assert np.array_equal(a.clusters, c.clusters)
        assert np.array_equal(np.unique(a.vectors[:300], axis=0),
                              np.unique(c.vectors[:300], axis=0))


def test_queries_are_the_same_draws_in_a_seeded_order():
    q1 = dat.make_queries(BIG_SEED, 32, DATA, 50)
    q2 = dat.make_queries(BIG_SEED, 32, DATA, 50)
    q3 = dat.make_queries(5, 32, DATA, 50)
    corpus = dat.make_corpus(BIG_SEED, 32, DATA, 200, 0, False)
    assert np.array_equal(q1, q2)
    assert not np.array_equal(q1, q3)
    assert np.array_equal(np.unique(q1, axis=0), np.unique(q3, axis=0))
    assert not any((corpus.vectors == q).all(1).any() for q in q1)
    warm = dat.make_queries(BIG_SEED, 32, DATA, 50, stream=4)
    assert not np.array_equal(np.unique(q1, axis=0), np.unique(warm, axis=0))


def test_arrivals_same_work_for_every_seed():
    a = dat.arrival_offsets(BIG_SEED, 400.0, 30.0)
    b = dat.arrival_offsets(BIG_SEED, 400.0, 30.0)
    c = dat.arrival_offsets(7, 400.0, 30.0)
    assert np.array_equal(a, b)
    assert len(a) == len(c) == 12_000
    assert not np.array_equal(a, c)
    for o in (a, c):
        assert np.all(np.diff(o) > 0) and 0 < o[0] and o[-1] < 30.0
    # the same multiset of gaps, in another order
    assert np.allclose(np.sort(np.diff(np.r_[0, a])),
                       np.sort(np.diff(np.r_[0, c])))
    # exponential gaps: mean 1/rate, coefficient of variation ~1
    g = np.diff(np.r_[0, a])
    assert abs(g.mean() * 400 - 1) < 0.01 and 0.9 < g.std() / g.mean() < 1.1


def test_on_off_arrivals_keep_the_mean_rate_and_the_off_periods_quiet():
    on_off = {"kind": "on_off", "on_s": 0.5, "off_s": 1.5}
    a = dat.arrival_offsets(BIG_SEED, 100.0, 30.0, on_off)
    b = dat.arrival_offsets(7, 100.0, 30.0, on_off)
    assert len(a) == len(b) == 3000 and not np.array_equal(a, b)
    for o in (a, b):
        assert np.all(np.diff(o) > 0) and 0 <= o[0] and o[-1] < 30.0
        assert np.all(o % 2.0 < 0.5)  # only in the on periods
    # four times the rate inside the bursts: every on period is used
    assert set(np.floor(a / 2.0).astype(int)) == set(range(15))
    assert np.array_equal(dat.arrival_offsets(7, 100.0, 30.0, None),
                          dat.arrival_offsets(7, 100.0, 30.0,
                                              {"kind": "poisson"}))
    with pytest.raises(ValueError):
        dat.arrival_offsets(7, 100.0, 30.0, {"kind": "zipf"})


def test_live_query_clusters_avoid_the_deleted_end():
    corpus = dat.make_corpus(3, 16, DATA, 2000, 500, cluster_order=True)
    keep = dat.live_query_clusters(corpus, 500)
    assert len(keep)
    assert not set(keep) & set(corpus.clusters[:500].tolist())


def test_percentile_is_exact_nearest_rank():
    v = np.arange(1, 101, dtype=float)
    assert percentile(v, 50) == 50.0
    assert percentile(v, 95) == 95.0
    assert percentile(v[::-1], 95) == 95.0
    assert percentile([3.0], 95) == 3.0
    assert percentile(np.r_[np.ones(99), np.inf], 95) == 1.0
    assert percentile(np.r_[np.ones(90), np.full(10, np.inf)], 95) == np.inf


class FakeEngine:
    """Answers each pumped batch after ``service_s`` of host time."""

    def __init__(self, service_s, max_batch):
        self.cfg = SimpleNamespace(max_batch=max_batch)
        self.service_s = service_s
        self.queue, self.out, self.rid, self.pumps = [], {}, 0, 0
        self.ingest_backlog = 0
        self.metrics = SimpleNamespace(hops_weighted=0.0, hops_lanes=0)
        self.collection = SimpleNamespace(partitions=[])  # no partitions

    def submit_query(self, q, k, L, predicate=None):
        self.rid += 1
        self.queue.append(self.rid)
        return self.rid

    def pump(self, force=False):
        self.pumps += 1
        time.sleep(self.service_s)
        self.metrics.hops_weighted += 20.0 * len(self.queue)
        self.metrics.hops_lanes += len(self.queue)
        for rid in self.queue:
            self.out[rid] = SimpleNamespace(
                status=200, complete=True, batch_size=len(self.queue),
                ids=np.arange(3), dists=np.arange(3, dtype=np.float32))
        self.queue = []

    def pop_response(self, rid):
        return self.out.pop(rid, None)


def test_open_loop_latency_counts_the_wait_from_due_time():
    eng = FakeEngine(service_s=0.05, max_batch=1)
    offsets = np.array([0.01, 0.011, 0.012, 0.2])
    w = drive(SimpleNamespace(engine=eng), np.zeros((4, 2), np.float32),
              offsets, seconds=0.3, k=3, L=3, n_live=10)
    lat = w.latency_ms
    # one query per batch, 50 ms each: the second and third wait for the
    # batches before them although they were due almost at once
    assert 50 <= lat[0] < 150
    assert lat[1] >= 99 and lat[2] >= 148
    assert lat[2] - lat[1] >= 48 and lat[1] - lat[0] >= 48
    assert 50 <= lat[3] < 150  # due after the backlog cleared
    assert np.all(w.done > w.sent) and np.all(w.sent >= w.due)
    assert w.batches == 4 and list(w.batch) == [1, 1, 1, 1]
    assert len(w.wake_late) >= 1


def test_due_queries_go_out_together_up_to_max_batch():
    eng = FakeEngine(service_s=0.05, max_batch=2)
    offsets = np.array([0.001, 0.002, 0.003, 0.004, 0.005])
    w = drive(SimpleNamespace(engine=eng), np.zeros((5, 2), np.float32),
              offsets, seconds=0.1, k=3, L=3, n_live=10)
    # the queries due while a batch runs go out together, two at a time
    assert max(w.batch) == 2 and w.batches == eng.pumps <= 3
    assert (w.status == 200).all()


class QuietProfile(Profile):
    """The span's bookkeeping without a profiler: stopping a real trace
    takes long, which is why it waits for the window to close."""

    def start_trace(self):
        self.t_start = time.perf_counter()

    def stop_trace(self):
        self.t_stop = time.perf_counter()


def test_the_traced_span_is_the_windows_end_and_counts_only_itself():
    eng = FakeEngine(service_s=0.01, max_batch=4)
    offsets = np.linspace(0.005, 0.39, 40)
    prof = QuietProfile(offset=0.3, seconds=0.1, log_dir="")
    w = drive(SimpleNamespace(engine=eng), np.zeros((40, 2), np.float32),
              offsets, seconds=0.4, k=3, L=3, n_live=10, profile=prof)
    assert prof.started and prof.ended
    assert w.t0 + 0.3 <= prof.t_start < w.t0 + 0.32
    # stopped after the window's last answer, not inside the loop
    assert prof.t_stop >= w.t_end and prof.t_stop >= w.done.max()
    lo, hi = prof.sent
    assert 0 < lo < hi == 40
    assert np.all(w.due[lo:hi] >= w.t0 + 0.29)
    assert prof.answered == hi - lo
    assert prof.counters["hops_lanes"] == hi - lo
    assert prof.counters["hops_weighted"] == 20.0 * (hi - lo)
    assert 0 < prof.batches < w.batches
