"""The reference, the comparison's numbers, and the control failing them."""
from types import SimpleNamespace

import numpy as np
import pytest

import chipbench_testing  # noqa: F401  (sys.path)
from chipbench import check as chk
from chipbench import data as dat
from chipbench.deploy import WriteStream

DATA = {"latent": 16, "clusters": 64, "geometry_seed": 0}


def brute(q, x, lo, hi, k):
    d = ((x[lo:hi].astype(np.float64) - q) ** 2).sum(1)
    return lo + np.argsort(d)[:k]


def test_exact_topk_follows_each_querys_live_range():
    corpus = dat.make_corpus(5, 64, DATA, 600, 300, cluster_order=True)
    q = dat.make_queries(5, 64, DATA, 40)
    rng = np.random.default_rng(0)
    # writes and deletes interleaved: each query sees its own live window
    lo = np.sort(rng.integers(0, 300, 40))
    hi = 600 + np.sort(rng.integers(0, 300, 40))
    ref = chk.exact_topk(q, corpus.vectors, lo, hi, 10, block=7)
    for i in range(40):
        assert list(ref.truth[i]) == list(brute(q[i], corpus.vectors,
                                                lo[i], hi[i], 10))
        far = ref.truth[i, -1]
        assert np.isclose(ref.kth[i], ((corpus.vectors[far] - q[i]) ** 2).sum(),
                          rtol=1e-5)
    assert chk.recall(ref.truth, ref.truth) == 1.0
    half = ref.truth.copy()
    half[:, 5:] = -1
    assert chk.recall(half, ref.truth) == 0.5
    # a doc deleted before a query's dispatch is a bad answer for it
    stale = ref.truth.copy()
    stale[0, 3] = lo[0] - 1 if lo[0] else hi[0]
    d = np.sort(np.abs(rng.standard_normal((40, 10))), 1)
    assert chk.bad_answers(ref.truth, d, lo, hi) == 0
    assert chk.bad_answers(stale, d, lo, hi) == 1


def test_bad_answers_counts_duplicates_missing_and_disorder():
    pos = np.tile(np.arange(10), (4, 1))
    d = np.tile(np.arange(10, dtype=np.float32), (4, 1))
    lo, hi = np.zeros(4, int), np.full(4, 100)
    pos[1, 2] = pos[1, 3]
    pos[2, 9] = -1
    d[3, [4, 5]] = d[3, [5, 4]]
    assert chk.bad_answers(pos, d, lo, hi) == 3


def test_f32_answers_pass_and_the_bf16_control_fails():
    corpus = dat.make_corpus(9, 128, DATA, 2000, 0, cluster_order=False)
    q = dat.make_queries(9, 128, DATA, 64)
    lo, hi = np.zeros(64, np.int64), np.full(64, 2000, np.int64)
    ref = chk.exact_topk(q, corpus.vectors, lo, hi, 10)
    # the program's path: float32 rows, elementwise squared differences
    x = corpus.vectors[ref.truth]
    d32 = ((q[:, None, :] - x) ** 2).sum(-1, dtype=np.float32)
    ok = chk.compare(q, corpus.vectors, ref.truth, d32,
                     np.full(64, 200), np.ones(64, bool), lo, hi, 10, 0.1)
    assert ok.correct and ok.recall == 1.0
    assert ok.numbers["dist_gap"][0] < chk.DIST_GAP_LIMIT / 10
    pos, dbf = chk.bf16_answers(q, corpus.vectors, lo, hi, 10, block=16)
    ctrl = chk.compare(q, corpus.vectors, pos, dbf,
                       np.full(64, 200), np.ones(64, bool), lo, hi, 10, 0.1)
    assert not ctrl.correct
    assert ctrl.numbers["dist_gap"][0] > 10 * chk.DIST_GAP_LIMIT
    # one answer altered where it is produced: a wrong doc with the
    # distance of the right one
    bad = ref.truth.copy()
    bad[7, 0] = ref.truth[7, -1] + 1 if ref.truth[7, -1] + 1 < 2000 else 0
    alt = chk.compare(q, corpus.vectors, bad, d32,
                      np.full(64, 200), np.ones(64, bool), lo, hi, 10, 0.1)
    assert not alt.correct


def test_a_search_that_misses_neighbours_fails_recall_miss():
    corpus = dat.make_corpus(9, 32, DATA, 1000, 0, cluster_order=False)
    q = dat.make_queries(9, 32, DATA, 20)
    lo, hi = np.zeros(20, np.int64), np.full(20, 1000, np.int64)
    ref = chk.exact_topk(q, corpus.vectors, lo, hi, 10)
    # the 2nd to 11th nearest instead of the 1st to 10th: 9 of 10 found
    far = chk.exact_topk(q, corpus.vectors, lo, hi, 11).truth[:, 1:]
    d = ((q[:, None, :] - corpus.vectors[far]) ** 2).sum(-1)
    c = chk.compare(q, corpus.vectors, far, d, np.full(20, 200),
                    np.ones(20, bool), lo, hi, 10, 0.05)
    assert c.numbers["recall_miss"][0] == pytest.approx(0.1)
    assert c.numbers["dist_gap"][0] < chk.DIST_GAP_LIMIT
    assert not c.correct
    d = ((q[:, None, :] - corpus.vectors[ref.truth]) ** 2).sum(-1)
    assert chk.compare(q, corpus.vectors, ref.truth, d, np.full(20, 200),
                       np.ones(20, bool), lo, hi, 10, 0.05).correct


def test_a_filter_bars_docs_from_the_reference_and_the_answers():
    corpus = dat.make_corpus(4, 32, DATA, 1000, 0, cluster_order=False)
    q = dat.make_queries(4, 32, DATA, 16)
    lo, hi = np.zeros(16, np.int64), np.full(16, 1000, np.int64)
    eligible = corpus.ids % 10 == 3
    ref = chk.exact_topk(q, corpus.vectors, lo, hi, 10, block=5,
                         eligible=eligible)
    assert eligible[ref.truth].all()
    for i in range(16):
        d = ((corpus.vectors[eligible] - q[i]) ** 2).sum(1)
        assert list(ref.truth[i]) == list(
            np.flatnonzero(eligible)[np.argsort(d)[:10]])
    dist = np.sort(np.abs(np.random.default_rng(0).standard_normal(
        (16, 10))), 1)
    assert chk.bad_answers(ref.truth, dist, lo, hi, eligible) == 0
    barred = ref.truth.copy()
    barred[2, 4] = np.flatnonzero(~eligible)[0]
    assert chk.bad_answers(barred, dist, lo, hi, eligible) == 1
    assert chk.bad_answers(barred, dist, lo, hi) == 0
    pos, _ = chk.bf16_answers(q, corpus.vectors, lo, hi, 10, block=8,
                              eligible=eligible)
    assert eligible[pos].all()


def test_readback_numbers():
    corpus = dat.make_corpus(9, 16, DATA, 50, 0, cluster_order=False)
    q = corpus.vectors[:2]
    pos = np.array([[0, 1], [1, 0]])
    d = ((q[:, None] - corpus.vectors[pos]) ** 2).sum(-1)
    rb = (np.array([0, 1]), np.array([[0, 5], [7, 8]]),
          np.array([3]), np.array([[3, 4]]))
    c = chk.compare(q, corpus.vectors, pos, d,
                    np.full(2, 200), np.ones(2, bool), np.zeros(2, int),
                    np.full(2, 50), 2, 0.5, readback=rb)
    assert c.numbers["readback_missing"][0] == 1
    assert c.numbers["deleted_returned"][0] == 1
    assert not c.correct


class FakeService:
    def __init__(self):
        self.engine = SimpleNamespace(ingest_backlog=0,
                                      cfg=SimpleNamespace(ingest_chunk=64))
        self.calls = []

    def upsert_async(self, docs, vecs):
        self.calls.append(("upsert", [d["id"] for d in docs]))
        self.engine.ingest_backlog += len(docs)

    def delete_async(self, ids):
        self.calls.append(("delete", ids))
        self.engine.ingest_backlog += len(ids)


def test_write_stream_tracks_what_was_applied():
    corpus = dat.make_corpus(1, 8, DATA, 256, 16, cluster_order=True)
    svc = FakeService()
    pattern = (("upsert", 4),) + (("delete", 1),) * 4
    ws = WriteStream(svc, corpus, pattern, 10)
    assert ws.live_range() == (0, 256)
    for _ in range(10):
        assert ws.submit_next()
    assert [k for k, _ in svc.calls] == ["upsert"] + ["delete"] * 4 + \
        ["upsert"] + ["delete"] * 4
    assert svc.calls[0][1] == [256, 257, 258, 259]
    assert [c[1] for c in svc.calls[1:5]] == [[0], [1], [2], [3]]
    assert ws.live_range() == (0, 256)  # nothing applied yet
    svc.engine.ingest_backlog -= 4  # the engine applied the first upsert
    assert ws.live_range() == (0, 260)
    svc.engine.ingest_backlog -= 1 + 1  # ... and two deletes
    assert ws.live_range() == (2, 260)
    assert ws.applied() == (4, 2, 3)
    while ws.submit_next():
        pass
    assert ws.written == 272 and ws.deleted == 16


def test_write_requests_larger_than_an_ingest_chunk_are_refused():
    corpus = dat.make_corpus(1, 8, DATA, 64, 128, cluster_order=True)
    with pytest.raises(ValueError):
        WriteStream(FakeService(), corpus, (("upsert", 65),), 0)


def test_write_patterns_churn_or_upserts_alone():
    from chipbench.cell import write_pattern

    assert write_pattern({"upsert_docs": 8, "delete_docs": 1}) == (
        (("upsert", 8),) + (("delete", 1),) * 8)
    assert write_pattern({"upsert_docs": 64, "delete_docs": 64}) == (
        ("upsert", 64), ("delete", 64))
    # a bulk-ingest mix: upserts only
    assert write_pattern({"upsert_docs": 64}) == (("upsert", 64),)
    assert write_pattern({"upsert_docs": 64, "delete_docs": 0}) == (
        ("upsert", 64),)
    with pytest.raises(ValueError):
        write_pattern({"upsert_docs": 8, "delete_docs": 3})
    corpus = dat.make_corpus(1, 8, DATA, 64, 32, cluster_order=False)
    svc = FakeService()
    ws = WriteStream(svc, corpus, write_pattern({"upsert_docs": 16}), 0)
    while ws.submit_next():
        pass
    svc.engine.ingest_backlog = 0
    assert ws.live_range() == (0, 96) and ws.applied() == (32, 0, 2)
