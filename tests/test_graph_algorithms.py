"""DiskANN algorithm tests: search recall, prune invariants, deletes,
pagination, filters. Uses networkx to check structural graph properties."""
import functools

import jax
import jax.numpy as jnp
import networkx as nx
import numpy as np
import pytest
from proptest import given, settings
from proptest import strategies as st

from repro.core import GraphConfig, DiskANNIndex
from repro.core import delete as dmod
from repro.core import prune as prmod
from repro.core import recall as rec
from repro.core.graph import bitmap_init, bitmap_set, bitmap_test

from conftest import clustered_data


@pytest.fixture(scope="module")
def built_index():
    rng = np.random.RandomState(7)
    N, D = 2000, 32
    data = clustered_data(rng, N, D)
    cfg = GraphConfig(capacity=N + 64, R=24, M=16, L_build=48, L_search=48,
                      bootstrap_sample=256, refine_sample=10**9, batch_size=64)
    idx = DiskANNIndex(cfg, D, seed=0)
    idx.insert(list(range(N)), data)
    return idx, data, rng


def _queries_from(data, rng, n, noise=0.05):
    """In-distribution queries: perturbed database points (the realistic
    regime; fully out-of-distribution queries are a different benchmark)."""
    pick = rng.choice(len(data), n, replace=False)
    return (data[pick] + noise * rng.randn(n, data.shape[1])).astype(np.float32)


def test_search_recall(built_index):
    idx, data, rng = built_index
    q = _queries_from(data, np.random.RandomState(99), 32)
    ids, dists, stats = idx.search(q, k=10, L=64)
    gt = rec.ground_truth(q, data, np.ones(len(data), bool), 10)
    r = rec.recall_at_k(ids, gt, 10)
    assert r >= 0.85, f"recall@10 {r}"
    assert np.all(np.diff(dists, axis=1) >= -1e-5), "results must be sorted"


def test_search_stats_asymmetry(built_index):
    """§3.2: quantized reads ≫ full-precision reads (the paper's ~70×)."""
    idx, data, rng = built_index
    q = _queries_from(data, np.random.RandomState(5), 8)
    _, _, stats = idx.search(q, k=10, L=64, rerank_multiplier=2.5)
    assert stats.cmps > 4 * stats.full_reads


def test_graph_degree_bound_and_connectivity(built_index):
    idx, data, _ = built_index
    nbrs = idx.pv.neighbors
    deg = (nbrs >= 0).sum(1)
    live = idx.pv.live
    assert deg[live].max() <= idx.cfg.R_slack
    # medoid reaches nearly every live node (graph navigability)
    G = nx.DiGraph()
    for u in np.nonzero(live)[0]:
        for v in nbrs[u][nbrs[u] >= 0]:
            G.add_edge(int(u), int(v))
    reachable = nx.descendants(G, idx.medoid) | {idx.medoid}
    frac = len(reachable & set(map(int, np.nonzero(live)[0]))) / live.sum()
    assert frac > 0.95, f"only {frac:.2%} reachable from medoid"


def test_robust_prune_invariants():
    """Degree ≤ R; closest candidate always kept; no dominated survivor."""
    rng = np.random.RandomState(3)
    C, D, R, alpha = 40, 8, 8, 1.2
    p = rng.randn(D).astype(np.float32)
    cands = rng.randn(C, D).astype(np.float32)
    ids = jnp.arange(C, dtype=jnp.int32)
    kept = np.asarray(prmod.prune_with_vectors(
        jnp.asarray(p), ids, jnp.asarray(cands), alpha=alpha, R=R))
    kept_ids = kept[kept >= 0]
    assert len(kept_ids) <= R
    d = ((cands - p) ** 2).sum(1)
    assert d.argmin() in kept_ids, "nearest candidate must survive"
    # α-RNG property: for every kept q there is no EARLIER kept r with
    # α²·d(r,q) ≤ d(p,q)
    a2 = alpha * alpha
    for i, qi in enumerate(kept_ids):
        for rj in kept_ids[:i]:
            drq = ((cands[qi] - cands[rj]) ** 2).sum()
            assert a2 * drq > d[qi] - 1e-5, (qi, rj)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000), alpha=st.floats(1.0, 2.0), r=st.sampled_from([4, 8, 16]))
def test_property_prune_degree_bound(seed, alpha, r):
    rng = np.random.RandomState(seed)
    C, D = 30, 6
    p = rng.randn(D).astype(np.float32)
    cands = rng.randn(C, D).astype(np.float32)
    ids = jnp.asarray(np.where(rng.rand(C) < 0.8, np.arange(C), -1).astype(np.int32))
    kept = np.asarray(prmod.prune_with_vectors(
        jnp.asarray(p), ids, jnp.asarray(cands), alpha=alpha, R=r))
    kept_ids = kept[kept >= 0]
    assert len(kept_ids) <= r
    assert len(set(kept_ids.tolist())) == len(kept_ids), "no duplicates"
    valid = set(np.asarray(ids)[np.asarray(ids) >= 0].tolist())
    assert set(kept_ids.tolist()) <= valid


def test_bitmap_ops():
    bm = bitmap_init(1000)
    ids = jnp.asarray([0, 31, 32, 63, 999, 999, -1], jnp.int32)
    bm = bitmap_set(bm, ids)
    got = np.asarray(bitmap_test(bm, jnp.asarray([0, 1, 31, 32, 63, 64, 999], jnp.int32)))
    np.testing.assert_array_equal(got, [True, False, True, True, True, False, True])


def test_delete_keeps_recall(built_index):
    idx, data, _ = built_index
    snap = idx.snapshot()
    try:
        victims = list(range(100, 300))
        idx.delete(victims, policy="inplace")
        for _ in range(3):
            idx.consolidate()
        live = np.ones(len(data), bool)
        live[victims] = False
        rs = np.random.RandomState(123)
        pick = rs.choice(np.nonzero(live)[0], 24, replace=False)
        q = (data[pick] + 0.05 * rs.randn(24, 32)).astype(np.float32)
        ids, _, _ = idx.search(q, k=10, L=64)
        for row in ids:
            assert not (set(row.tolist()) & set(victims)), "deleted ids returned"
        gt = rec.ground_truth(q, data, live, 10)
        r = rec.recall_at_k(ids, gt, 10)
        assert r >= 0.8, f"post-delete recall {r}"
    finally:
        idx.restore(snap)


def test_replace_updates_results(built_index):
    idx, data, _ = built_index
    snap = idx.snapshot()
    try:
        # move doc 0 on top of doc 1500's vector: searching near it must find 0
        target = data[1500] + 1e-3
        idx.insert([0], target[None, :])  # replace path
        ids, _, _ = idx.search(target[None, :], k=5, L=48)
        assert 0 in ids[0].tolist()
    finally:
        idx.restore(snap)


def test_paginated_search_disjoint_and_ordered(built_index):
    idx, data, _ = built_index
    q = _queries_from(data, np.random.RandomState(55), 1)[0]
    state = idx.start_pagination(q, L=32)
    seen, all_pages = set(), []
    for _ in range(4):
        ids, dists, state = idx.next_page(q, state, k=5, rerank=False)
        page = [i for i in ids.tolist() if i >= 0]
        assert not (set(page) & seen), "pages must not repeat results"
        seen |= set(page)
        all_pages.append(page)
    assert len(seen) >= 15
    # union of pages ≈ prefix of brute-force ranking
    gt = rec.ground_truth(q[None], data, np.ones(len(data), bool), 20)[0]
    overlap = len(seen & set(gt.tolist())) / 20
    assert overlap >= 0.6, overlap


def test_filtered_search_modes(built_index):
    idx, data, _ = built_index
    rng = np.random.RandomState(31)
    doc_filter = np.zeros(idx.cfg.capacity, bool)
    match_slots = rng.choice(len(data), 400, replace=False)
    doc_filter[match_slots] = True
    q = clustered_data(np.random.RandomState(77), 8, 32)
    live = np.zeros(len(data), bool)
    live[match_slots] = True
    gt = rec.ground_truth(q, data, live, 5)
    for mode in ("qflat", "post", "beta"):
        ids, dists, stats = idx.filtered_search(q, k=5, doc_filter=doc_filter, mode=mode)
        valid = ids[ids >= 0]
        assert np.isin(valid, match_slots).all(), f"{mode} returned non-matching docs"
        r = rec.recall_at_k(ids, gt, 5)
        assert r >= 0.5, f"{mode} filtered recall {r}"


def test_filtered_auto_routing(built_index):
    idx, data, _ = built_index
    few = np.zeros(idx.cfg.capacity, bool)
    few[:50] = True  # < QFLAT_MAX_MATCHES → qflat plan
    q = clustered_data(np.random.RandomState(2), 2, 32)
    _, _, stats = idx.filtered_search(q, k=5, doc_filter=few, mode="auto")
    assert stats.plan in ("qflat", "brute")


# ---------------------------------------------------------------------------
# in-place delete: the batched kernel against the serial Alg 6 loops
# ---------------------------------------------------------------------------

INF = jnp.float32(jnp.inf)


@functools.partial(
    jax.jit,
    static_argnames=("R", "R_slack", "alpha", "c_replace", "metric"),
    donate_argnames=("neighbors",),
)
def _inplace_delete_serial(
    neighbors: jax.Array,  # (N, R_slack)
    live: jax.Array,  # (N,) bool — p should already be marked dead
    vectors: jax.Array,  # (N, D) decoded-PQ or full coordinates for pruning
    p: jax.Array,  # () int32 node being deleted
    *,
    R: int,
    R_slack: int,
    alpha: float,
    c_replace: int = 3,
    metric: str = "l2",
) -> jax.Array:
    """Reference: Alg 6 as two ``lax.scan`` loops, one hood entry at a time."""
    nout_p = neighbors[p]  # (R_slack,)
    safe_out = jnp.maximum(nout_p, 0)
    valid_out = (nout_p >= 0) & live[safe_out]

    # --- two-hop out-neighborhood ---------------------------------------
    twohop = neighbors[safe_out].reshape(-1)  # (R_slack^2,)
    twohop = jnp.where(jnp.repeat(valid_out, R_slack), twohop, -1)
    hood = jnp.concatenate([nout_p, twohop])  # candidate in-neighbors
    hood = jnp.where(hood == p, -1, hood)

    # --- loop over the hood: b with p ∈ N_out(b) get rewired -------------
    def fix_b(nb, b):
        row = nb[jnp.maximum(b, 0)]
        has_p = jnp.any(row == p) & (b >= 0) & live[jnp.maximum(b, 0)]

        # remove p, compact left
        no_p = jnp.where(row == p, -1, row)
        order = jnp.argsort(jnp.where(no_p >= 0, 0, 1), stable=True)
        no_p = no_p[order]

        # c closest live members of N_out(p) to b, excluding b itself
        b_vec = vectors[jnp.maximum(b, 0)]
        cand_vecs = vectors[safe_out]
        if metric == "l2":
            dd = jnp.sum((cand_vecs - b_vec[None, :]) ** 2, -1)
        else:
            dd = -cand_vecs @ b_vec
        dd = jnp.where(valid_out & (nout_p != b), dd, INF)
        closest = jnp.where(
            jnp.isfinite(jnp.sort(dd)[:c_replace]),
            nout_p[jnp.argsort(dd)[:c_replace]],
            -1,
        )

        merged = jnp.concatenate([no_p, closest])  # (R_slack + c,)
        # dedup + prune to R if above bound, else compact to R_slack
        pruned = prmod.prune_with_vectors(
            b_vec,
            merged,
            vectors[jnp.maximum(merged, 0)],
            alpha=alpha,
            R=R,
            metric=metric,
            self_id=b,
        )
        deg_merged = (merged >= 0).sum() - jnp.sum(
            (merged[:, None] == merged[None, :])
            & (merged[:, None] >= 0)
            & jnp.tril(jnp.ones((merged.shape[0],) * 2, bool), k=-1)
        )
        use_prune = deg_merged > R_slack
        # non-prune path: first R_slack unique entries of merged
        eq = (merged[:, None] == merged[None, :]) & (merged[None, :] >= 0)
        dup = jnp.any(eq & jnp.tril(jnp.ones_like(eq), k=-1).astype(bool), axis=1)
        uniq = jnp.where(dup, -1, merged)
        order2 = jnp.argsort(jnp.where(uniq >= 0, 0, 1), stable=True)
        compacted = uniq[order2][:R_slack]
        padded_prune = jnp.concatenate([pruned, jnp.full((R_slack - R,), -1, jnp.int32)])
        new_row = jnp.where(use_prune, padded_prune, compacted)

        out = jnp.where(has_p, new_row, row)
        return nb.at[jnp.maximum(b, 0)].set(out), None

    neighbors, _ = jax.lax.scan(fix_b, neighbors, hood)

    # --- second loop of Alg 6: stitch N_out(p) among themselves ----------
    def stitch(nb, b):
        ok = (b >= 0) & live[jnp.maximum(b, 0)]
        b_vec = vectors[jnp.maximum(b, 0)]
        cand_vecs = vectors[safe_out]
        if metric == "l2":
            dd = jnp.sum((cand_vecs - b_vec[None, :]) ** 2, -1)
        else:
            dd = -cand_vecs @ b_vec
        dd = jnp.where(valid_out & (nout_p != b), dd, INF)
        closest = jnp.argsort(dd)[:1]  # c=1 sibling link keeps degree churn low
        sib = jnp.where(jnp.isfinite(dd[closest]), nout_p[closest], -1)[0]

        row = nb[jnp.maximum(b, 0)]
        deg = (row >= 0).sum()
        already = jnp.any(row == sib) | (sib < 0)
        appended = jnp.where(jnp.arange(row.shape[0]) == deg, sib, row)
        can = ok & ~already & (deg < row.shape[0])
        return nb.at[jnp.maximum(b, 0)].set(jnp.where(can, appended, row)), None

    neighbors, _ = jax.lax.scan(stitch, neighbors, nout_p)

    # clear p's own list
    neighbors = neighbors.at[p].set(jnp.full((R_slack,), -1, jnp.int32))
    return neighbors


_DEL_N, _DEL_D, _DEL_R, _DEL_RS = 96, 8, 5, 8


def _add_edge(nb, a, b):
    """Put b in row a: appended if the row has room, else over its last
    entry (rows stay unique, with no holes)."""
    row = nb[a]
    if b in row or a == b:
        return
    deg = int((row >= 0).sum())
    nb[a, min(deg, len(row) - 1)] = b


def _delete_case(case: str, seed: int):
    """A seeded random graph around p = 5, shaped so one Alg 6 corner shows.

    Every case has p in the rows of three of its out-neighbours (mutual
    edges, so the hood holds in-neighbours).
    """
    rng = np.random.RandomState(seed)
    N, R_slack = _DEL_N, _DEL_RS
    vectors = rng.randn(N, _DEL_D).astype(np.float32)
    nb = np.full((N, R_slack), -1, np.int32)
    for i in range(N):
        deg = rng.randint(_DEL_R // 2, R_slack + 1)
        nb[i, :deg] = rng.choice(np.delete(np.arange(N), i), deg, replace=False)
    live = np.ones(N, bool)
    p = 5
    out = [int(x) for x in nb[p] if x >= 0]

    if case == "no_in":
        # nobody points at p, and p has one out-neighbour, so the stitch
        # has no sibling to add: only row p changes
        for a in range(N):
            row = [x for x in nb[a] if x >= 0 and x != p]
            nb[a] = row + [-1] * (R_slack - len(row))
        nb[p] = [out[0]] + [-1] * (R_slack - 1)
        live[p] = False
        return nb, live, vectors, p

    for b in out[:3]:
        _add_edge(nb, b, p)
    if case == "slot0_padding":
        # slot 0 is p's first out-neighbour and an in-neighbour; p's row and
        # its neighbours' rows end in -1 padding, which maps to slot 0
        nb[p] = [0] + out[1:4] + [-1] * (R_slack - 4)
        for b in nb[p][:4]:
            row = [x for x in nb[b] if x >= 0][: R_slack - 2]
            nb[b] = row + [-1] * (R_slack - len(row))
        _add_edge(nb, 0, p)
    elif case == "dup_twohop":
        # b sits in the rows of three out-neighbours of p and points at p
        b = next(x for x in range(N) if x != p and x not in out)
        for a in out[:3]:
            nb[a, 0] = b if b not in nb[a] else nb[a, 0]
        _add_edge(nb, b, p)
    elif case == "dead_out":
        # half of N_out(p) and a few two-hop nodes are dead
        live[out[::2]] = False
        live[nb[out[1]][nb[out[1]] >= 0][:2]] = False
    elif case == "full_prune":
        # in-neighbours at full R_slack with p among them: the merge goes
        # above R_slack and Alg 6 prunes to R
        for b in out:
            row = [x for x in nb[b] if x >= 0 and x != p]
            extra = [x for x in rng.permutation(N) if x not in row and x not in (b, p)]
            row = (row + extra)[: R_slack - 1] + [p]
            nb[b] = row
    live[p] = False
    return nb, live, vectors, p


_DELETE_CASES = ("slot0_padding", "dup_twohop", "dead_out", "full_prune", "no_in")


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("metric", ["l2", "ip"])
@pytest.mark.parametrize("case", _DELETE_CASES)
def test_inplace_delete_matches_serial(case, metric, seed):
    """The batched repair returns exactly the serial kernel's graph."""
    nb, live, vectors, p = _delete_case(case, seed)
    kw = dict(R=_DEL_R, R_slack=_DEL_RS, alpha=1.2, c_replace=3, metric=metric)
    args = (jnp.asarray(live), jnp.asarray(vectors), jnp.int32(p))
    want = np.asarray(_inplace_delete_serial(jnp.asarray(nb), *args, **kw))
    got = np.asarray(dmod.inplace_delete(jnp.asarray(nb), *args, **kw))
    np.testing.assert_array_equal(got, want)

    changed = set(np.nonzero((got != nb).any(axis=1))[0].tolist())
    out = nb[p][(nb[p] >= 0)]
    hood = np.concatenate([out, nb[out[live[out]]].reshape(-1)])
    hood = hood[(hood >= 0) & live[np.maximum(hood, 0)]]
    assert (got[p] == -1).all() and not (got[hood] == p).any()
    if case == "no_in":
        assert changed == {p}
    elif case == "slot0_padding":
        assert 0 in changed and (nb[p] == -1).any()
    elif case == "dup_twohop":
        b = int(nb[nb[p][0], 0])
        assert (hood == b).sum() >= 2 and b in changed
    elif case == "dead_out":
        new_edges = [(a, x) for a in changed for x in got[a]
                     if x >= 0 and x not in nb[a]]
        assert new_edges and all(live[x] for _, x in new_edges)
    elif case == "full_prune":
        # pruned to R (the stitch may append one sibling); the compacting
        # branch would keep at least R_slack - 1 > R + 1
        full = [b for b in nb[p] if b >= 0 and live[b] and (nb[b] >= 0).all()]
        assert any((got[b] >= 0).sum() <= _DEL_R + 1 for b in full)


def test_inplace_delete_matches_serial_on_built_graph(built_index):
    """A run of deletes on a built index's graph, in its quantized space:
    the batched repair and the serial kernel agree row for row."""
    idx, _, _ = built_index
    cfg = idx.cfg
    nb = np.array(idx.pv.neighbors)
    live = np.array(idx.pv.live)
    decoded = idx._decoded(np.arange(cfg.capacity))
    kw = dict(R=cfg.R, R_slack=cfg.R_slack, alpha=cfg.alpha,
              c_replace=cfg.c_replace, metric=cfg.metric)
    for doc in (0, 17, 401, 402, 1999):
        p = idx.doc_to_slot[doc]
        live[p] = False
        args = (jnp.asarray(live), decoded, jnp.int32(p))
        want = np.asarray(_inplace_delete_serial(jnp.asarray(nb), *args, **kw))
        got = np.asarray(dmod.inplace_delete(jnp.asarray(nb), *args, **kw))
        np.testing.assert_array_equal(got, want)
        nb = got


def test_repair_rows_counts_rows_written(built_index):
    """``repair_rows`` adds the rows each delete's diff wrote; a delete that
    rewires no in-neighbour adds only row p."""
    idx, _, _ = built_index
    snap = idx.snapshot()
    written = []
    set_neighbors = idx.pv.set_neighbors

    def spy(ctx, ids, rows):
        written.append(len(ids))
        set_neighbors(ctx, ids, rows)

    idx.pv.set_neighbors = spy
    try:
        r0 = idx.repair_rows
        idx.delete(list(range(600, 608)), policy="inplace")
        assert len(written) == 8 and min(written) > 1
        assert idx.repair_rows - r0 == sum(written)

        # isolate p: nobody points at it and it has a single out-neighbour
        p = idx.doc_to_slot[700]
        nb = idx.pv.neighbors
        q = int(nb[p, 0])
        rows = np.nonzero((nb == p).any(axis=1))[0]
        fixed = np.full((len(rows), nb.shape[1]), -1, np.int32)
        for i, a in enumerate(rows):
            keep = nb[a][(nb[a] >= 0) & (nb[a] != p)]
            fixed[i, : len(keep)] = keep
        set_neighbors(idx.ctx, rows, fixed)
        set_neighbors(idx.ctx, [p], np.asarray([[q] + [-1] * (nb.shape[1] - 1)], np.int32))
        r1, before = idx.repair_rows, nb.copy()
        written.clear()
        idx.delete([700], policy="inplace")
        assert idx.repair_rows - r1 == 1 and written == [1]
        assert np.nonzero((before != idx.pv.neighbors).any(axis=1))[0].tolist() == [p]
    finally:
        idx.pv.set_neighbors = set_neighbors
        idx.restore(snap)
