"""GreedySearch (Algorithm 1) — batched, fixed-shape, TPU-native.

The paper's search walks the graph with async SSD reads, amortizing per-hop
cost with a *beamWidth* knob: several frontier nodes expand per round so each
I/O round does more useful work (§3.2). On TPU the same knob pays off for a
different reason: the L-entry search list ("beam") is a sorted array advanced
by a `lax.while_loop`, and under lockstep `vmap` every lane in a micro-batch
waits for the slowest lane's round count. Expanding the W best unexpanded
beam entries per round (``beam_width``) gathers ``W × R_slack`` neighbors in
one shot, computes all their ADC distances in a single call, and merges with
one `lax.top_k` — cutting the sequential trip count ~W× while widening the
vectorized work per dispatch.

Search runs in *quantized space* (§3.2): distances come from per-query ADC
LUTs against the uint8 PQ codes; full-precision vectors are only touched by
the re-rank stage (``repro.core.flat.rerank``), preserving the paper's ≈70×
access-frequency asymmetry.

Filter-aware (β) search — Algorithm 7 — is folded in: when a packed filter
bitmap is supplied, distances of filter-passing nodes are scaled by β < 1 so
the frontier drifts toward the filtered region (§3.5, Fig 9).

Counter semantics with hop batching:
  * ``n_hops`` — sequential rounds (the latency-critical quantity; drops
    ~W× at beam_width W);
  * ``n_exp`` — frontier nodes actually expanded, i.e. adjacency rows
    fetched (the RU-relevant quantity; ≈ n_hops at W=1);
  * ``n_cmps`` — quantized distance comparisons (rises modestly with W:
    a wider frontier visits a few extra neighborhoods).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import graph as g
from . import pq as pqmod

INF = jnp.float32(jnp.inf)


class SearchResult(NamedTuple):
    beam_ids: jax.Array  # (L,) int32, ascending distance, -1 padded
    beam_dists: jax.Array  # (L,) f32 (quantized-space, β-scaled if filtered)
    visited_ids: jax.Array  # (V,) int32 expanded nodes in order, -1 padded
    visited_dists: jax.Array  # (V,) f32
    n_hops: jax.Array  # () int32 — sequential expansion rounds
    n_exp: jax.Array  # () int32 — nodes expanded (adjacency rows fetched)
    n_cmps: jax.Array  # () int32 — number of quantized distance comps


class _LoopState(NamedTuple):
    ids: jax.Array
    dists: jax.Array
    expanded: jax.Array
    bitmap: jax.Array
    visited_ids: jax.Array
    visited_dists: jax.Array
    hops: jax.Array
    exp: jax.Array
    cmps: jax.Array


def mask_duplicates(ids: jax.Array) -> jax.Array:
    """True where ids[i] repeats an earlier (lower-index) entry.

    Sort-based O(n log n): the stable argsort groups equal ids with the
    earliest original position first, so adjacent-equal in sorted order
    marks exactly the later occurrences. Replaces the former O(n²) pairwise
    mask, which would explode at the W·R_slack widths hop batching gathers.
    Negative ids (padding) are never marked — they are invalid anyway.
    """
    order = jnp.argsort(ids)  # stable: ties keep original index order
    s = ids[order]
    dup_sorted = jnp.concatenate([jnp.zeros((1,), bool), s[1:] == s[:-1]])
    dup = jnp.zeros_like(dup_sorted).at[order].set(dup_sorted)
    return dup & (ids >= 0)


def frontier_topw(
    ids: jax.Array, dists: jax.Array, expanded: jax.Array, W: int
) -> tuple[jax.Array, jax.Array]:
    """Positions of the W best unexpanded beam entries.

    Returns (positions (W,), valid (W,)). Lanes beyond the remaining
    frontier are flagged invalid; their positions point at expanded or
    padding entries, so marking them expanded is a no-op.
    """
    masked = jnp.where(expanded | (ids < 0), INF, dists)
    neg, pos = jax.lax.top_k(-masked, W)
    return pos, neg > -INF


def expand_frontier(
    neighbors: jax.Array,
    codes: jax.Array,
    versions: jax.Array,
    live: jax.Array,
    luts: jax.Array,
    bitmap: jax.Array,
    p_ids: jax.Array,  # (W,) frontier node ids
    p_valid: jax.Array,  # (W,) bool
    filter_bits: Optional[jax.Array],
    beta: jax.Array,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """The shared W-way hop: gather all W adjacency rows at once, drop
    already-visited / dead / duplicate candidates with one sort-based pass,
    and compute every ADC distance in a single call.

    Returns (cand_ids (W·R_slack,), cand_dists, new_bitmap, n_new).
    Used by both the greedy-search loop body and the pagination loop.
    """
    nbrs = neighbors[jnp.maximum(p_ids, 0)]  # (W, R_slack)
    nbrs = jnp.where(p_valid[:, None], nbrs, -1).reshape(-1)
    safe = jnp.maximum(nbrs, 0)
    valid = (nbrs >= 0) & live[safe] & ~g.bitmap_test(bitmap, nbrs)
    valid &= ~mask_duplicates(nbrs)
    bitmap = g.bitmap_set(bitmap, jnp.where(valid, nbrs, -1))

    d = pqmod.adc_distance_versioned(luts, codes[safe], versions[safe])
    if filter_bits is not None:
        passes = g.bitmap_test(filter_bits, safe) & (nbrs >= 0)
        d = jnp.where(passes, beta * d, d)
    d = jnp.where(valid, d, INF)
    return jnp.where(valid, nbrs, -1), d, bitmap, valid.sum()


def _expand_w(
    st: _LoopState,
    neighbors: jax.Array,
    codes: jax.Array,
    versions: jax.Array,
    live: jax.Array,
    luts: jax.Array,
    filter_bits: Optional[jax.Array],
    beta: jax.Array,
    W: int,
) -> _LoopState:
    """One round: expand the W best unexpanded beam entries, merge their
    neighbors into the L-beam with a single top-k."""
    L = st.ids.shape[0]
    cap_v = st.visited_ids.shape[0]

    p_pos, p_valid = frontier_topw(st.ids, st.dists, st.expanded, W)
    p_ids = st.ids[p_pos]
    expanded = st.expanded.at[p_pos].set(True)

    # visited log: valid expansions pack contiguously after the running
    # expansion count; invalid lanes scatter out of bounds and drop
    nv = p_valid.astype(jnp.int32)
    vpos = (st.exp + jnp.cumsum(nv) - nv) % cap_v
    vpos = jnp.where(p_valid, vpos, cap_v)
    visited_ids = st.visited_ids.at[vpos].set(p_ids, mode="drop")
    visited_dists = st.visited_dists.at[vpos].set(st.dists[p_pos], mode="drop")

    cand_ids, cand_d, bitmap, n_new = expand_frontier(
        neighbors, codes, versions, live, luts, st.bitmap,
        p_ids, p_valid, filter_bits, beta,
    )

    all_ids = jnp.concatenate([st.ids, cand_ids])
    all_d = jnp.concatenate([st.dists, cand_d])
    all_e = jnp.concatenate([expanded, jnp.zeros(cand_ids.shape, bool)])
    _, order = jax.lax.top_k(-all_d, L)  # ties keep lower index: stays sorted
    return _LoopState(
        ids=all_ids[order],
        dists=all_d[order],
        expanded=all_e[order],
        bitmap=bitmap,
        visited_ids=visited_ids,
        visited_dists=visited_dists,
        hops=st.hops + 1,
        exp=st.exp + nv.sum(),
        cmps=st.cmps + n_new,
    )


@functools.partial(
    jax.jit,
    static_argnames=("L", "max_hops", "visited_cap", "has_filter", "beam_width"),
)
def greedy_search(
    neighbors: jax.Array,
    codes: jax.Array,
    versions: jax.Array,
    live: jax.Array,
    luts: jax.Array,  # (Vschemas, M, K) from pq.multi_lut
    start: jax.Array,  # () int32
    *,
    L: int,
    max_hops: int = 0,
    visited_cap: int = 0,
    has_filter: bool = False,
    filter_bits: Optional[jax.Array] = None,
    beta: jax.Array | float = 1.0,
    beam_width: int = 1,
) -> SearchResult:
    """Single-query GreedySearch. vmap over (luts, filter_bits) for batches.

    ``beam_width`` (the paper's beamWidth, §3.2) expands the W best
    unexpanded beam entries per round. ``max_hops`` bounds *rounds*; its
    default keeps the total expansion budget (~2L+16 nodes) independent of
    W, so W only changes how the same candidate pool is scheduled.
    """
    W = int(beam_width)
    assert 1 <= W <= L, f"beam_width {W} must be in [1, L={L}]"
    if max_hops == 0:
        max_hops = -(-(2 * L + 16) // W)  # ceil: same node budget at any W
    if visited_cap == 0:
        visited_cap = W * max_hops
    if not has_filter:
        filter_bits = None
    beta = jnp.float32(beta)
    cap = neighbors.shape[0]

    start_d = pqmod.adc_distance_versioned(
        luts, codes[start][None], versions[start][None]
    )[0]
    ids0 = jnp.full((L,), -1, jnp.int32).at[0].set(start)
    dists0 = jnp.full((L,), INF).at[0].set(start_d)
    expanded0 = jnp.ones((L,), bool).at[0].set(False)
    bm0 = g.bitmap_set(g.bitmap_init(cap), jnp.array([start], jnp.int32))

    st0 = _LoopState(
        ids=ids0,
        dists=dists0,
        expanded=expanded0,
        bitmap=bm0,
        visited_ids=jnp.full((visited_cap,), -1, jnp.int32),
        visited_dists=jnp.full((visited_cap,), INF),
        hops=jnp.int32(0),
        exp=jnp.int32(0),
        cmps=jnp.int32(1),
    )

    def cond(st: _LoopState):
        frontier = (~st.expanded) & (st.ids >= 0)
        return jnp.any(frontier) & (st.hops < max_hops)

    def body(st: _LoopState):
        return _expand_w(
            st, neighbors, codes, versions, live, luts, filter_bits, beta, W
        )

    st = jax.lax.while_loop(cond, body, st0)
    return SearchResult(
        beam_ids=st.ids,
        beam_dists=st.dists,
        visited_ids=st.visited_ids,
        visited_dists=st.visited_dists,
        n_hops=st.hops,
        n_exp=st.exp,
        n_cmps=st.cmps,
    )


@functools.partial(
    jax.jit,
    static_argnames=("L", "max_hops", "visited_cap", "has_filter", "beam_width"),
)
def _batched_search_entry(
    neighbors, codes, versions, live, luts, start, filter_bits, beta,
    *, L: int, max_hops: int, visited_cap: int, has_filter: bool,
    beam_width: int,
) -> SearchResult:
    """Top-level jitted vmap over ``greedy_search``.

    Being the outermost jit matters: its compile cache is keyed by the full
    (batch, L, beam_width, …) signature, so ``jit_cache_size()`` is a
    truthful recompile counter for the serving hot path (an inner jit under
    vmap never sees its own cache populated — compilation happens in the
    pjit-primitive path). A beam_width change costs exactly one compile per
    (bucket, L) signature it is used with.
    """
    fn = functools.partial(
        greedy_search, neighbors, codes, versions, live,
        L=L, max_hops=max_hops, visited_cap=visited_cap,
        has_filter=has_filter, beta=beta, beam_width=beam_width,
    )
    if has_filter:
        return jax.vmap(lambda lut, fb: fn(lut, start, filter_bits=fb))(luts, filter_bits)
    return jax.vmap(lambda lut: fn(lut, start))(luts)


def batch_greedy_search(
    neighbors: jax.Array,
    codes: jax.Array,
    versions: jax.Array,
    live: jax.Array,
    luts: jax.Array,  # (B, Vschemas, M, K)
    start: jax.Array,
    *,
    L: int,
    max_hops: int = 0,
    visited_cap: int = 0,
    filter_bits: Optional[jax.Array] = None,  # (B, Nw) or None
    beta: float = 1.0,
    beam_width: int = 1,
) -> SearchResult:
    """vmapped GreedySearch over a query batch (lockstep beam expansion).

    W-way hop batching shrinks the lockstep critical path directly: lanes
    wait for the slowest lane's *round* count, and rounds drop ~W×.
    """
    has_filter = filter_bits is not None
    if not has_filter:
        # dummy with a stable shape so the jit signature doesn't churn
        filter_bits = jnp.zeros((luts.shape[0], 1), jnp.uint32)
    return _batched_search_entry(
        neighbors, codes, versions, live, luts, jnp.asarray(start, jnp.int32),
        filter_bits, jnp.float32(beta),
        L=L, max_hops=max_hops, visited_cap=visited_cap, has_filter=has_filter,
        beam_width=int(beam_width),
    )


def jit_cache_size() -> int:
    """Compiled-signature count of the batched-search entry (recompile
    telemetry for the serving layer; see serve/vector_engine.py)."""
    return int(_batched_search_entry._cache_size())


# ---------------------------------------------------------------------------
# shape bucketing — fixed (batch, L) signatures for the serving layer
# ---------------------------------------------------------------------------

BATCH_BUCKETS: tuple[int, ...] = (1, 2, 4, 8, 16, 32, 64)


def next_bucket(n: int, buckets: tuple[int, ...] = BATCH_BUCKETS) -> int:
    """Smallest bucket ≥ n; beyond the largest, round up to a multiple of it
    (the serving engine splits oversized batches into top-bucket chunks —
    ``vector_engine._dispatch`` — so the rounding here is only a safety net
    against shape explosions for direct callers)."""
    for b in buckets:
        if n <= b:
            return b
    top = buckets[-1]
    return ((n + top - 1) // top) * top


def pad_batch(arr: jax.Array, bucket: int) -> jax.Array:
    """Pad the leading (batch) axis to `bucket` by repeating row 0 — padded
    lanes redo real work so every lane stays numerically well-formed."""
    b = arr.shape[0]
    if b == bucket:
        return arr
    filler = jnp.broadcast_to(arr[:1], (bucket - b,) + arr.shape[1:])
    return jnp.concatenate([arr, filler], axis=0)


def pad_batch_np(arr: np.ndarray, bucket: int) -> np.ndarray:
    """Host-side twin of ``pad_batch`` — pads query batches before they
    enter any jitted stage (LUTs, search, re-rank share one bucket)."""
    b = len(arr)
    if b == bucket:
        return arr
    return np.concatenate(
        [arr, np.broadcast_to(arr[:1], (bucket - b,) + arr.shape[1:])]
    )


def bucketed_batch_greedy_search(
    neighbors: jax.Array,
    codes: jax.Array,
    versions: jax.Array,
    live: jax.Array,
    luts: jax.Array,  # (B, Vschemas, M, K)
    start: jax.Array,
    *,
    L: int,
    batch_buckets: tuple[int, ...] = BATCH_BUCKETS,
    max_hops: int = 0,
    visited_cap: int = 0,
    filter_bits: Optional[jax.Array] = None,
    beta: float = 1.0,
    beam_width: int = 1,
) -> SearchResult:
    """`batch_greedy_search` padded to a fixed batch bucket, results sliced
    back to the true batch — steady-state traffic whose batch sizes vary
    within one bucket reuses a single compiled executable (zero recompiles)."""
    B = luts.shape[0]
    bucket = next_bucket(B, batch_buckets)
    if bucket != B:
        luts = pad_batch(luts, bucket)
        if filter_bits is not None:
            filter_bits = pad_batch(filter_bits, bucket)
    res = batch_greedy_search(
        neighbors, codes, versions, live, luts, start,
        L=L, max_hops=max_hops, visited_cap=visited_cap,
        filter_bits=filter_bits, beta=beta, beam_width=beam_width,
    )
    if bucket != B:
        res = SearchResult(*(a[:B] for a in res))
    return res


def search_candidates(res: SearchResult) -> tuple[jax.Array, jax.Array]:
    """Union of expanded set and final beam — the prune candidate pool used
    by Insert (Algorithm 2 consumes the visited set V)."""
    ids = jnp.concatenate([res.visited_ids, res.beam_ids], axis=-1)
    dists = jnp.concatenate([res.visited_dists, res.beam_dists], axis=-1)
    # dedup: keep first occurrence (visited log wins; beam dupes masked)
    def dedup_one(i, d):
        dup = mask_duplicates(i)
        return jnp.where(dup, -1, i), jnp.where(dup, INF, d)

    if ids.ndim == 1:
        return dedup_one(ids, dists)
    return jax.vmap(dedup_one)(ids, dists)
