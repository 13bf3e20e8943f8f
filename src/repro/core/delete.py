"""In-place delete (Algorithm 6) + lightweight background consolidation.

The paper (§2.1 "In-place Deletion", Fig 13) shows that rewiring the deleted
node's critical connections keeps recall stable over long update streams,
whereas simply dropping the vector ("Drop Policy") degrades — dramatically so
under distribution shift. We implement both so the runbook benchmarks can
reproduce the comparison.

Alg 6, faithfully:
  * B = in-neighbors of p found within p's two-hop out-neighborhood;
  * every b ∈ B: drop p, splice in the c closest of N_out(p) to b, prune if
    over the degree bound;
  * every b ∈ N_out(p): connect b to its c closest siblings in N_out(p);
  * a background sweep (``consolidate_chunk``) erases remaining dangling
    edges to dead nodes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import prune as prmod
from . import search as smod

INF = jnp.float32(jnp.inf)


@functools.partial(
    jax.jit,
    static_argnames=("R", "R_slack", "alpha", "c_replace", "metric"),
    donate_argnames=("neighbors",),
)
def inplace_delete(
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
    """Rewire the graph around deleted node p. Returns new neighbors.

    Row b of the result depends only on row b, N_out(p), ``live`` and
    ``vectors``, so each loop of Alg 6 runs as one batched pass.
    """
    nout_p = neighbors[p]  # (R_slack,)
    safe_out = jnp.maximum(nout_p, 0)
    valid_out = (nout_p >= 0) & live[safe_out]
    out_vecs = vectors[safe_out]

    # --- two-hop out-neighborhood ---------------------------------------
    twohop = neighbors[safe_out].reshape(-1)  # (R_slack^2,)
    twohop = jnp.where(jnp.repeat(valid_out, R_slack), twohop, -1)
    hood = jnp.concatenate([nout_p, twohop])  # candidate in-neighbors
    hood = jnp.where(hood == p, -1, hood)

    def closest_out(b, c):
        """The c closest live members of N_out(p) to b, b excluded."""
        b_vec = vectors[jnp.maximum(b, 0)]
        if metric == "l2":
            dd = jnp.sum((out_vecs - b_vec[None, :]) ** 2, -1)
        else:
            dd = -out_vecs @ b_vec
        dd = jnp.where(valid_out & (nout_p != b), dd, INF)
        take = jnp.argsort(dd)[:c]
        return jnp.where(jnp.isfinite(dd[take]), nout_p[take], -1)

    def scatter_rows(nb, ids, rows, write):
        """nb with rows[i] at ids[i] where write[i]; the rest dropped."""
        return nb.at[jnp.where(write, ids, nb.shape[0])].set(rows, mode="drop")

    def first_live(ids):  # padding, dead nodes and repeats write nothing
        return (ids >= 0) & live[jnp.maximum(ids, 0)] & ~smod.mask_duplicates(ids)

    # --- first loop: b with p ∈ N_out(b) drop p and take its neighbors ----
    def repair(b, row):
        # remove p, compact left
        no_p = jnp.where(row == p, -1, row)
        order = jnp.argsort(jnp.where(no_p >= 0, 0, 1), stable=True)
        merged = jnp.concatenate([no_p[order], closest_out(b, c_replace)])
        # dedup + prune to R if above bound, else compact to R_slack
        pruned = prmod.prune_with_vectors(
            vectors[jnp.maximum(b, 0)],
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
        uniq = jnp.where(smod.mask_duplicates(merged), -1, merged)
        order2 = jnp.argsort(jnp.where(uniq >= 0, 0, 1), stable=True)
        compacted = uniq[order2][:R_slack]
        padded_prune = jnp.concatenate([pruned, jnp.full((R_slack - R,), -1, jnp.int32)])
        return jnp.where(use_prune, padded_prune, compacted)

    rows = neighbors[jnp.maximum(hood, 0)]  # (R_slack + R_slack^2, R_slack)
    has_p = jnp.any(rows == p, axis=1) & first_live(hood)
    neighbors = scatter_rows(neighbors, hood, jax.vmap(repair)(hood, rows), has_p)

    # --- second loop of Alg 6: stitch N_out(p) among themselves ----------
    # c=1 sibling link keeps degree churn low
    sib = jax.vmap(lambda b: closest_out(b, 1)[0])(nout_p)
    rows = neighbors[safe_out]
    deg = (rows >= 0).sum(axis=1)
    already = jnp.any(rows == sib[:, None], axis=1) | (sib < 0)
    appended = jnp.where(jnp.arange(R_slack)[None, :] == deg[:, None], sib[:, None], rows)
    can = first_live(nout_p) & ~already & (deg < R_slack)
    neighbors = scatter_rows(neighbors, nout_p, appended, can)

    # clear p's own list
    neighbors = neighbors.at[p].set(jnp.full((R_slack,), -1, jnp.int32))
    return neighbors


@functools.partial(jax.jit, static_argnames=("chunk",), donate_argnames=("neighbors",))
def consolidate_chunk(
    neighbors: jax.Array, live: jax.Array, start_row: jax.Array, chunk: int = 1024
) -> jax.Array:
    """Background sweep (§2.1): erase edges to dead nodes in rows
    [start_row, start_row + chunk), compacting left."""
    rows = start_row + jnp.arange(chunk)
    rows = jnp.minimum(rows, neighbors.shape[0] - 1)
    block = neighbors[rows]  # (chunk, R_slack)
    dead = ~live[jnp.maximum(block, 0)] | (block < 0)
    cleaned = jnp.where(dead, -1, block)
    order = jnp.argsort(jnp.where(cleaned >= 0, 0, 1), axis=1, stable=True)
    compacted = jnp.take_along_axis(cleaned, order, axis=1)
    return neighbors.at[rows].set(compacted)
