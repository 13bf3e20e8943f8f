"""Insert (Algorithm 2) and MiniBatchInsert (Algorithm 5).

Two implementations, matching the paper's split between the latency-critical
path and background index maintenance (§3.4 "Graph Operations"):

  * ``insert_candidates`` / ``prune_batch`` — the jitted, vmapped pieces
    (GreedySearch in quantized space + RobustPrune), used by the host-side
    orchestrator in ``index.py``. The host applies the reverse-edge updates
    as one consolidated append per touched node — exactly the Bw-Tree
    "no duplicate patches for a key" contract the mini-batch design exists
    to satisfy (§2.1).

  * ``insert_batch_jit`` — a single fully-jitted mini-batch insert (reverse
    edges applied via an in-graph fori loop with prune-on-overflow). This is
    the form the distributed ingest dry-run lowers, and the oracle for the
    host path's tests.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import graph as g
from . import pq as pqmod
from . import prune as prmod
from . import search as smod

INF = jnp.float32(jnp.inf)


class InsertStats(NamedTuple):
    hops: jax.Array  # (B,) search hops per inserted vector
    cmps: jax.Array  # (B,) quantized distance comparisons per insert


@functools.partial(jax.jit, static_argnames=("L_build", "max_hops", "metric"))
def insert_candidates(
    neighbors: jax.Array,
    codes: jax.Array,
    versions: jax.Array,
    live: jax.Array,
    schemas_codebooks: jax.Array,  # (V, M, K, dsub) stacked schema codebooks
    new_vecs: jax.Array,  # (B, D)
    medoid: jax.Array,
    *,
    L_build: int,
    max_hops: int = 0,
    metric: str = "l2",
) -> tuple[jax.Array, jax.Array, InsertStats]:
    """Search phase of Alg 2 for a mini-batch: returns the candidate pool
    (visited ∪ beam) per new vector: ids (B, C), dists (B, C)."""
    V = schemas_codebooks.shape[0]
    schemas = [
        pqmod.PQSchema(codebooks=schemas_codebooks[v], version=jnp.int32(v))
        for v in range(V)
    ]
    luts = jax.vmap(lambda q: pqmod.multi_lut(tuple(schemas), q, metric))(new_vecs)
    res = smod.batch_greedy_search(
        neighbors, codes, versions, live, luts, medoid, L=L_build, max_hops=max_hops
    )
    cand_ids, cand_dists = smod.search_candidates(res)
    return cand_ids, cand_dists, InsertStats(hops=res.n_hops, cmps=res.n_cmps)


@functools.partial(jax.jit, static_argnames=("R", "alpha", "metric"))
def prune_batch(
    codes: jax.Array,
    versions: jax.Array,
    schemas_codebooks: jax.Array,  # (V, M, K, dsub)
    new_vecs: jax.Array,  # (B, D)
    cand_ids: jax.Array,  # (B, C)
    *,
    R: int,
    alpha: float,
    metric: str = "l2",
) -> jax.Array:
    """Prune phase of Alg 2 (quantized-space prune, §3.2): (B, R) ids."""

    def decode_rows(ids):
        safe = jnp.maximum(ids, 0)
        return pqmod.decode_versioned(schemas_codebooks, codes[safe],
                                      versions[safe])

    def one(vec, ids):
        cand_vecs = decode_rows(ids)
        return prmod.prune_with_vectors(
            vec, ids, cand_vecs, alpha=alpha, R=R, metric=metric
        )

    return jax.vmap(one)(new_vecs, cand_ids)


@functools.partial(jax.jit, static_argnames=("R", "alpha", "metric"))
def prune_nodes(
    codes: jax.Array,
    versions: jax.Array,
    schemas_codebooks: jax.Array,  # (V, M, K, dsub)
    nodes: jax.Array,  # (P,) existing nodes whose slack row overflowed
    cand_ids: jax.Array,  # (P, C) row ∪ new reverse edges, -1 = invalid
    *,
    R: int,
    alpha: float,
    metric: str = "l2",
) -> jax.Array:
    """Overflow prune of Alg 5 for every overflowing node of a mini-batch
    in one call, in quantized space like ``prune_batch``: (P, R) ids."""

    def decode_rows(ids):
        safe = jnp.maximum(ids, 0)
        return pqmod.decode_versioned(schemas_codebooks, codes[safe],
                                      versions[safe])

    def one(node, ids):
        return prmod.prune_with_vectors(
            decode_rows(node[None])[0], ids, decode_rows(ids),
            alpha=alpha, R=R, metric=metric, self_id=node,
        )

    return jax.vmap(one)(nodes, cand_ids)


# ---------------------------------------------------------------------------
# Fully-jitted mini-batch insert (dry-run / oracle path)
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=("L_build", "R", "R_slack", "alpha", "metric", "max_hops"),
    donate_argnames=("neighbors", "codes", "versions", "live"),
)
def insert_batch_jit(
    neighbors: jax.Array,
    codes: jax.Array,
    versions: jax.Array,
    live: jax.Array,
    schemas_codebooks: jax.Array,
    new_vecs: jax.Array,  # (B, D)
    slots: jax.Array,  # (B,) destination rows
    medoid: jax.Array,
    *,
    L_build: int,
    R: int,
    R_slack: int,
    alpha: float,
    metric: str = "l2",
    max_hops: int = 0,
):
    """One mini-batch insert as a single XLA program.

    Phase 1 (parallel): candidates + prune for every new node (Alg 5 lines
    1-5). Phase 2 (sequential fori over B·R reverse edges): append the new
    node to each chosen neighbor, pruning to R when the slack degree
    overflows — the "apply to the graph in a single update" step.
    """
    B = new_vecs.shape[0]
    newest_schema = schemas_codebooks.shape[0] - 1

    # register the new codes/liveness first so batch members can see each
    # other through the visited pool (ParlayANN-style batch build).
    schema = pqmod.PQSchema(
        codebooks=schemas_codebooks[newest_schema], version=jnp.int32(newest_schema)
    )
    new_codes = pqmod.encode(schema, new_vecs)
    codes = codes.at[slots].set(new_codes)
    versions = versions.at[slots].set(jnp.uint8(newest_schema))

    cand_ids, cand_dists, stats = insert_candidates(
        neighbors, codes, versions, live, schemas_codebooks, new_vecs, medoid,
        L_build=L_build, max_hops=max_hops, metric=metric,
    )
    nbrs = prune_batch(
        codes, versions, schemas_codebooks, new_vecs, cand_ids,
        R=R, alpha=alpha, metric=metric,
    )  # (B, R)

    pad = jnp.full((B, R_slack - R), -1, jnp.int32)
    neighbors = neighbors.at[slots].set(jnp.concatenate([nbrs, pad], axis=1))
    live = live.at[slots].set(True)

    # --- phase 2: reverse edges ------------------------------------------
    edge_src = jnp.repeat(slots, R)  # (B*R,) the new node p
    edge_dst = nbrs.reshape(-1)  # (B*R,) target b

    def decode_ids(ids):
        safe = jnp.maximum(ids, 0)
        return pqmod.decode_versioned(schemas_codebooks, codes[safe],
                                      versions[safe])

    def body(i, carry):
        nb, = carry
        b = edge_dst[i]
        p = edge_src[i]
        row = nb[jnp.maximum(b, 0)]  # (R_slack,)
        deg = (row >= 0).sum()
        already = jnp.any(row == p)
        can_append = (deg < R_slack) & ~already & (b >= 0)

        appended = jnp.where(
            jnp.arange(R_slack) == deg, p, row
        )
        row_after_append = jnp.where(can_append, appended, row)

        # overflow: prune {row ∪ p} down to R
        cand = jnp.concatenate([row, jnp.array([p])])  # (R_slack+1,)
        cand_vecs = decode_ids(cand)
        b_vec = decode_ids(jnp.array([jnp.maximum(b, 0)]))[0]
        pruned = prmod.prune_with_vectors(
            b_vec, cand, cand_vecs, alpha=alpha, R=R, metric=metric, self_id=b
        )  # (R,)
        pruned_row = jnp.concatenate([pruned, jnp.full((R_slack - R,), -1, jnp.int32)])

        need_prune = (deg >= R_slack) & ~already & (b >= 0)
        new_row = jnp.where(need_prune, pruned_row, row_after_append)
        nb = nb.at[jnp.maximum(b, 0)].set(
            jnp.where((b >= 0), new_row, nb[jnp.maximum(b, 0)])
        )
        return (nb,)

    (neighbors,) = jax.lax.fori_loop(0, B * R, body, (neighbors,))
    return neighbors, codes, versions, live, stats
