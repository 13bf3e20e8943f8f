"""topk_select — two-stage blockwise partial top-k (smallest first).

Candidate selection after a Q-Flat scan (and the rerank cut) needs the L
smallest of N distances. A full sort is O(N log N) and serializes badly on
the VPU; instead the selection runs in two fixed-shape stages:

  stage 1 (Pallas, grid (B/8, N/Nb)): each block extracts its rows' local
    top-L by L iterated masked argmins over a VMEM-resident (8, Nb) tile —
    eight query rows per tile, one per sublane. The argmin is spelled as a
    row min-reduce plus an iota comparison (first-index tie break, same as
    ``lax.top_k``) and the survivor mask as a ``where`` over the column
    iota — pure vector ops, no scatter, no per-element stores. Each block
    writes its winners into an (8, Lp) slab, Lp = L rounded up to the
    128-lane tile, with one full-block store; slots ≥ L stay +inf / -1.

  stage 2 (host-side, fixed shape): the (B, nblk·L) survivors merge with a
    single small ``lax.top_k``. When the row fits one block the stage-1
    output is already the sorted answer and the merge is skipped.

The candidate set shrinks by Nb/L per level while staying rectangular; at
large N stage 2 touches nblk·L ≪ N values, so the merge cost is negligible.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROWS = 8  # query rows per tile: the f32 sublane count


def _topk_block_kernel(d_ref, vals_ref, idx_ref, *, L: int, block_n: int):
    dd = d_ref[...].astype(jnp.float32)  # (ROWS, Nb)
    Lp = vals_ref.shape[1]
    base = pl.program_id(1) * block_n
    col = jax.lax.broadcasted_iota(jnp.int32, dd.shape, 1)
    slot = jax.lax.broadcasted_iota(jnp.int32, (ROWS, Lp), 1)

    def body(i, carry):
        dd, vals, idxs = carry
        v = jnp.min(dd, axis=1, keepdims=True)  # (ROWS, 1)
        # first index attaining the min — lax.top_k's tie-break order
        j = jnp.min(jnp.where(dd == v, col, block_n), axis=1, keepdims=True)
        vals = jnp.where(slot == i, v, vals)
        idxs = jnp.where(slot == i, base + j, idxs)
        dd = jnp.where(col == j, jnp.inf, dd)
        return dd, vals, idxs

    init = (
        dd,
        jnp.full((ROWS, Lp), jnp.inf, jnp.float32),
        jnp.full((ROWS, Lp), -1, jnp.int32),
    )
    _, vals, idxs = jax.lax.fori_loop(0, L, body, init)
    vals_ref[...] = vals
    idx_ref[...] = idxs


@functools.partial(jax.jit, static_argnames=("L", "block_n", "interpret"))
def topk_select_pallas(
    dists: jax.Array,  # (B, N) float32 — smaller is better
    *,
    L: int,
    block_n: int = 1024,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Returns (vals (B, L), idx (B, L)) of the L smallest per row."""
    B, N = dists.shape
    Bp = -(-B // ROWS) * ROWS
    Np = -(-N // block_n) * block_n
    Lp = -(-L // 128) * 128
    d = jnp.pad(dists, ((0, Bp - B), (0, Np - N)), constant_values=jnp.inf)
    nblk = Np // block_n

    vals, idx = pl.pallas_call(
        functools.partial(_topk_block_kernel, L=L, block_n=block_n),
        grid=(Bp // ROWS, nblk),
        in_specs=[pl.BlockSpec((ROWS, block_n), lambda b, n: (b, n))],
        out_specs=[
            pl.BlockSpec((ROWS, Lp), lambda b, n: (b, n)),
            pl.BlockSpec((ROWS, Lp), lambda b, n: (b, n)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bp, nblk * Lp), jnp.float32),
            jax.ShapeDtypeStruct((Bp, nblk * Lp), jnp.int32),
        ],
        interpret=interpret,
    )(d)
    vals = vals[:B].reshape(B, nblk, Lp)[:, :, :L].reshape(B, nblk * L)
    idx = idx[:B].reshape(B, nblk, Lp)[:, :, :L].reshape(B, nblk * L)

    if nblk > 1:
        # stage 2: merge block winners (fixed shape, nblk·L ≪ N)
        neg, pos = jax.lax.top_k(-vals, L)
        out_vals = -neg
        out_idx = jnp.take_along_axis(idx, pos, axis=1)
    else:
        out_vals, out_idx = vals, idx  # already sorted ascending
    out_idx = jnp.where(jnp.isfinite(out_vals), out_idx, -1)
    return out_vals, out_idx
