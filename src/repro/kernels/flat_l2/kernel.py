"""flat_l2 — tiled full-precision distance matrix (re-rank / brute force).

Workload: queries (B, D) × vectors (N, D) → squared-L2 (or −IP) distances
(B, N). This backs the Fig 5 re-rank (C ≈ 50 vectors per query) and the
small-collection brute-force plan (§3).

Classic three-level matmul tiling: grid (B/Bb, N/Nb, D/Db) with the
contraction dimension innermost; the output block is revisited across the
D-steps and accumulated in place (f32). Block shapes keep every operand in
VMEM with MXU-aligned (multiple-of-128) matmul dims; norms are added on the
final contraction step so the kernel emits finished distances. The norms
ride in as 2-D column / row blocks, (Bb, 1) and (1, Nb): Mosaic lays out
1-D operands differently from XLA and refuses them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _flat_kernel(q_ref, x_ref, q2_ref, x2_ref, out_ref, *, n_dsteps: int, metric: str):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += jax.lax.dot_general(
        q_ref[...], x_ref[...], (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == n_dsteps - 1)
    def _finish():
        if metric == "l2":
            out_ref[...] = q2_ref[...] + x2_ref[...] - 2.0 * out_ref[...]
        else:  # ip: negative inner product
            out_ref[...] = -out_ref[...]


@functools.partial(
    jax.jit, static_argnames=("block_b", "block_n", "block_d", "metric", "interpret")
)
def flat_l2_pallas(
    q: jax.Array,  # (B, D)
    x: jax.Array,  # (N, D)
    *,
    block_b: int = 128,
    block_n: int = 256,
    block_d: int = 128,
    metric: str = "l2",
    interpret: bool = False,
) -> jax.Array:
    B, D = q.shape
    N = x.shape[0]
    bb, bn, bd = min(block_b, B), min(block_n, N), min(block_d, D)

    def pad_to(a, m0, m1):
        p0 = (-a.shape[0]) % m0
        p1 = (-a.shape[1]) % m1
        return jnp.pad(a, ((0, p0), (0, p1))) if (p0 or p1) else a

    qp = pad_to(q.astype(jnp.float32), bb, bd)
    xp = pad_to(x.astype(jnp.float32), bn, bd)
    Bp, Dp = qp.shape
    Np = xp.shape[0]
    q2 = jnp.sum(qp * qp, -1, keepdims=True)  # (Bp, 1)
    x2 = jnp.sum(xp * xp, -1)[None, :]  # (1, Np)
    n_dsteps = Dp // bd

    out = pl.pallas_call(
        functools.partial(_flat_kernel, n_dsteps=n_dsteps, metric=metric),
        grid=(Bp // bb, Np // bn, n_dsteps),
        in_specs=[
            pl.BlockSpec((bb, bd), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bd), lambda i, j, k: (j, k)),
            pl.BlockSpec((bb, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bb, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Bp, Np), jnp.float32),
        interpret=interpret,
    )(qp, xp, q2, x2)
    out = out[:B, :N]
    if metric == "l2":
        out = jnp.maximum(out, 0.0)
    return out
