"""pq_encode — PQ encoding (nearest centroid per subspace) tiled for TPU.

Workload: x (N, D) float32, codebooks (M, K, dsub) → codes (N, M).
Per subspace m: scores (K, Nb) = ‖c‖² − 2·C_m·x_mᵀ (+ ‖x_m‖²) → argmin
over K.

Layout: x is fed transposed per subspace, as (M, dsub, N), so a block
(1, dsub, Nb) keeps the vectors on the 128-wide lane axis and dsub on the
sublanes, and the (1, K, dsub) codebook block covers its array's last two
dims whole. Each grid step (N/Nb, M) is one (K, dsub) × (dsub, Nb) MXU
product at HIGHEST precision; the argmin runs down the K sublanes as a
min-reduce plus an iota compare (first index wins, like ``jnp.argmin``),
and one (1, 1, Nb) row of codes is stored per step. The ‖x‖² term is
constant across K and irrelevant to the argmin, so the kernel skips it —
scores are shifted but the codes are identical.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _encode_kernel(x_ref, cent_ref, out_ref):
    """x_ref: (1, dsub, Nb); cent_ref: (1, K, dsub); out_ref: (1, 1, Nb) i32."""
    x = x_ref[0]  # (dsub, Nb)
    cent = cent_ref[0]  # (K, dsub)
    K = cent.shape[0]
    cross = jnp.dot(cent, x, precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)  # (K, Nb)
    scores = jnp.sum(cent * cent, axis=1, keepdims=True) - 2.0 * cross
    best = jnp.min(scores, axis=0, keepdims=True)  # (1, Nb)
    rows = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
    code = jnp.min(jnp.where(scores == best, rows, K), axis=0, keepdims=True)
    out_ref[0] = code


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def pq_encode_pallas(
    x: jax.Array,  # (N, D)
    codebooks: jax.Array,  # (M, K, dsub)
    *,
    block_n: int = 256,
    interpret: bool = False,
) -> jax.Array:
    N, D = x.shape
    M, K, dsub = codebooks.shape
    assert D == M * dsub
    Np = -(-N // block_n) * block_n
    xp = jnp.pad(x.astype(jnp.float32), ((0, Np - N), (0, 0)))
    x_t = xp.reshape(Np, M, dsub).transpose(1, 2, 0)  # (M, dsub, Np)

    out = pl.pallas_call(
        _encode_kernel,
        grid=(Np // block_n, M),
        in_specs=[
            pl.BlockSpec((1, dsub, block_n), lambda n, m: (m, 0, n)),
            pl.BlockSpec((1, K, dsub), lambda n, m: (m, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_n), lambda n, m: (m, 0, n)),
        out_shape=jax.ShapeDtypeStruct((M, 1, Np), jnp.int32),
        interpret=interpret,
    )(x_t, codebooks.astype(jnp.float32))
    return out.reshape(M, Np)[:, :N].T.astype(jnp.uint8)
