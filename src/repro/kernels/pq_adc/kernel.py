"""pq_adc — ADC distance scan, the paper's hottest loop, tiled for TPU.

Workload: for a batch of queries with precomputed LUTs (B, M, K) and a set
of PQ codes (C, M) uint8, compute distances (B, C):

    out[b, c] = Σ_m  lut[b, m, codes[c, m]]

CPU DiskANN does this as L1-cache scalar lookups; a TPU has no scalar
gather path worth using, but it has an MXU. We rewrite the lookup as a
one-hot contraction

    out[b, c] = Σ_m  lut[b, m, :] · onehot(codes[c, m])

and tile it: a (Bb, M, K) block of LUTs lives in VMEM across the whole
scan; codes stream through VMEM in (M, Cb) tiles. The one-hot never
materializes in HBM — it is built per (tile, m) as a (K, Cb) compare
against a sublane iota and fed straight to the MXU as a
(Bb, K) × (K, Cb) product at HIGHEST precision, which reproduces each f32
table entry exactly (every one-hot column holds a single 1).

Layout: both operands are laid out so that the subspace index ``m`` walks
a leading, untiled dimension — LUTs as (M, B, K), codes as (M, 1, C) —
which Mosaic indexes dynamically; the tiled (last two) block dims are
(Bb, K) and (1, Cb) with Bb a multiple of 8 and Cb of 128.

Grid: (B/Bb, C/Cb) — codes tiles innermost so the LUT block is reused
across the entire scan.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _adc_kernel(lut_ref, codes_ref, out_ref):
    """lut_ref: (M, Bb, K) f32; codes_ref: (M, 1, Cb) i32; out_ref: (Bb, Cb)."""
    M, _, K = lut_ref.shape
    Cb = codes_ref.shape[2]
    rows = jax.lax.broadcasted_iota(jnp.int32, (K, Cb), 0)

    def body(m, acc):
        onehot = (rows == codes_ref[m]).astype(jnp.float32)  # (K, Cb)
        return acc + jnp.dot(lut_ref[m], onehot,
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)

    out_ref[...] = jax.lax.fori_loop(
        0, M, body, jnp.zeros(out_ref.shape, jnp.float32))


@functools.partial(jax.jit, static_argnames=("block_b", "block_c", "interpret"))
def pq_adc_pallas(
    lut: jax.Array,  # (B, M, K) float32
    codes: jax.Array,  # (C, M) uint8/int32
    *,
    block_b: int = 8,
    block_c: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Distances (B, C) via the tiled one-hot ADC kernel."""
    B, M, K = lut.shape
    C = codes.shape[0]
    Bp = -(-B // block_b) * block_b
    Cp = -(-C // block_c) * block_c
    lut_t = jnp.pad(lut.astype(jnp.float32).transpose(1, 0, 2),
                    ((0, 0), (0, Bp - B), (0, 0)))
    codes_t = jnp.pad(codes.astype(jnp.int32).T, ((0, 0), (0, Cp - C)))

    out = pl.pallas_call(
        _adc_kernel,
        grid=(Bp // block_b, Cp // block_c),
        in_specs=[
            pl.BlockSpec((M, block_b, K), lambda b, c: (0, b, 0)),
            pl.BlockSpec((M, 1, block_c), lambda b, c: (0, 0, c)),
        ],
        out_specs=pl.BlockSpec((block_b, block_c), lambda b, c: (b, c)),
        out_shape=jax.ShapeDtypeStruct((Bp, Cp), jnp.float32),
        interpret=interpret,
    )(lut_t, codes_t.reshape(M, 1, Cp))
    return out[:B, :C]
