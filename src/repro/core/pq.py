"""Product Quantization (PQ) — §2.1 / §3.4 of the paper.

The paper compresses vectors with PQ so that (a) the navigation structures fit
in fast storage and (b) distance comparisons are cheap. We reproduce:

  * k-means codebook training from a small sample (1000-vector bootstrap
    schema, refined with a 25 000-vector sample — §3.4 "Re-quantization"),
  * encode / decode,
  * ADC (asymmetric distance computation) lookup tables per query,
  * cross-schema distances so vectors encoded under the *old* schema remain
    comparable during re-quantization (§3.4), without a graph rebuild.

TPU adaptation (see DESIGN.md §2): on CPU the ADC inner loop is an L1-cache
table lookup; on TPU we express it as a one-hot × LUT contraction that maps
onto the MXU. The pure-jnp forms here are the reference; the Pallas kernels
in ``repro.kernels`` implement the tiled versions.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

# Paper operating points (§3.4): bootstrap schema after 1000 vectors,
# refine ("re-quantize") after 25 000.
BOOTSTRAP_SAMPLE = 1000
REFINE_SAMPLE = 25000


class PQSchema(NamedTuple):
    """A trained product quantizer.

    codebooks: (M, K, dsub) float32 — M subspaces, K centroids each.
    version:   () int32 — schema version, bumped by re-quantization.
    """

    codebooks: jax.Array
    version: jax.Array

    @property
    def M(self) -> int:
        return self.codebooks.shape[0]

    @property
    def K(self) -> int:
        return self.codebooks.shape[1]

    @property
    def dsub(self) -> int:
        return self.codebooks.shape[2]

    @property
    def dim(self) -> int:
        return self.M * self.dsub


def _split(x: jax.Array, M: int) -> jax.Array:
    """(..., D) -> (..., M, dsub)."""
    *lead, D = x.shape
    return x.reshape(*lead, M, D // M)


# ---------------------------------------------------------------------------
# Training (k-means per subspace, Lloyd iterations)
# ---------------------------------------------------------------------------


def _kmeans_one(key: jax.Array, pts: jax.Array, K: int, iters: int) -> jax.Array:
    """k-means over pts (S, dsub) -> centroids (K, dsub)."""
    S = pts.shape[0]
    init_idx = jax.random.choice(key, S, shape=(K,), replace=S < K)
    init = pts[init_idx]

    def step(cent, _):
        # assign
        d = (
            jnp.sum(pts * pts, -1, keepdims=True)
            - 2.0 * pts @ cent.T
            + jnp.sum(cent * cent, -1)[None, :]
        )  # (S, K)
        assign = jnp.argmin(d, axis=-1)
        onehot = jax.nn.one_hot(assign, K, dtype=pts.dtype)  # (S, K)
        counts = onehot.sum(0)  # (K,)
        sums = onehot.T @ pts  # (K, dsub)
        new = jnp.where(counts[:, None] > 0, sums / jnp.maximum(counts[:, None], 1.0), cent)
        return new, None

    cent, _ = jax.lax.scan(step, init, None, length=iters)
    return cent


@functools.partial(jax.jit, static_argnames=("M", "K", "iters"))
def train_pq(key: jax.Array, sample: jax.Array, M: int, K: int = 256, iters: int = 12) -> PQSchema:
    """Train a PQ schema from a sample (S, D). D must be divisible by M."""
    S, D = sample.shape
    assert D % M == 0, f"dim {D} not divisible by M={M}"
    sub = _split(sample, M).transpose(1, 0, 2)  # (M, S, dsub)
    keys = jax.random.split(key, M)
    codebooks = jax.vmap(lambda k, p: _kmeans_one(k, p, K, iters))(keys, sub)
    return PQSchema(codebooks=codebooks.astype(jnp.float32), version=jnp.int32(0))


def refine_pq(key: jax.Array, schema: PQSchema, sample: jax.Array, iters: int = 12) -> PQSchema:
    """Re-quantization (§3.4): retrain on a larger sample; bump version.

    The refined schema is "related" to the original (same M/K; warm-started
    from the old centroids so codes drift little), which is what makes
    cross-schema distances meaningful in the paper.
    """
    M, K = schema.M, schema.K
    sub = _split(sample, M).transpose(1, 0, 2)  # (M, S, dsub)

    def one(pts, cent0):
        def step(cent, _):
            d = (
                jnp.sum(pts * pts, -1, keepdims=True)
                - 2.0 * pts @ cent.T
                + jnp.sum(cent * cent, -1)[None, :]
            )
            assign = jnp.argmin(d, axis=-1)
            onehot = jax.nn.one_hot(assign, K, dtype=pts.dtype)
            counts = onehot.sum(0)
            sums = onehot.T @ pts
            return jnp.where(counts[:, None] > 0, sums / jnp.maximum(counts[:, None], 1.0), cent), None

        cent, _ = jax.lax.scan(step, cent0, None, length=iters)
        return cent

    codebooks = jax.vmap(one)(sub, schema.codebooks)
    return PQSchema(codebooks=codebooks.astype(jnp.float32), version=schema.version + 1)


# ---------------------------------------------------------------------------
# Encode / decode
# ---------------------------------------------------------------------------


@jax.jit
def encode(schema: PQSchema, x: jax.Array) -> jax.Array:
    """(..., D) float -> (..., M) uint8 codes."""
    sub = _split(x, schema.M)  # (..., M, dsub)
    cent = schema.codebooks  # (M, K, dsub)
    # dists (..., M, K)
    d = (
        jnp.sum(sub * sub, -1, keepdims=True)
        - 2.0 * jnp.einsum("...md,mkd->...mk", sub, cent)
        + jnp.sum(cent * cent, -1)
    )
    return jnp.argmin(d, axis=-1).astype(jnp.uint8)


@jax.jit
def decode(schema: PQSchema, codes: jax.Array) -> jax.Array:
    """(..., M) uint8 -> (..., D) float32 reconstruction."""
    flat = codes.reshape(-1, schema.M).astype(jnp.int32)
    sub = jnp.arange(schema.M)[None, :]
    gathered = schema.codebooks[sub, flat]  # (N, M, dsub)
    return gathered.reshape(*codes.shape[:-1], schema.dim)


@jax.jit
def decode_versioned(codebooks: jax.Array, codes: jax.Array,
                     versions: jax.Array) -> jax.Array:
    """(V, M, K, dsub) stacked codebooks, (C, M) codes, (C,) schema
    versions -> (C, D) reconstruction. One gather of each row's centroids:
    indexing ``codebooks[versions]`` first would copy a whole (M, K, dsub)
    codebook per row."""
    M, dsub = codebooks.shape[1], codebooks.shape[3]
    sub = jnp.arange(M)[None, :]
    picked = codebooks[versions.astype(jnp.int32)[:, None], sub,
                       codes.astype(jnp.int32)]  # (C, M, dsub)
    return picked.reshape(codes.shape[0], M * dsub)


# ---------------------------------------------------------------------------
# ADC lookup tables + distances
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("metric",))
def adc_lut(schema: PQSchema, q: jax.Array, metric: str = "l2") -> jax.Array:
    """LUT for query q: (..., M, K) float32.

    l2: squared L2 between query subvector and centroid.
    ip: negative inner product (so smaller = closer, uniformly min-is-best).
    cosine: callers should pre-normalize; then ip == cosine distance - 1.
    """
    sub = _split(q, schema.M)  # (..., M, dsub)
    cent = schema.codebooks  # (M, K, dsub)
    if metric == "l2":
        lut = (
            jnp.sum(sub * sub, -1, keepdims=True)
            - 2.0 * jnp.einsum("...md,mkd->...mk", sub, cent)
            + jnp.sum(cent * cent, -1)
        )
    elif metric in ("ip", "cosine"):
        lut = -jnp.einsum("...md,mkd->...mk", sub, cent)
    else:
        raise ValueError(f"unknown metric {metric!r}")
    return lut.astype(jnp.float32)


@jax.jit
def adc_distance(lut: jax.Array, codes: jax.Array) -> jax.Array:
    """Asymmetric distance from the LUT's query to encoded points.

    lut:   (M, K) float32
    codes: (..., M) uint8
    -> (...) float32
    """
    M = lut.shape[0]
    flat = codes.reshape(-1, M).astype(jnp.int32)  # (C, M)
    picked = jnp.take_along_axis(lut.T, flat, axis=0) if False else None  # noqa
    # gather per subspace: lut[m, code[c, m]]
    d = jnp.take_along_axis(lut[None, :, :], flat[:, :, None], axis=2)[..., 0]  # (C, M)
    return d.sum(-1).reshape(codes.shape[:-1])


@jax.jit
def adc_distance_onehot(lut: jax.Array, codes: jax.Array) -> jax.Array:
    """MXU-friendly ADC: one-hot(codes) · lut — same result as adc_distance.

    This is the TPU formulation the pq_adc Pallas kernel tiles: the table
    lookup becomes a (C, M·K) × (M·K,) contraction on the MXU instead of a
    scalar gather. See DESIGN.md §2.
    """
    M, K = lut.shape
    flat = codes.reshape(-1, M)
    onehot = jax.nn.one_hot(flat, K, dtype=lut.dtype)  # (C, M, K)
    d = jnp.einsum("cmk,mk->c", onehot, lut)
    return d.reshape(codes.shape[:-1])


# ---------------------------------------------------------------------------
# Cross-schema support (re-quantization without rebuild)
# ---------------------------------------------------------------------------


def multi_lut(schemas: tuple[PQSchema, ...], q: jax.Array, metric: str = "l2") -> jax.Array:
    """Stack LUTs for several coexisting schemas: (V, M, K).

    During re-quantization old codes (schema v) and new codes (schema v+1)
    coexist; each vector row is tagged with its schema version and distances
    are computed against the matching LUT. Distances remain comparable
    because both LUTs measure against the *same* query in the original space
    (§3.4: "such distance calculations are meaningful").
    """
    return jnp.stack([adc_lut(s, q, metric) for s in schemas], axis=0)


def _adc_gather(luts, flat, ver):
    """One gather of ``luts[ver, m, code]``: (C, M)."""
    M = luts.shape[1]
    return luts[ver[:, None], jnp.arange(M)[None, :], flat]


def _adc_select(luts, flat, ver):
    """``luts[ver, m, code]`` as a compare-select-sum over the K centroids:
    each row has exactly one hit, so the sum is the table entry itself."""
    V, _, K = luts.shape
    hit = flat[:, :, None] == jnp.arange(K)
    return sum(jnp.where(ver[:, None] == v,
                         jnp.where(hit, luts[v], 0.0).sum(-1), 0.0)
               for v in range(V))


@jax.jit
def adc_distance_versioned(luts: jax.Array, codes: jax.Array, versions: jax.Array) -> jax.Array:
    """ADC with a per-row schema version.

    luts:     (V, M, K) float32
    codes:    (..., M) uint8
    versions: (...,) int — index into luts

    Both forms give the same bits. A TPU pays per gathered scalar (one per
    row and subspace, every search round), so it takes the fused vector
    pass; a CPU gathers cheaply and would pay K× the work for the select.
    """
    M = luts.shape[1]
    flat = codes.reshape(-1, M).astype(jnp.int32)
    ver = versions.reshape(-1).astype(jnp.int32)
    d = jax.lax.platform_dependent(luts, flat, ver, cpu=_adc_gather,
                                   default=_adc_select)  # (C, M)
    return d.sum(-1).reshape(codes.shape[:-1])


# ---------------------------------------------------------------------------
# Exact distances (document-store re-rank path)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("metric",))
def exact_distance(q: jax.Array, x: jax.Array, metric: str = "l2") -> jax.Array:
    """q (..., D), x (..., D) -> (...) float32 full-precision distance."""
    if metric == "l2":
        diff = q - x
        return jnp.sum(diff * diff, -1)
    if metric in ("ip", "cosine"):
        return -jnp.sum(q * x, -1)
    raise ValueError(metric)


@functools.partial(jax.jit, static_argnames=("metric",))
def pairwise_distance(a: jax.Array, b: jax.Array, metric: str = "l2") -> jax.Array:
    """a (N, D), b (M, D) -> (N, M)."""
    if metric == "l2":
        return (
            jnp.sum(a * a, -1, keepdims=True)
            - 2.0 * a @ b.T
            + jnp.sum(b * b, -1)[None, :]
        )
    if metric in ("ip", "cosine"):
        return -(a @ b.T)
    raise ValueError(metric)
