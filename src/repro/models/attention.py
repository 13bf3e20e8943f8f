"""Attention: GQA/MQA (with qk_norm, RoPE variants) and MLA (DeepSeek).

Three entry points per flavor:
  * ``*_train``   — full-sequence self-attention (causal or bidirectional);
  * ``*_prefill`` — same, but also returns the KV cache;
  * ``*_decode``  — one new token against a cache of ``cache_len`` tokens.

Decode KV caches can be *sequence-sharded* across the `model` mesh axis
(constraint applied in steps.py): softmax and the PV contraction over a
sharded S dimension lower to partial reductions + all-reduce under GSPMD —
the flash-decoding split-KV scheme expressed declaratively.

MLA decode uses the *absorbed* formulation: W_UK folds into the query and
W_UV into the output, so per-step attention runs entirely in the compressed
kv_lora space and the cache stays (S, kv_lora + rope_dim) per sequence —
the architecture-level analogue of the paper's "navigate in quantized
space, touch full precision rarely".
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .config import MLAConfig, ModelConfig
from .layers import apply_rope, dense_init, rmsnorm, rmsnorm_init

NEG_INF = -1e30


class KVCache(NamedTuple):
    k: jax.Array  # (B, S_max, H_kv, Dh)   [MLA: (B, S_max, kv_lora+rope)]
    v: jax.Array  # (B, S_max, H_kv, Dh)   [MLA: unused placeholder (B,0)]


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------


def gqa_init(key, cfg: ModelConfig, dtype) -> dict:
    dm, H, Hkv, Dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 6)
    p = {
        "wq": dense_init(ks[0], (dm, H * Dh), dtype),
        "wk": dense_init(ks[1], (dm, Hkv * Dh), dtype),
        "wv": dense_init(ks[2], (dm, Hkv * Dh), dtype),
        "wo": dense_init(ks[3], (H * Dh, dm), dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(Dh, dtype)
        p["k_norm"] = rmsnorm_init(Dh, dtype)
    return p


def _qkv(params, cfg: ModelConfig, x, positions):
    B, S, _ = x.shape
    H, Hkv, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = (x @ params["wq"]).reshape(B, S, H, Dh)
    k = (x @ params["wk"]).reshape(B, S, Hkv, Dh)
    v = (x @ params["wv"]).reshape(B, S, Hkv, Dh)
    if cfg.qk_norm:
        q = rmsnorm(params["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(params["k_norm"], k, cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta, cfg.rope)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.rope)
    return q, k, v


def _cp_constrain(x: jax.Array, seq_axis: int) -> jax.Array:
    """Shard dim `seq_axis` over the `model` mesh axis (context parallelism)
    under the ambient mesh; no-op without one or when indivisible."""
    m = jax.sharding.get_abstract_mesh()
    if "model" not in m.axis_names:
        return x
    if x.shape[seq_axis] % m.shape["model"] != 0:
        return x
    spec = [None] * x.ndim
    spec[seq_axis] = "model"
    if x.shape[0] % 16 == 0 and "data" in m.axis_names:
        pass  # leave batch to propagation; over-constraining hurts
    return jax.lax.with_sharding_constraint(x, P(*spec))


def _sdpa_core(q, k, v, H, Hkv, causal: bool, q_offset=0, cp: bool = False):
    """q (B,Sq,H,Dh) × k,v (B,Sk,Hkv,Dh) → (B,Sq,H,Dh). f32 softmax."""
    B, Sq, _, Dh = q.shape
    Sk = k.shape[1]
    G = H // Hkv
    qg = q.reshape(B, Sq, Hkv, G, Dh)
    if cp:
        qg = _cp_constrain(qg, 1)  # queries sharded over model on Sq
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(Dh).astype(jnp.float32)
    if causal:
        qpos = q_offset + jnp.arange(Sq)
        mask = qpos[:, None] >= jnp.arange(Sk)[None, :]
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    # NOTE (§Perf iteration 6): constraining `scores`/`out` here forces
    # GSPMD to re-shard the S² tensor at the constraint boundaries in the
    # backward pass (+7.3 GiB of all-gathers per layer measured on smollm).
    # Constraining only the (small) query tensor lets the Sq sharding
    # propagate through softmax and the PV contraction for free.
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", w, v)
    return out.reshape(B, Sq, H * Dh)


def _sdpa(q, k, v, H, Hkv, causal: bool, q_offset=0, cp: bool = False,
          q_chunk: int = 0, unroll: bool = False):
    """SDPA with optional query-block chunking (flash-attention's memory
    shape, declaratively): peak scores buffer is (B, H, q_chunk, Sk) instead
    of (B, H, Sq, Sk). On TPU the Pallas flash kernel would replace the
    chunk body; the chunk loop itself is a `lax.scan` (or unrolled for the
    dry-run's cost extraction, like the SSM chunk loops)."""
    B, Sq, _, Dh = q.shape
    if not q_chunk or Sq <= q_chunk or Sq % q_chunk != 0:
        return _sdpa_core(q, k, v, H, Hkv, causal, q_offset, cp)
    nch = Sq // q_chunk
    qs = q.reshape(B, nch, q_chunk, H, Dh).swapaxes(0, 1)  # (nch, B, qc, H, Dh)
    offs = q_offset + jnp.arange(nch) * q_chunk

    def body(qc, off):
        return _sdpa_core(qc, k, v, H, Hkv, causal, off, cp)

    if unroll:
        outs = jnp.stack([body(qs[i], offs[i]) for i in range(nch)])
    else:
        _, outs = jax.lax.scan(lambda c, inp: (c, body(*inp)), None, (qs, offs))
    return outs.swapaxes(0, 1).reshape(B, Sq, H * Dh)


def gqa_train(params, cfg: ModelConfig, x, positions) -> jax.Array:
    q, k, v = _qkv(params, cfg, x, positions)
    out = _sdpa(q, k, v, cfg.num_heads, cfg.num_kv_heads, cfg.causal,
                cp=cfg.cp_attn, q_chunk=cfg.attn_q_chunk,
                unroll=cfg.force_unroll)
    return out @ params["wo"]


def gqa_prefill(params, cfg: ModelConfig, x, positions, cache: KVCache):
    q, k, v = _qkv(params, cfg, x, positions)
    S = x.shape[1]
    cache = KVCache(
        k=jax.lax.dynamic_update_slice_in_dim(cache.k, k.astype(cache.k.dtype), 0, 1),
        v=jax.lax.dynamic_update_slice_in_dim(cache.v, v.astype(cache.v.dtype), 0, 1),
    )
    out = _sdpa(q, k, v, cfg.num_heads, cfg.num_kv_heads, causal=True,
                cp=cfg.cp_attn, q_chunk=cfg.attn_q_chunk,
                unroll=cfg.force_unroll)
    return out @ params["wo"], cache


def gqa_decode(params, cfg: ModelConfig, x, cache: KVCache, cache_len):
    """x (B, 1, dm); attends to cache[:cache_len] + itself."""
    B = x.shape[0]
    H, Hkv, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    pos = jnp.full((B, 1), cache_len, jnp.int32)
    q, k, v = _qkv(params, cfg, x, pos)
    k_cache = jax.lax.dynamic_update_slice(cache.k, k.astype(cache.k.dtype), (0, cache_len, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(cache.v, v.astype(cache.v.dtype), (0, cache_len, 0, 0))
    S_max = k_cache.shape[1]

    G = H // Hkv
    qg = q.reshape(B, 1, Hkv, G, Dh)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_cache.astype(q.dtype)).astype(jnp.float32)
    scores = scores / jnp.sqrt(Dh).astype(jnp.float32)
    valid = jnp.arange(S_max)[None, :] <= cache_len  # includes the new token
    scores = jnp.where(valid[:, None, None, None, :][0], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", w, v_cache.astype(q.dtype)).reshape(B, 1, H * Dh)
    return out @ params["wo"], KVCache(k=k_cache, v=v_cache)


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2)
# ---------------------------------------------------------------------------


def mla_init(key, cfg: ModelConfig, dtype) -> dict:
    m: MLAConfig = cfg.mla
    dm, H = cfg.d_model, cfg.num_heads
    ks = jax.random.split(key, 6)
    return {
        "wq": dense_init(ks[0], (dm, H * (m.qk_nope_head_dim + m.qk_rope_head_dim)), dtype),
        "wdkv": dense_init(ks[1], (dm, m.kv_lora_rank), dtype),
        "wkr": dense_init(ks[2], (dm, m.qk_rope_head_dim), dtype),
        "kv_norm": rmsnorm_init(m.kv_lora_rank, dtype),
        "wuk": dense_init(ks[3], (m.kv_lora_rank, H * m.qk_nope_head_dim), dtype),
        "wuv": dense_init(ks[4], (m.kv_lora_rank, H * m.v_head_dim), dtype),
        "wo": dense_init(ks[5], (H * m.v_head_dim, dm), dtype),
    }


def _mla_q(params, cfg, x, positions):
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.num_heads
    q = (x @ params["wq"]).reshape(B, S, H, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope, q_rope = q[..., : m.qk_nope_head_dim], q[..., m.qk_nope_head_dim :]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, "full")
    return q_nope, q_rope


def _mla_attend(q_nope, q_rope, k_nope, k_rope, v, m, q_offset, dtype):
    """One query block of MLA attention: (B,Sq,H,·) vs full keys."""
    Sq, Sk = q_nope.shape[1], k_nope.shape[1]
    scale = 1.0 / jnp.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    scores = (
        jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope)
        + jnp.einsum("bqhd,bkxd->bhqk", q_rope, k_rope)
    ).astype(jnp.float32) * scale
    qpos = q_offset + jnp.arange(Sq)
    mask = qpos[:, None] >= jnp.arange(Sk)[None, :]
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v)


def mla_train(params, cfg: ModelConfig, x, positions) -> jax.Array:
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.num_heads
    q_nope, q_rope = _mla_q(params, cfg, x, positions)
    c_kv = rmsnorm(params["kv_norm"], x @ params["wdkv"], cfg.norm_eps)  # (B,S,r)
    k_rope = apply_rope(
        (x @ params["wkr"])[:, :, None, :], positions, cfg.rope_theta, "full"
    )  # (B,S,1,dr) shared across heads
    k_nope = (c_kv @ params["wuk"]).reshape(B, S, H, m.qk_nope_head_dim)
    v = (c_kv @ params["wuv"]).reshape(B, S, H, m.v_head_dim)

    qc = cfg.attn_q_chunk
    if not qc or S <= qc or S % qc != 0:
        out = _mla_attend(q_nope, q_rope, k_nope, k_rope, v, m, 0, x.dtype)
    else:
        nch = S // qc
        qn = q_nope.reshape(B, nch, qc, H, -1).swapaxes(0, 1)
        qr = q_rope.reshape(B, nch, qc, H, -1).swapaxes(0, 1)
        offs = jnp.arange(nch) * qc

        def body(qnc, qrc, off):
            return _mla_attend(qnc, qrc, k_nope, k_rope, v, m, off, x.dtype)

        if cfg.force_unroll:
            outs = jnp.stack([body(qn[i], qr[i], offs[i]) for i in range(nch)])
        else:
            _, outs = jax.lax.scan(
                lambda c, inp: (c, body(*inp)), None, (qn, qr, offs)
            )
        out = outs.swapaxes(0, 1).reshape(B, S, H, m.v_head_dim)
    out = out.reshape(B, S, H * m.v_head_dim)
    return out @ params["wo"]


def mla_prefill(params, cfg: ModelConfig, x, positions, cache: KVCache):
    """Cache the compressed (c_kv ‖ k_rope) stream — (B, S, r + dr)."""
    m = cfg.mla
    c_kv = rmsnorm(params["kv_norm"], x @ params["wdkv"], cfg.norm_eps)
    k_rope = apply_rope(
        (x @ params["wkr"])[:, :, None, :], positions, cfg.rope_theta, "full"
    )[:, :, 0, :]
    packed = jnp.concatenate([c_kv, k_rope], axis=-1).astype(cache.k.dtype)
    new_k = jax.lax.dynamic_update_slice_in_dim(cache.k, packed, 0, 1)
    out = mla_train(params, cfg, x, positions)
    return out, KVCache(k=new_k, v=cache.v)


def mla_decode(params, cfg: ModelConfig, x, cache: KVCache, cache_len):
    """Absorbed MLA decode: attention entirely in kv_lora space."""
    m = cfg.mla
    B = x.shape[0]
    H = cfg.num_heads
    pos = jnp.full((B, 1), cache_len, jnp.int32)
    q_nope, q_rope = _mla_q(params, cfg, x, pos)  # (B,1,H,dn),(B,1,H,dr)

    c_kv_new = rmsnorm(params["kv_norm"], x @ params["wdkv"], cfg.norm_eps)
    k_rope_new = apply_rope(
        (x @ params["wkr"])[:, :, None, :], pos, cfg.rope_theta, "full"
    )[:, :, 0, :]
    packed = jnp.concatenate([c_kv_new, k_rope_new], axis=-1).astype(cache.k.dtype)
    k_cache = jax.lax.dynamic_update_slice(cache.k, packed, (0, cache_len, 0))
    S_max = k_cache.shape[1]
    c_all = k_cache[..., : m.kv_lora_rank].astype(x.dtype)  # (B,S,r)
    r_all = k_cache[..., m.kv_lora_rank :].astype(x.dtype)  # (B,S,dr)

    # absorb W_UK into q: q' (B,1,H,r)
    wuk = params["wuk"].reshape(m.kv_lora_rank, H, m.qk_nope_head_dim)
    q_abs = jnp.einsum("bqhd,rhd->bqhr", q_nope, wuk)
    scale = 1.0 / jnp.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    scores = (
        jnp.einsum("bqhr,bkr->bhqk", q_abs, c_all)
        + jnp.einsum("bqhd,bkd->bhqk", q_rope, r_all)
    ).astype(jnp.float32) * scale
    valid = jnp.arange(S_max)[None, :] <= cache_len
    scores = jnp.where(valid[:, None, None, :][0], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    ctx = jnp.einsum("bhqk,bkr->bqhr", w, c_all)  # (B,1,H,r)
    # absorb W_UV on the way out
    wuv = params["wuv"].reshape(m.kv_lora_rank, H, m.v_head_dim)
    out = jnp.einsum("bqhr,rhd->bqhd", ctx, wuv).reshape(B, 1, H * m.v_head_dim)
    return out @ params["wo"], KVCache(k=k_cache, v=cache.v)
