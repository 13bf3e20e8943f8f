"""Sharding rules: parameters, activations, caches → PartitionSpecs.

Scheme (MaxText-style 2D):
  * `data` axis: FSDP — every ≥2D weight shards its d_model-ish (first big)
    dimension over `data`;
  * `model` axis: TP — heads / ffn / vocab (last big) dimension over `model`;
  * MoE experts shard their leading E dimension over `model` (EP);
  * `pod` axis (multi-pod mesh): pure DP — composes with `data` on the batch
    dimension only, so cross-pod traffic is exactly the gradient all-reduce;
  * decode KV caches shard batch over `data` and the *sequence* dimension
    over `model` (flash-decoding-style split-KV — the only layout that fits
    32k–500k caches in HBM; softmax over the sharded S lowers to partial
    reductions + all-reduce under GSPMD);
  * every dim only shards when divisible by the axis size (e.g. hubert's
    vocab of 504 stays replicated on its V dim rather than failing).
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P


from .config import ModelConfig


def _axsize(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1


def _fits(dim: int, mesh: Mesh, name: Optional[str]) -> Optional[str]:
    if name is None or name not in mesh.axis_names:
        return None
    return name if dim % _axsize(mesh, name) == 0 else None


def dp_axes(mesh: Mesh) -> tuple[str, ...]:
    """Batch-sharding axes: ('pod','data') on multi-pod, ('data',) otherwise."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def batch_spec(mesh: Mesh, batch: int, ndim: int) -> P:
    axes = dp_axes(mesh)
    total = int(np.prod([_axsize(mesh, a) for a in axes]))
    first = axes if batch % total == 0 else ()
    return P(first if first else None, *([None] * (ndim - 1)))


def param_spec(path: str, shape: tuple[int, ...], mesh: Mesh, stacked: bool) -> P:
    """Sharding rule for one parameter leaf.

    path: '/'-joined key path (e.g. 'blocks/mixer/wq'); stacked: leading L axis.
    """
    lead: list[Any] = [None] if stacked else []
    dims = shape[1:] if stacked else shape
    name = path.rsplit("/", 1)[-1]

    def spec(*entries):
        return P(*lead, *entries)

    if len(dims) == 0:
        return spec()
    if len(dims) == 1:
        # norms / biases / small vectors: shard over data when divisible
        return spec(_fits(dims[0], mesh, "data"))
    if name == "embed":  # (V, dm)
        return spec(_fits(dims[0], mesh, "model"), _fits(dims[1], mesh, "data"))
    if name == "lm_head":  # (dm, V)
        return spec(_fits(dims[0], mesh, "data"), _fits(dims[1], mesh, "model"))
    if name == "router":  # (dm, E) — replicate E for stable routing math
        return spec(_fits(dims[0], mesh, "data"), None)
    if len(dims) == 3:  # MoE expert stacks (E, dm, ff) / (E, ff, dm)
        return spec(
            _fits(dims[0], mesh, "model"),
            _fits(dims[1], mesh, "data"),
            None,
        )
    if len(dims) == 2:
        if name in ("wo", "w2", "out_proj", "wuk", "wuv"):
            # output-side projections: (big, dm) — model on the input dim
            return spec(_fits(dims[0], mesh, "model"), _fits(dims[1], mesh, "data"))
        # input-side projections: (dm, big)
        return spec(_fits(dims[0], mesh, "data"), _fits(dims[1], mesh, "model"))
    return spec(*([None] * len(dims)))


def param_specs(params: Any, cfg: ModelConfig, mesh: Mesh) -> Any:
    """PartitionSpec pytree matching `params` (works on ShapeDtypeStructs).

    Every leaf under 'blocks' carries a leading segment-stack axis (see
    model.segments), so block params are always `stacked`."""

    def walk(tree, path, in_blocks):
        if isinstance(tree, dict):
            return {
                k: walk(v, f"{path}/{k}" if path else k, in_blocks or k == "blocks")
                for k, v in tree.items()
            }
        if isinstance(tree, (list, tuple)) and not hasattr(tree, "shape"):
            out = [walk(v, f"{path}/{i}", in_blocks) for i, v in enumerate(tree)]
            return type(tree)(out) if not hasattr(tree, "_fields") else type(tree)(*out)
        shape = tuple(tree.shape)
        return param_spec(path, shape, mesh, stacked=in_blocks)

    return walk(params, "", False)


def cache_specs(cache: Any, cfg: ModelConfig, mesh: Mesh, batch: int) -> Any:
    """KV/SSM cache specs: batch over dp axes, sequence over `model`.

    Caches are lists of per-segment stacks: leaves (seg_len, B, S, ...) or
    (seg_len, B, ...).
    """
    axes = dp_axes(mesh)
    total = int(np.prod([_axsize(mesh, a) for a in axes]))
    b_ax = axes if batch % total == 0 else None
    lead = 1

    def leaf_spec(a):
        shape = tuple(a.shape)
        entries: list[Any] = [None] * len(shape)
        if len(shape) <= lead:
            return P(*entries)
        entries[lead] = b_ax  # batch dim
        # sequence dim: caches (L,B,S,...) with S >= 1024 shard over model
        if len(shape) > lead + 1 and shape[lead + 1] >= 1024:
            entries[lead + 1] = _fits(shape[lead + 1], mesh, "model")
        elif len(shape) > lead + 1:
            # ssm states: (B, nh, hd, ds) — shard heads over model
            entries[lead + 1] = _fits(shape[lead + 1], mesh, "model")
        return P(*entries)

    return jax.tree.map(leaf_spec, cache)


def to_shardings(specs: Any, mesh: Mesh) -> Any:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def constrain_batch_dim(x: jax.Array, extra: tuple = ()) -> jax.Array:
    """with_sharding_constraint(x, P(dp_axes, None, ...)) under the ambient
    mesh (steps.py traces inside `jax.sharding.use_mesh`). No-op without a
    mesh or when the batch dim doesn't divide — keeps model code mesh-free.

    Pinning activations' batch dim to the data axes stops GSPMD from
    replicating layer inputs across the mesh (measured: smollm train went
    from fully-replicated compute to properly sharded once constrained).
    """
    m = jax.sharding.get_abstract_mesh()
    if not m.axis_names:
        return x
    axes = tuple(a for a in ("pod", "data") if a in m.axis_names)
    if not axes:
        return x
    total = int(np.prod([m.shape[a] for a in axes]))
    if x.ndim == 0 or x.shape[0] % total != 0:
        return x
    rest = list(extra) + [None] * (x.ndim - 1 - len(extra))
    return jax.lax.with_sharding_constraint(x, P(axes, *rest))
