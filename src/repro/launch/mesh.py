"""Production meshes.

Single pod: 16×16 = 256 chips, axes (data, model).
Multi-pod: 2×16×16 = 512 chips, axes (pod, data, model) — `pod` crosses DCN
and carries only the data-parallel gradient all-reduce (see
models/sharding.py). Defined as a function so importing this module never
touches jax device state (the dry-run must set XLA_FLAGS first).

Every mesh is built with Auto axis types: the repo shards through explicit
in/out shardings, not sharding-in-types (``jax.make_mesh`` defaults to
Explicit axes).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(shape: tuple[int, ...] = None, axes: tuple[str, ...] = None):
    """Small mesh over whatever devices exist (tests / examples)."""
    if shape is None:
        shape, axes = (len(jax.devices()),), ("data",)
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_serve_mesh(devices: int = None) -> jax.sharding.Mesh:
    """1-D data mesh for the serving engine's SPMD fan-out
    (`partition.fanout.SpmdFanout`): partitions shard across the single
    ``data`` axis, one stacked-graph search per device slice. Defaults to
    every visible device (1 on a plain CPU host; set
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` to emulate a
    pod)."""
    n = devices or len(jax.devices())
    return jax.make_mesh((n,), ("data",), axis_types=(AxisType.Auto,))
