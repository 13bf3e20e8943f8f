"""jit'd dispatch wrapper for flat_l2."""
from __future__ import annotations

import jax

from .. import interpret_default
from .kernel import flat_l2_pallas
from .ref import flat_l2_ref


def flat_l2(q: jax.Array, x: jax.Array, *, metric: str = "l2",
            use_pallas: bool | None = None, **blocks) -> jax.Array:
    if use_pallas is None:
        use_pallas = True
    interpret = interpret_default()
    if not use_pallas:
        return flat_l2_ref(q, x, metric=metric)
    return flat_l2_pallas(q, x, metric=metric, interpret=interpret, **blocks)
