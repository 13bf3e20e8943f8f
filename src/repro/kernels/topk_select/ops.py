"""jit'd dispatch wrapper for topk_select."""
from __future__ import annotations

import jax

from .. import interpret_default
from .kernel import topk_select_pallas
from .ref import topk_select_ref


def topk_select(dists: jax.Array, *, L: int, block_n: int = 1024,
                use_pallas: bool | None = None) -> tuple[jax.Array, jax.Array]:
    if use_pallas is None:
        use_pallas = True
    interpret = interpret_default()
    if not use_pallas:
        return topk_select_ref(dists, L=L)
    return topk_select_pallas(dists, L=L, block_n=block_n, interpret=interpret)
