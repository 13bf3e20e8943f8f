"""jit'd dispatch wrapper for pq_encode."""
from __future__ import annotations

import jax

from .. import interpret_default
from .kernel import pq_encode_pallas
from .ref import pq_encode_ref


def pq_encode(x: jax.Array, codebooks: jax.Array, *, block_n: int = 256,
              use_pallas: bool | None = None) -> jax.Array:
    if use_pallas is None:
        use_pallas = True
    interpret = interpret_default()
    if not use_pallas:
        return pq_encode_ref(x, codebooks)
    return pq_encode_pallas(x, codebooks, block_n=block_n, interpret=interpret)
