"""jit'd dispatch wrapper for pq_adc: Pallas on TPU, interpret elsewhere."""
from __future__ import annotations

import jax

from .. import interpret_default
from .kernel import pq_adc_pallas
from .ref import pq_adc_ref


def pq_adc(lut: jax.Array, codes: jax.Array, *, block_c: int = 512,
           use_pallas: bool | None = None) -> jax.Array:
    """ADC distances (B, C). `use_pallas=None` → Pallas compiled on TPU,
    Pallas interpret mode elsewhere."""
    if use_pallas is None:
        use_pallas = True
    interpret = interpret_default()
    if not use_pallas:
        return pq_adc_ref(lut, codes)
    return pq_adc_pallas(lut, codes, block_c=block_c, interpret=interpret)
