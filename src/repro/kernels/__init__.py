"""Pallas TPU kernels for the paper's compute hot spots.

The paper's profile (Fig 11) is dominated by quantized-vector access and
distance computation; the query path touches ~3500 quantized vectors and
~50 full-precision vectors per search (§3.2). The kernels here tile exactly
those loops for the TPU memory hierarchy:

    pq_adc       ADC distance scan: LUT in VMEM, PQ codes streamed in tiles,
                 table lookups expressed as one-hot × LUT contractions (MXU)
    pq_encode    PQ encoding: per-subspace nearest-centroid (MXU matmuls)
    topk_select  blockwise partial top-k for candidate selection
    flat_l2      tiled full-precision distance matrix (re-rank / brute force)

Each subpackage: kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd
wrapper), ref.py (pure-jnp oracle). On a TPU the kernels compile through
Mosaic; on any other backend they run under ``interpret=True`` and are
validated against the oracles across shape/dtype sweeps in
tests/test_kernels.py. tests/test_tpu_compile.py compiles them for a
described v5e at the paper's widths.
"""
import jax


def interpret_default() -> bool:
    """Pallas kernels compile only for TPU; elsewhere they are interpreted.
    (Defined before the subpackage imports below, which use it.)"""
    return jax.default_backend() != "tpu"


from .pq_adc import ops as pq_adc_ops  # noqa: E402
from .pq_encode import ops as pq_encode_ops  # noqa: E402
from .topk_select import ops as topk_ops  # noqa: E402
from .flat_l2 import ops as flat_l2_ops  # noqa: E402

__all__ = ["interpret_default", "pq_adc_ops", "pq_encode_ops", "topk_ops",
           "flat_l2_ops"]
