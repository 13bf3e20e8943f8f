"""Compile the served path's kernels and jitted programs for a TPU v5e.

Nothing runs: each program is lowered from shapes at the paper's §4
widths (``configs/cosmosann.py:config()``: 768-D, PQ M=96 × K=256,
R_slack=41, L=100) and a one-default-partition capacity, then compiled by
the TPU compiler for one chip of a described ``v5e:2x2`` topology. That
refuses what interpret mode accepts: Pallas blocks off the (8, 128) tiling,
Mosaic layouts XLA does not share, and programs whose temporaries exceed
the chip's 16 GB of HBM.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import cosmosann
from repro.core import delete as dmod
from repro.core import flat as fmod
from repro.core import insert as imod
from repro.core import search as smod
from repro.kernels.flat_l2.kernel import flat_l2_pallas
from repro.kernels.pq_adc.kernel import pq_adc_pallas
from repro.kernels.pq_encode.kernel import pq_encode_pallas
from repro.kernels.topk_select.kernel import topk_select_pallas

CFG = cosmosann.config()
N = 100_000 + 1024  # one default partition's capacity
DSUB = CFG.dim // CFG.M


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topology = topologies.get_topology_desc(platform="tpu",
                                                    topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield topology


@pytest.fixture(scope="module")
def shape(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype,
                                                    sharding=one_chip)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _graph(shape):
    return (shape((N, CFG.R_slack), jnp.int32), shape((N, CFG.M), jnp.uint8),
            shape((N,), jnp.uint8), shape((N,), jnp.bool_))


@pytest.mark.parametrize("kernel", ["pq_adc", "topk_select", "flat_l2",
                                    "pq_encode"])
def test_kernel_compiles_for_v5e(shape, kernel):
    B, C = 16, 16_384
    f32 = jnp.float32
    fn, args = {
        "pq_adc": (pq_adc_pallas, (shape((B, CFG.M, CFG.K), f32),
                                   shape((C, CFG.M), jnp.uint8))),
        "topk_select": (functools.partial(topk_select_pallas,
                                          L=CFG.L_search),
                        (shape((B, N), f32),)),
        "flat_l2": (flat_l2_pallas, (shape((B, CFG.dim), f32),
                                     shape((C, CFG.dim), f32))),
        "pq_encode": (pq_encode_pallas, (shape((C, CFG.dim), f32),
                                         shape((CFG.M, CFG.K, DSUB), f32))),
    }[kernel]
    compiled = _compile(fn, *args)
    assert "tpu_custom_call" in compiled.as_text()


def test_batched_search_entry_compiles_for_v5e(shape):
    B = 128
    smod._batched_search_entry.lower(
        *_graph(shape), shape((B, 1, CFG.M, CFG.K), jnp.float32),
        shape((), jnp.int32), shape((B, 1), jnp.uint32),
        shape((), jnp.float32), L=CFG.L_search, max_hops=0, visited_cap=0,
        has_filter=False, beam_width=CFG.beam_width,
    ).compile()


def test_insert_batch_jit_compiles_for_v5e(shape):
    B = 64  # the engine's ingest chunk
    fn = functools.partial(imod.insert_batch_jit, L_build=CFG.L_search,
                           R=CFG.R, R_slack=CFG.R_slack, alpha=1.2)
    _compile(fn, *_graph(shape),
             shape((2, CFG.M, CFG.K, DSUB), jnp.float32),
             shape((B, CFG.dim), jnp.float32), shape((B,), jnp.int32),
             shape((), jnp.int32))


@pytest.mark.parametrize("program", ["insert_candidates", "prune_batch",
                                     "prune_nodes"])
def test_service_insert_path_compiles_for_v5e(shape, program):
    """The host-orchestrated insert that ``VectorCollectionService.upsert``
    runs, with two coexisting PQ schemas (after re-quantization)."""
    B, P = 64, 512  # the engine's ingest chunk; the top overflow bucket
    C = 3 * CFG.L_search + 16  # visited log (2L+16) + beam (L)
    cap = CFG.R_slack + 100  # overflowing row + one mini-batch of edges
    f32, i32 = jnp.float32, jnp.int32
    codebooks = shape((2, CFG.M, CFG.K, DSUB), f32)
    nb, codes, versions, live = _graph(shape)
    fn, args = {
        "insert_candidates": (
            functools.partial(imod.insert_candidates, L_build=CFG.L_search),
            (nb, codes, versions, live, codebooks, shape((B, CFG.dim), f32),
             shape((), i32))),
        "prune_batch": (
            functools.partial(imod.prune_batch, R=CFG.R, alpha=1.2),
            (codes, versions, codebooks, shape((B, CFG.dim), f32),
             shape((B, C), i32))),
        "prune_nodes": (
            functools.partial(imod.prune_nodes, R=CFG.R, alpha=1.2),
            (codes, versions, codebooks, shape((P,), i32),
             shape((P, cap), i32))),
    }[program]
    _compile(fn, *args)


def test_inplace_delete_compiles_for_v5e(shape):
    """The batched repair over the whole two-hop hood (R_slack + R_slack²
    rows, each gathering its merged candidates' 768-D coordinates) fits
    the chip."""
    nb, _, _, live = _graph(shape)
    dmod.inplace_delete.lower(
        nb, live, shape((N, CFG.dim), jnp.float32), shape((), jnp.int32),
        R=CFG.R, R_slack=CFG.R_slack, alpha=1.2, c_replace=3, metric="l2",
    ).compile()


def test_rerank_compiles_for_v5e(shape):
    B = 128
    _compile(functools.partial(fmod.rerank, k=CFG.k),
             shape((B, CFG.dim), jnp.float32),
             shape((B, CFG.L_search), jnp.int32),
             shape((N, CFG.dim), jnp.float32))


def test_qflat_scan_compiles_for_v5e(shape):
    B = 16  # the engine's max micro-batch
    _compile(functools.partial(fmod.qflat_scan, kprime=5 * CFG.k),
             shape((B, 2, CFG.M, CFG.K), jnp.float32),
             *_graph(shape)[1:])
