"""The paper's own workload: a partitioned DiskANN collection at Cosmos
scale, as a distributed-search dry-run config.

10M Wiki-Cohere-like vectors (768D float32 documents, 96-byte PQ codes,
R=32 graph) sharded one-DiskANN-index-per-device across the production
mesh; the query step is `repro.partition.fanout.distributed_search_fn`
(local beam search + all-gather merge). This is the §4 workload the paper
evaluates, expressed on TPU.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class VectorWorkloadConfig:
    name: str = "cosmosann-10m"
    total_vectors: int = 10_000_000
    dim: int = 768
    M: int = 96  # PQ subspaces (96-byte codes, §2.1's OpenAI example rate)
    K: int = 256
    R: int = 32  # graph degree bound; R_slack = R × slack 1.3 = 41
    L_search: int = 100
    k: int = 10
    query_batch: int = 128
    metric: str = "l2"
    beam_width: int = 4  # W-way hop batching on the search loop (§3.2)
    # serving control plane (repro.serve.policy): "static" pins every
    # knob; "adaptive" closes the loop — beam width / ingest yield /
    # topology actuate per pump tick from the observability rollups
    policy: str = "static"
    # the adaptive W ladder; warmup compiles every (bucket, L, W) in it
    # once so policy moves never recompile in steady state
    policy_widths: tuple[int, ...] = (1, 2, 4)

    @property
    def R_slack(self) -> int:
        return int(self.R * 1.3)


def config() -> VectorWorkloadConfig:
    return VectorWorkloadConfig()


def smoke() -> VectorWorkloadConfig:
    return VectorWorkloadConfig(
        name="cosmosann-smoke", total_vectors=2000, dim=32, M=8, R=10,
        L_search=20, k=5, query_batch=4,
    )


def shard_specs(cfg: VectorWorkloadConfig, num_shards: int) -> dict:
    """ShapeDtypeStructs for the shard-stacked index arrays + queries."""
    n = cfg.total_vectors // num_shards
    S = num_shards
    return dict(
        neighbors=jax.ShapeDtypeStruct((S, n, cfg.R_slack), jnp.int32),
        codes=jax.ShapeDtypeStruct((S, n, cfg.M), jnp.uint8),
        versions=jax.ShapeDtypeStruct((S, n), jnp.uint8),
        live=jax.ShapeDtypeStruct((S, n), jnp.bool_),
        vectors=jax.ShapeDtypeStruct((S, n, cfg.dim), jnp.float32),
        doc_ids=jax.ShapeDtypeStruct((S, n), jnp.int64),
        medoid=jax.ShapeDtypeStruct((S,), jnp.int32),
        codebooks=jax.ShapeDtypeStruct((S, cfg.M, cfg.K, cfg.dim // cfg.M), jnp.float32),
        queries=jax.ShapeDtypeStruct((cfg.query_batch, cfg.dim), jnp.float32),
    )
