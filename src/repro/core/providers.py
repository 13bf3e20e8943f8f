"""Provider traits — the paper's stateless-DiskANN interface (§3.1).

The 2025 rewrite's core idea: the index layout is not visible to the
algorithms. The library reads/writes *index terms* — quantized vectors,
full-precision vectors, neighbor lists — through Provider implementations
owned by the database, addressed by an execution ``Context`` that selects
the target replica/collection (one DiskANN instance serves many indices).

Here the jitted algorithms consume dense arrays (the Bw-Tree page cache's
role), and Providers define where those arrays come from and where updates
are persisted:

  * ``ArrayProviderSet`` — memory-backed terms ("the new library is at least
    as fast as the previous monolithic DiskANN" — §3.1): numpy canonical
    state + a cached jnp materialization for the query path.
  * ``StoreProviderSet`` (repro.store.provider) — terms encoded in the
    Bw-Tree analogue, with RU metering; write-through into the array cache.

The async MaybeDone future of the Rust rewrite has no TPU analogue (device
steps are synchronous); its *purpose* — overlapping slow term fetches —
reappears as batched gathers, and the latency asymmetry it hides is captured
by the RU/latency model in ``repro.store.ru``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Protocol

import jax.numpy as jnp
import numpy as np

from .spans import span


@dataclasses.dataclass(frozen=True)
class Context:
    """Execution context (§3.1): identifies the logical index a call targets
    and carries telemetry identity. The database (not the library) interprets
    it; our store uses it to select term-key prefixes and meter RUs."""

    collection: str = "default"
    replica: int = 0
    shard_key: Optional[int] = None  # sharded-DiskANN logical index (§3.3)
    activity_id: str = ""
    lsn: int = 0


class ProviderSet(Protocol):
    """The union of the paper's Neighbor/QuantVector/FullVector providers."""

    def get_neighbors(self, ctx: Context, ids: np.ndarray) -> np.ndarray: ...
    def set_neighbors(self, ctx: Context, ids: np.ndarray, rows: np.ndarray) -> None: ...
    def append_neighbors(self, ctx: Context, node: int, new_ids: np.ndarray) -> None: ...
    def get_quant(self, ctx: Context, ids: np.ndarray) -> tuple[np.ndarray, np.ndarray]: ...
    def set_quant(self, ctx: Context, ids: np.ndarray, codes: np.ndarray, versions: np.ndarray) -> None: ...
    def get_full(self, ctx: Context, ids: np.ndarray) -> np.ndarray: ...
    def set_full(self, ctx: Context, ids: np.ndarray, vecs: np.ndarray) -> None: ...
    def set_live(self, ctx: Context, ids: np.ndarray, value: bool) -> None: ...
    def materialize(self, ctx: Context): ...
    def materialize_graph(self, ctx: Context): ...
    def barrier(self, name: str) -> None: ...


class ArrayProviderSet:
    """Memory-backed providers: numpy canonical state, jnp cache for jit."""

    def __init__(self, capacity: int, R_slack: int, M: int, dim: int):
        # deferred import: store.provider subclasses this module, so a
        # top-level import of store.pages would be circular
        from repro.store.pages import PagedVectorStore

        self.neighbors = np.full((capacity, R_slack), -1, np.int32)
        self.codes = np.zeros((capacity, M), np.uint8)
        self.versions = np.zeros((capacity,), np.uint8)
        self.live = np.zeros((capacity,), bool)
        self.vectors = np.zeros((capacity, dim), np.float32)
        # tiered residency ledger for the full-precision tier (ISSUE 10):
        # budget=None → fully resident → bit-identical pre-tier behaviour
        self.pages = PagedVectorStore(capacity, dim)
        self._cache: dict = {}  # field name -> jnp materialization
        self.write_count = 0
        # host-to-device copies made by _device: arrays and bytes
        self.uploads = 0
        self.upload_bytes = 0

    def barrier(self, name: str) -> None:
        """Named crash-barrier hook; no-op without an attached FaultPlan
        (StoreProviderSet overrides with the armed version)."""

    # -- invalidation ------------------------------------------------------
    FIELDS = ("neighbors", "codes", "versions", "live", "vectors")

    def _dirty(self, *fields: str):
        """Drop the device copies of ``fields`` (all when none are named)."""
        for name in fields or self.FIELDS:
            self._cache.pop(name, None)
        self.write_count += 1

    def _device(self, name: str):
        arr = self._cache.get(name)
        if arr is None:
            host = getattr(self, name)
            with span("store.upload", field=name, bytes=host.nbytes):
                arr = self._cache[name] = jnp.asarray(host)
            self.uploads += 1
            self.upload_bytes += host.nbytes
        return arr

    def materialize(self, ctx: Context = Context()):
        """jnp views of (neighbors, codes, versions, live, vectors) for the
        jitted query/update kernels; each re-uploaded only after a write
        to it."""
        return tuple(self._device(name) for name in self.FIELDS)

    def materialize_graph(self, ctx: Context = Context()):
        """(neighbors, codes, versions, live) without the full-precision
        vectors: the graph-update path never reads them, and re-uploading
        the whole vector table after every write batch is most of a load's
        host-to-device traffic."""
        return tuple(self._device(name) for name in self.FIELDS[:4])

    # -- neighbor terms ------------------------------------------------------
    def get_neighbors(self, ctx: Context, ids):
        return self.neighbors[np.asarray(ids)]

    def set_neighbors(self, ctx: Context, ids, rows):
        self.neighbors[np.asarray(ids)] = rows
        self._dirty("neighbors")

    def append_neighbors(self, ctx: Context, node: int, new_ids):
        """Blind incremental append (the Bw-Tree forward-term fast path)."""
        row = self.neighbors[node]
        deg = int((row >= 0).sum())
        n = min(len(new_ids), row.shape[0] - deg)
        row[deg : deg + n] = new_ids[:n]
        self._dirty("neighbors")
        return n  # how many fit; caller prunes on overflow

    # -- quantized terms ---------------------------------------------------
    def get_quant(self, ctx: Context, ids):
        ids = np.asarray(ids)
        return self.codes[ids], self.versions[ids]

    def set_quant(self, ctx: Context, ids, codes, versions):
        ids = np.asarray(ids)
        self.codes[ids] = codes
        self.versions[ids] = versions
        self._dirty("codes", "versions")

    # -- full-precision vectors (document store role) ----------------------
    def get_full(self, ctx: Context, ids):
        return self.vectors[np.asarray(ids)]

    def set_full(self, ctx: Context, ids, vecs):
        self.vectors[np.asarray(ids)] = vecs
        self._dirty("vectors")

    def set_live(self, ctx: Context, ids, value: bool):
        self.live[np.asarray(ids)] = value
        self._dirty("live")
