"""The deployment a configuration file describes, built on the program's
public service, and the write stream a traffic mix offers it.

Built as the bring-up smoke builds it: one ``VectorCollectionService``
with the file's graph widths, the service's default partition capacity
(100,000 + 1,024 slots, so every O(capacity) array and loop runs at
deployment size), 4 replicas, serial dispatch and a tenant budget far
above the offered load (a 429 would be a budget artefact, not a device
result). Everything else is the engine's defaults.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from .data import Corpus


def build_service(config: dict):
    from repro.core import GraphConfig
    from repro.serve import EngineConfig, VectorCollectionService

    g, e = config["graph"], config["engine"]
    graph = GraphConfig(
        capacity=config["max_vectors_per_partition"] + config["capacity_slack"],
        R=g["R"], slack=g["slack"], L_build=g["L_build"],
        L_search=g["L_search"], alpha=g["alpha"], M=g["M"],
        metric=config["metric"], batch_size=g["insert_batch"],
        bootstrap_sample=g["bootstrap_sample"],
        refine_sample=g["refine_sample"], beam_width=g["beam_width"],
    )
    if graph.R_slack != g["R_slack"]:
        raise ValueError(f"R_slack {graph.R_slack} != config {g['R_slack']}")
    return VectorCollectionService(
        dim=config["dim"], graph=graph,
        max_vectors_per_partition=config["max_vectors_per_partition"],
        initial_partitions=config["partitions"], replicas=config["replicas"],
        engine_cfg=EngineConfig(
            max_batch=e["max_batch"], ingest_chunk=e["ingest_chunk"],
            beam_width=g["beam_width"], dispatch_mode=e["dispatch_mode"],
            policy=e["policy"], tenant_ru_s=e["tenant_ru_s"]),
    )


def documents(ids: np.ndarray, categories: int) -> list[dict]:
    """One JSON document per id; with ``categories`` each carries one
    filterable property, as Cosmos documents carry theirs."""
    if not categories:
        return [{"id": int(i)} for i in ids]
    return [{"id": int(i), "category": int(i) % categories} for i in ids]


@dataclasses.dataclass
class WriteStream:
    """Churn offered as a backlog of write requests in the order of
    ``pattern``, a cycle of (kind, docs) such as one upsert of 8 docs then
    8 deletes of 1 doc each. Upserts write the next unwritten corpus
    positions; deletes remove the oldest live ones. The engine applies
    queued requests in order, so the requests applied are the first
    ``submitted - backlog`` ops, and the live docs are always the positions
    ``[deleted, written)``."""
    svc: object
    corpus: Corpus
    pattern: tuple[tuple[str, int], ...]
    categories: int
    written: int = 0  # positions submitted for upsert
    deleted: int = 0  # positions submitted for delete
    ops: list = dataclasses.field(default_factory=list)  # (kind, lo, hi)
    _step: int = 0
    _queued: int = 0  # ops submitted
    _done: int = 0  # requests applied
    _done_ops: int = 0
    _ups: int = 0
    _dels: int = 0

    def __post_init__(self):
        self.written = self.corpus.n_load
        chunk = self.svc.engine.cfg.ingest_chunk
        if any(n > chunk for _, n in self.pattern):
            raise ValueError(f"a write request over ingest_chunk={chunk} "
                             "docs would be split by the engine")

    def submit_next(self) -> bool:
        """Queue the next request of the pattern; False once the corpus has
        no unwritten docs left for an upsert."""
        kind, n = self.pattern[self._step % len(self.pattern)]
        if kind == "upsert":
            if self.written + n > len(self.corpus.ids):
                return False
            lo, hi = self.written, self.written + n
            self.svc.upsert_async(
                documents(self.corpus.ids[lo:hi], self.categories),
                self.corpus.vectors[lo:hi])
            self.written = hi
        elif kind == "delete":
            lo, hi = self.deleted, self.deleted + n
            self.svc.delete_async(self.corpus.ids[lo:hi].tolist())
            self.deleted = hi
        else:
            raise ValueError(f"unknown write kind {kind!r}")
        self.ops.append((kind, lo, hi))
        self._queued += hi - lo
        self._step += 1
        return True

    def applied(self) -> tuple[int, int, int]:
        """(upsert ops applied, delete ops applied, requests applied)."""
        applied_ops = self._queued - self.svc.engine.ingest_backlog
        while (self._done < len(self.ops)
               and self._done_ops + self._size(self._done) <= applied_ops):
            kind, lo, hi = self.ops[self._done]
            self._done_ops += hi - lo
            if kind == "upsert":
                self._ups += hi - lo
            else:
                self._dels += hi - lo
            self._done += 1
        return self._ups, self._dels, self._done

    def _size(self, i: int) -> int:
        return self.ops[i][2] - self.ops[i][1]

    def live_range(self) -> tuple[int, int]:
        """Positions ``[lo, hi)`` live now."""
        ups, dels, _ = self.applied()
        return dels, self.corpus.n_load + ups
