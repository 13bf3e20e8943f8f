"""DiskANNIndex — the host-side orchestrator tying the pieces together.

Mirrors the paper's control flow for one replica:

  * documents arrive → full vector to the document store, quantized term
    generated inline (once a schema exists), graph updates applied in
    mini-batches *outside* the transactional path (§3.4);
  * first PQ schema trained after ``bootstrap_sample`` docs; re-quantization
    at ``refine_sample`` docs re-encodes terms in place, old/new schemas
    coexisting via versioned codes (§3.4);
  * queries run in quantized space over the graph, then re-rank
    ``quantizedVectorListMultiplier × k`` candidates with full-precision
    vectors from the document store (§3.5, Fig 5);
  * the query planner routes by selectivity: brute force for tiny
    collections, Q-Flat below ~5000 predicate matches, graph search with
    post-filtering or filter-aware β-search otherwise (§3.5);
  * deletes are in-place (Alg 6) with a background consolidation sweep.

All distance-heavy work is jitted; this class only sequences it and applies
term writes through the Provider interface — the same split as
IndexManager / DiskANN-library / Bw-Tree in Fig 15.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import delete as dmod
from . import flat as fmod
from . import graph as g
from . import insert as imod
from . import paginate as pgmod
from . import pq as pqmod
from . import search as smod
from .providers import ArrayProviderSet, Context, ProviderSet
from .spans import span


# backup-queue capacity for paginated search: one value service-wide, so
# every continuation token carries a single known shape (the serving layer
# validates client tokens against it — an arbitrary width would mint a
# fresh jit signature per forged token)
PAGE_BACKUP_CAP = 512


@dataclasses.dataclass
class QueryStats:
    hops: float = 0.0  # sequential expansion rounds (latency-critical path)
    cmps: float = 0.0  # quantized distance comparisons (≈3500 @ L=100 in paper)
    full_reads: float = 0.0  # full-precision vectors touched (≈50 in paper)
    expansions: float = 0.0  # adjacency rows fetched (= hops·W̄; RU-relevant)
    # paged vector tier (ISSUE 10): rerank-stage page touches, per-query
    # means (= batch page totals / B, the same convention as cmps/hops);
    # a miss costs RU + modelled latency via store/ru.py, a hit is free
    tier_hits: float = 0.0
    tier_misses: float = 0.0
    plan: str = "graph"


class DiskANNIndex:
    def __init__(
        self,
        cfg: g.GraphConfig,
        dim: int,
        providers: Optional[ProviderSet] = None,
        seed: int = 0,
        context: Context = Context(),
    ):
        assert dim % cfg.M == 0, f"dim {dim} must divide into M={cfg.M} subspaces"
        self.cfg = cfg
        self.dim = dim
        self.ctx = context
        self.pv: ProviderSet = providers or ArrayProviderSet(
            cfg.capacity, cfg.R_slack, cfg.M, dim
        )
        self.key = jax.random.PRNGKey(seed)
        self.schemas: list[pqmod.PQSchema] = []  # ≤2 coexisting (§3.4)
        self.count = 0  # slot high-watermark
        self.medoid = 0
        self.doc_to_slot: dict[int, int] = {}
        self.slot_to_doc = np.full((cfg.capacity,), -1, np.int64)
        self._graph_built = False
        self._pending: list[int] = []  # slots awaiting first graph build
        self._requant_cursor = 0  # background re-encode progress
        self._consolidate_cursor = 0
        # tier touches of the most recent next_page() call (pagination has
        # no QueryStats of its own; the partition layer folds these into
        # the page_stats delta)
        self.last_page_tier: tuple[float, float] = (0.0, 0.0)

    # ------------------------------------------------------------------
    # helpers
    # ------------------------------------------------------------------
    @property
    def num_live(self) -> int:
        return int(self.pv.live.sum())

    def _codebook_stack(self) -> jax.Array:
        return jnp.stack([s.codebooks for s in self.schemas], axis=0)

    def _luts(self, queries: np.ndarray) -> jax.Array:
        schemas = tuple(self.schemas)
        q = jnp.asarray(queries, jnp.float32)
        return jax.vmap(lambda qq: pqmod.multi_lut(schemas, qq, self.cfg.metric))(q)

    def _next_key(self) -> jax.Array:
        self.key, sub = jax.random.split(self.key)
        return sub

    # -- paged vector tier (ISSUE 10) ----------------------------------
    def _touch_tier(self, slots, stats: QueryStats, B: int,
                    admit: bool = True, pin: bool = False):
        """Record a rerank-stage access to the paged full-precision tier.

        Folds page-level hit/miss counts into ``stats`` as per-query
        means (batch totals / B). With ``pin=True`` the touched pages
        stay pinned (never evicted mid-rerank) until the returned handle
        is passed to :meth:`_unpin_tier`. ``admit=False`` marks a full
        scan (brute/exact): billed, never cached."""
        pages = getattr(self.pv, "pages", None)
        if pages is None:
            return None
        hits, misses, touched = pages.touch(slots, admit=admit, pin=pin)
        stats.tier_hits += hits / max(B, 1)
        stats.tier_misses += misses / max(B, 1)
        return touched if pin else None

    def _unpin_tier(self, handle) -> None:
        if handle is not None:
            self.pv.pages.unpin(handle)

    # ------------------------------------------------------------------
    # ingestion
    # ------------------------------------------------------------------
    def insert(self, doc_ids: Sequence[int], vectors: np.ndarray) -> QueryStats:
        """Insert documents. Returns aggregate ingest stats."""
        vectors = np.asarray(vectors, np.float32)
        assert vectors.shape[1] == self.dim
        stats = QueryStats(plan="insert")
        for start in range(0, len(doc_ids), self.cfg.batch_size):
            ids = list(doc_ids[start : start + self.cfg.batch_size])
            vecs = vectors[start : start + self.cfg.batch_size]
            with span("write.insert", docs=len(ids)):
                self._insert_batch(ids, vecs, stats)
        return stats

    def _alloc(self, n: int) -> np.ndarray:
        if self.count + n > self.cfg.capacity:
            raise RuntimeError(
                f"partition full ({self.count}+{n} > {self.cfg.capacity}); "
                "split required (repro.partition handles this)"
            )
        slots = np.arange(self.count, self.count + n, dtype=np.int64)
        self.count += n
        return slots

    def _insert_batch(self, ids: list[int], vecs: np.ndarray, stats: QueryStats):
        replace_mask = np.array([d in self.doc_to_slot for d in ids])
        if replace_mask.any():
            # Replace = overwrite vector + re-insert (§2.1 "Inserts and
            # Replaces"); old edges cleaned lazily by later prunes.
            keep = ~replace_mask
            for d, v in zip(np.asarray(ids)[replace_mask], vecs[replace_mask]):
                self._replace_one(int(d), v)
            ids = list(np.asarray(ids)[keep])
            vecs = vecs[keep]
            if len(ids) == 0:
                return

        slots = self._alloc(len(ids))
        for d, s in zip(ids, slots):
            self.doc_to_slot[int(d)] = int(s)
            self.slot_to_doc[s] = int(d)
        self.pv.set_full(self.ctx, slots, vecs)
        # crash point right after the full-vector (paged tier) write: a
        # WAL that loses set_full replay would resurface stale vectors
        # at rerank — recovery_invariants bit-compares the tier
        self.pv.barrier("upsert:post_full")

        if not self.schemas:
            self._pending.extend(int(s) for s in slots)
            self.pv.set_live(self.ctx, slots, True)
            if self.count >= min(self.cfg.bootstrap_sample, self.cfg.capacity):
                self._bootstrap_schema()
            return

        # quantized term inline with the document write (§3.4)
        with span("write.encode"):
            codes = np.asarray(pqmod.encode(self.schemas[-1],
                                            jnp.asarray(vecs)))
        ver = np.full((len(slots),), len(self.schemas) - 1, np.uint8)
        self.pv.set_quant(self.ctx, slots, codes, ver)
        self.pv.set_live(self.ctx, slots, True)

        if self._graph_built:
            self._graph_insert(slots, vecs, stats)
        else:
            self._pending.extend(int(s) for s in slots)

        if (
            len(self.schemas) == 1
            and self.count >= min(self.cfg.refine_sample, self.cfg.capacity)
        ):
            self.requantize()

    def _bootstrap_schema(self):
        """Train the first PQ schema from the earliest docs (§3.4), backfill
        quantized terms, then build the graph over the backlog."""
        sample = self.pv.vectors[: min(self.count, self.cfg.bootstrap_sample)]
        self.schemas = [
            pqmod.train_pq(self._next_key(), jnp.asarray(sample), self.cfg.M)
        ]
        backlog = np.asarray(self._pending, np.int64)
        codes = np.asarray(
            pqmod.encode(self.schemas[0], jnp.asarray(self.pv.vectors[backlog]))
        )
        self.pv.set_quant(self.ctx, backlog, codes, np.zeros(len(backlog), np.uint8))
        self._pending = []
        self._build_initial_graph(backlog)

    def _build_initial_graph(self, slots: np.ndarray):
        self.medoid = int(
            g.compute_medoid(jnp.asarray(self.pv.vectors), jnp.asarray(self.pv.live))
        )
        self._graph_built = True
        order = np.random.RandomState(0).permutation(slots)
        st = QueryStats()
        # Ramp-up: batch-inserting into a near-empty graph funnels every new
        # node's single candidate (the medoid) into one overflowing adjacency
        # list — the losers end up with zero in-degree, permanently
        # unreachable. Grow batches 4 → 8 → … so early nodes wire densely.
        i, bs = 0, 4
        while i < len(order):
            batch = order[i : i + bs]
            i += bs
            bs = min(bs * 2, self.cfg.batch_size)
            batch = batch[batch != self.medoid]
            if len(batch) == 0:
                continue
            self._graph_insert(batch, self.pv.vectors[batch], st)
        self.repair_orphans()

    def repair_orphans(self) -> int:
        """Re-insert live nodes with zero in-degree (background maintenance;
        guarantees every vector is reachable from the medoid's side)."""
        nb = self.pv.neighbors[: self.count]
        indeg = np.bincount(nb[nb >= 0], minlength=self.cfg.capacity)
        live = self.pv.live
        orphans = np.nonzero((indeg[: self.count] == 0) & live[: self.count])[0]
        orphans = orphans[orphans != self.medoid]
        if len(orphans) == 0:
            return 0
        st = QueryStats()
        for i in range(0, len(orphans), self.cfg.batch_size):
            batch = orphans[i : i + self.cfg.batch_size]
            self._graph_insert(batch, self.pv.vectors[batch], st)
        return len(orphans)

    def _graph_insert(self, slots: np.ndarray, vecs: np.ndarray, stats: QueryStats):
        """Mini-batch graph update (Alg 5): jitted search+prune
        (``write.graph_insert``), then one consolidated reverse-edge append
        per touched node and the re-prune of rows that overflow
        (``write.prune``)."""
        cfg = self.cfg
        n = len(slots)
        with span("write.graph_insert"):
            neighbors, codes, versions, live = self.pv.materialize_graph(
                self.ctx)
            # padded to a batch bucket (padding lanes repeat row 0 and are
            # dropped): the ramp-up build's odd batch sizes share compiles
            padded = jnp.asarray(smod.pad_batch_np(vecs, smod.next_bucket(n)))
            cand_ids, _cand_d, istats = imod.insert_candidates(
                neighbors, codes, versions, live, self._codebook_stack(),
                padded, jnp.int32(self.medoid),
                L_build=cfg.L_build, metric=cfg.metric,
            )
            nbrs = np.asarray(
                imod.prune_batch(
                    codes, versions, self._codebook_stack(), padded,
                    cand_ids, R=cfg.R, alpha=cfg.alpha, metric=cfg.metric,
                )
            )[:n]  # (B, R)
            stats.hops += float(np.asarray(istats.hops)[:n].sum())
            stats.cmps += float(np.asarray(istats.cmps)[:n].sum())
        with span("write.prune"):
            self._link_rows(slots, nbrs, codes, versions)

    def _link_rows(self, slots: np.ndarray, nbrs: np.ndarray,
                   codes: jax.Array, versions: jax.Array):
        """Write the new rows, append their reverse edges, and re-prune
        every row that overflows."""
        cfg = self.cfg
        rows = np.full((len(slots), cfg.R_slack), -1, np.int32)
        rows[:, : cfg.R] = nbrs
        self.pv.set_neighbors(self.ctx, slots, rows)

        # group reverse edges by target: ONE consolidated append per node —
        # the Bw-Tree "no duplicate patch for a key" contract (§2.1)
        rev: dict[int, list[int]] = {}
        for i, s in enumerate(slots):
            for b in nbrs[i]:
                if b >= 0 and b != s:
                    rev.setdefault(int(b), []).append(int(s))
        overflow: list[tuple[int, list[int]]] = []
        for b, ps in rev.items():
            row = self.pv.neighbors[b]
            existing = set(int(x) for x in row[row >= 0])
            ps = [p for p in dict.fromkeys(ps) if p not in existing]
            if not ps:
                continue
            fitted = self.pv.append_neighbors(self.ctx, b, np.asarray(ps, np.int32))
            if fitted < len(ps):
                row = self.pv.neighbors[b]
                overflow.append((b, list(dict.fromkeys(list(row[row >= 0]) + ps))))
        if overflow:
            self._prune_nodes(codes, versions, overflow)

    def _prune_nodes(self, codes: jax.Array, versions: jax.Array,
                     overflow: list):
        """Prune every overflowing row back to R in one device call; each
        node appears once per mini-batch, so their prunes are independent."""
        cfg = self.cfg
        cap = cfg.R_slack + cfg.batch_size
        P = smod.next_bucket(len(overflow), (8, 16, 32, 64, 128, 256, 512))
        nodes = np.zeros((P,), np.int32)
        ids = np.full((P, cap), -1, np.int64)
        for i, (b, cand) in enumerate(overflow):
            nodes[i] = b
            cand = cand[:cap]
            ids[i, : len(cand)] = cand
        ids = np.where(self.pv.live[np.maximum(ids, 0)] & (ids >= 0), ids, -1)
        pruned = np.asarray(
            imod.prune_nodes(
                codes, versions, self._codebook_stack(), jnp.asarray(nodes),
                jnp.asarray(ids.astype(np.int32)),
                R=cfg.R, alpha=cfg.alpha, metric=cfg.metric,
            )
        )[: len(overflow)]
        rows = np.full((len(overflow), cfg.R_slack), -1, np.int32)
        rows[:, : cfg.R] = pruned
        self.pv.set_neighbors(self.ctx, nodes[: len(overflow)], rows)

    def _decoded(self, ids: np.ndarray) -> jax.Array:
        """Quantized-space coordinates for pruning (§3.2)."""
        codes, versions = self.pv.get_quant(self.ctx, ids)
        return pqmod.decode_versioned(self._codebook_stack(),
                                      jnp.asarray(codes), jnp.asarray(versions))

    def _replace_one(self, doc_id: int, vec: np.ndarray):
        slot = self.doc_to_slot[doc_id]
        self.pv.set_full(self.ctx, np.asarray([slot]), vec[None, :])
        self.pv.barrier("upsert:post_full")
        if self.schemas:
            codes = np.asarray(pqmod.encode(self.schemas[-1], jnp.asarray(vec[None, :])))
            self.pv.set_quant(
                self.ctx, np.asarray([slot]), codes,
                np.asarray([len(self.schemas) - 1], np.uint8),
            )
        if self._graph_built:
            st = QueryStats()
            self._graph_insert(np.asarray([slot]), vec[None, :], st)

    # ------------------------------------------------------------------
    # re-quantization (§3.4)
    # ------------------------------------------------------------------
    def requantize(self):
        """Refine the PQ schema from a larger sample; terms re-encode in
        place (background chunks via requantize_step); the graph is NOT
        rebuilt — old/new codes coexist through versioned LUTs."""
        n = min(self.count, self.cfg.refine_sample)
        sample = self.pv.vectors[:n]
        refined = pqmod.refine_pq(self._next_key(), self.schemas[-1], jnp.asarray(sample))
        self.schemas = [self.schemas[-1], refined][-2:]
        self._requant_cursor = 0

    def requantize_step(self, chunk: int = 4096) -> bool:
        """Re-encode one chunk with the newest schema. True when done."""
        if len(self.schemas) < 2:
            return True
        lo = self._requant_cursor
        hi = min(lo + chunk, self.count)
        if lo >= hi:
            # transition complete: retire the old schema
            self.schemas = [self.schemas[-1]]
            self.pv.versions[: self.count] = 0
            self.pv._dirty()
            return True
        ids = np.arange(lo, hi)
        codes = np.asarray(
            pqmod.encode(self.schemas[-1], jnp.asarray(self.pv.vectors[ids]))
        )
        self.pv.set_quant(self.ctx, ids, codes, np.full(len(ids), 1, np.uint8))
        self._requant_cursor = hi
        return False

    def requantize_all(self):
        while not self.requantize_step():
            pass

    # ------------------------------------------------------------------
    # deletion (Alg 6) + background consolidation
    # ------------------------------------------------------------------
    def delete(self, doc_ids: Sequence[int], policy: str = "inplace"):
        for d in doc_ids:
            slot = self.doc_to_slot.pop(int(d), None)
            if slot is None:
                continue
            with span("write.delete", docs=1):
                self._delete_slot(slot, policy)

    def _delete_slot(self, slot: int, policy: str):
        cfg = self.cfg
        self.slot_to_doc[slot] = -1
        self.pv.set_live(self.ctx, np.asarray([slot]), False)
        if policy == "inplace" and self._graph_built:
            neighbors, _, _, live, _ = self.pv.materialize(self.ctx)
            with span("write.readback"):
                old_nb = np.array(neighbors)  # copy: kernel donates its input
            with span("write.repair"):
                # every slot decoded (one shape for any count), unused
                # slots zeroed
                decoded = jnp.where(
                    (jnp.arange(cfg.capacity) < self.count)[:, None],
                    self._decoded(np.arange(cfg.capacity)), 0.0)
                new_nb = np.asarray(dmod.inplace_delete(
                    neighbors, live, decoded,
                    jnp.int32(slot),
                    R=cfg.R, R_slack=cfg.R_slack, alpha=cfg.alpha,
                    c_replace=cfg.c_replace, metric=cfg.metric,
                ))
            with span("write.diff"):
                self._write_neighbor_diff(old_nb, new_nb)
        if slot == self.medoid and self.num_live:
            self.medoid = int(
                g.compute_medoid(
                    jnp.asarray(self.pv.vectors), jnp.asarray(self.pv.live)
                )
            )

    def recompute_medoid(self):
        """Start-point maintenance (FreshDiskANN practice): after heavy
        churn the medoid should track the live distribution."""
        if self.num_live:
            self.medoid = int(
                g.compute_medoid(jnp.asarray(self.pv.vectors), jnp.asarray(self.pv.live))
            )

    def consolidate(self, chunk: int = 1024):
        """One background-sweep step: clear dangling edges to dead nodes."""
        neighbors, _, _, live, _ = self.pv.materialize(self.ctx)
        old_nb = np.array(neighbors)  # copy: kernel donates its input
        new_nb = dmod.consolidate_chunk(
            neighbors, live, jnp.int32(self._consolidate_cursor), chunk
        )
        self._write_neighbor_diff(old_nb, np.asarray(new_nb))
        self._consolidate_cursor = (self._consolidate_cursor + chunk) % max(self.count, 1)

    def _write_neighbor_diff(self, old_nb: np.ndarray, new_nb: np.ndarray):
        """Write only the rows a graph repair changed, through the provider.

        Durable providers log `set_neighbors` to their WAL; a direct
        whole-array store would leave the repair invisible to replay, so
        recovery would resurrect dangling edges the repair had cleared.
        """
        changed = np.nonzero((old_nb != new_nb).any(axis=1))[0]
        if changed.size:
            self.pv.set_neighbors(self.ctx, changed, new_nb[changed])
        # the repair kernels donate the provider's cached device buffer, so
        # the materialize cache is stale even when no row changed
        self.pv._dirty()

    # ------------------------------------------------------------------
    # queries (§3.5)
    # ------------------------------------------------------------------
    def search(
        self,
        queries: np.ndarray,
        k: int,
        L: Optional[int] = None,
        rerank_multiplier: float = fmod.QUANTIZED_LIST_MULTIPLIER,
        pad_to_bucket: bool = False,
        batch_buckets: tuple[int, ...] = smod.BATCH_BUCKETS,
        beam_width: Optional[int] = None,
    ) -> tuple[np.ndarray, np.ndarray, QueryStats]:
        """Top-k ANN: graph search in quantized space + full-precision
        re-rank. Returns (doc_ids (B,k), dists (B,k), stats).

        With ``pad_to_bucket`` the query batch is padded to the next static
        bucket before any jitted stage (LUTs, graph search, re-rank) so the
        serving layer's varying batch sizes map onto a handful of compiled
        signatures; outputs and stats are sliced back to the true batch.
        ``beam_width`` overrides the config's W (frontier nodes expanded
        per round); None → ``cfg.beam_width``.

        Spans: ``search.prepare`` (pad, device arrays, LUTs),
        ``search.graph`` (to the beam's read back), ``search.rerank`` (tier
        touch and launch), ``search.finish`` (stats and answers read back,
        slot → doc id).
        """
        W = int(beam_width or self.cfg.beam_width)
        queries = np.asarray(queries, np.float32)
        B = len(queries)
        L = L or self.cfg.L_search
        stats = QueryStats()
        kprime = max(k, int(round(rerank_multiplier * k)))
        with span("search.prepare"):
            if pad_to_bucket:
                queries = smod.pad_batch_np(
                    queries, smod.next_bucket(B, batch_buckets))
            neighbors, codes, versions, live, vectors = self.pv.materialize(
                self.ctx)
            if self._graph_built:
                luts = self._luts(queries)

        if not self._graph_built:
            stats.plan = "brute_force"
            ids, dists = fmod.brute_force(
                jnp.asarray(queries), vectors, live, k=k, metric=self.cfg.metric
            )
            stats.full_reads = self.num_live
            # a full sweep reads every live page once for the whole
            # batch; scan-resistant (admit=False) so it can't flush the
            # rerank working set
            self._touch_tier(np.nonzero(self.pv.live)[0], stats, B,
                             admit=False)
            return (
                self._to_doc_ids(np.asarray(ids))[:B],
                np.asarray(dists)[:B],
                stats,
            )

        L_eff = max(L, kprime)
        with span("search.graph", W=W, L=L_eff):
            # queries are already bucket-padded above when pad_to_bucket is
            # set, so the wrapper's own pad is a no-op then; it still
            # normalizes any direct unpadded call onto the same static
            # signatures
            res = smod.bucketed_batch_greedy_search(
                neighbors, codes, versions, live, luts,
                jnp.int32(self.medoid),
                L=L_eff, batch_buckets=batch_buckets, beam_width=W,
            )
            beam = np.asarray(res.beam_ids)
        with span("search.rerank", kprime=kprime):
            # final rerank is the ONLY stage that reads full-precision
            # vectors: pin the candidate pages (they must not be evicted
            # mid-rerank), fetch misses, release after
            pinned = self._touch_tier(beam[:B, :kprime], stats, B, pin=True)
            ids, dists = fmod.rerank(
                jnp.asarray(queries), res.beam_ids[:, :kprime], vectors,
                k=k, metric=self.cfg.metric,
            )
            self._unpin_tier(pinned)
        # the stats are read while the rerank runs, its answers last
        with span("search.finish"):
            stats.hops = float(np.asarray(res.n_hops)[:B].mean())
            stats.cmps = float(np.asarray(res.n_cmps)[:B].mean())
            stats.expansions = float(np.asarray(res.n_exp)[:B].mean())
            stats.full_reads = float(kprime)
            return (self._to_doc_ids(np.asarray(ids))[:B],
                    np.asarray(dists)[:B], stats)

    def _to_doc_ids(self, slots: np.ndarray) -> np.ndarray:
        out = np.where(slots >= 0, self.slot_to_doc[np.maximum(slots, 0)], -1)
        return out

    # -- filtered queries (§3.5, Fig 9) ---------------------------------
    def filtered_search(
        self,
        queries: np.ndarray,
        k: int,
        doc_filter: np.ndarray,  # bool over doc slots (the PES bitmap role)
        L: Optional[int] = None,
        mode: str = "auto",  # auto | post | beta | qflat | brute
        beta: float = 0.3,
        rerank_multiplier: float = fmod.QUANTIZED_LIST_MULTIPLIER,
        beam_width: Optional[int] = None,
        pad_to_bucket: bool = False,
        batch_buckets: tuple[int, ...] = smod.BATCH_BUCKETS,
        filter_words: Optional[np.ndarray] = None,
    ) -> tuple[np.ndarray, np.ndarray, QueryStats]:
        """Query-planner routing by selectivity, then post-filter or
        β-biased graph search.

        With ``pad_to_bucket`` the micro-batch pads to the next static
        bucket before any jitted stage — the serving engine's batched
        filtered path (same-predicate queries share one bitmap broadcast)
        reuses the exact (bucket, L, W) signature set as unfiltered
        serving, so steady-state filtered traffic triggers zero
        recompiles. Outputs and stats slice back to the true batch.
        ``filter_words`` optionally supplies ``doc_filter`` pre-packed in
        the uint32 ``filter_bits`` layout (the predicate compiler's native
        output), skipping the β-branch re-pack.

        Spans as in ``search``; the brute-force and Q-Flat plans run
        under ``search.prepare`` alone."""
        W = int(beam_width or self.cfg.beam_width)
        queries = np.asarray(queries, np.float32)
        B = len(queries)
        L = L or self.cfg.L_search
        matches = int((doc_filter & self.pv.live).sum())
        stats = QueryStats()
        if mode == "auto":
            if self.num_live <= fmod.BRUTE_FORCE_MAX_DOCS or not self._graph_built:
                mode = "brute"
            elif matches < fmod.QFLAT_MAX_MATCHES:
                mode = "qflat"
            else:
                mode = "beta"
        stats.plan = mode
        kprime = max(k, int(round(rerank_multiplier * k)))
        with span("search.prepare"):
            if pad_to_bucket:
                queries = smod.pad_batch_np(
                    queries, smod.next_bucket(B, batch_buckets)
                )
            neighbors, codes, versions, live, vectors = self.pv.materialize(
                self.ctx)
            fmask = jnp.asarray(doc_filter & self.pv.live)
            if mode != "brute":
                luts = self._luts(queries)

        if mode == "brute":
            ids, dists = fmod.brute_force(
                jnp.asarray(queries), vectors, fmask, k=k, metric=self.cfg.metric
            )
            stats.full_reads = matches
            self._touch_tier(np.nonzero(doc_filter & self.pv.live)[0],
                             stats, B, admit=False)
            return (self._to_doc_ids(np.asarray(ids))[:B],
                    np.asarray(dists)[:B], stats)

        if mode == "qflat":
            cand, _ = fmod.qflat_scan(
                luts, codes, versions, fmask, kprime=kprime, metric=self.cfg.metric
            )
            pinned = self._touch_tier(np.asarray(cand)[:B], stats, B,
                                      pin=True)
            ids, dists = fmod.rerank(
                jnp.asarray(queries), cand, vectors, k=k, metric=self.cfg.metric
            )
            self._unpin_tier(pinned)
            stats.cmps = matches
            stats.full_reads = kprime
            return (self._to_doc_ids(np.asarray(ids))[:B],
                    np.asarray(dists)[:B], stats)

        with span("search.graph", W=W, L=max(L, kprime)):
            if mode == "post":
                res = smod.bucketed_batch_greedy_search(
                    neighbors, codes, versions, live, luts,
                    jnp.int32(self.medoid), L=max(L, kprime),
                    batch_buckets=batch_buckets, beam_width=W,
                )
            else:  # beta (Alg 7)
                fbits = (filter_words if filter_words is not None
                         else self._pack_bits(np.asarray(doc_filter)))
                fb = jnp.asarray(
                    np.broadcast_to(fbits, (len(queries),) + fbits.shape)
                )
                res = smod.bucketed_batch_greedy_search(
                    neighbors, codes, versions, live, luts,
                    jnp.int32(self.medoid), L=max(L, kprime),
                    batch_buckets=batch_buckets,
                    filter_bits=fb, beta=beta, beam_width=W,
                )
            beam = np.asarray(res.beam_ids)
            passes = doc_filter[np.maximum(beam, 0)] & (beam >= 0)
            beam = np.where(passes, beam, -1)
        with span("search.rerank", kprime=kprime):
            pinned = self._touch_tier(beam[:B, : max(L, kprime)], stats, B,
                                      pin=True)
            ids, dists = fmod.rerank(
                jnp.asarray(queries), jnp.asarray(beam[:, : max(L, kprime)]),
                vectors, k=k, metric=self.cfg.metric,
            )
            self._unpin_tier(pinned)
        with span("search.finish"):
            stats.hops = float(np.asarray(res.n_hops)[:B].mean())
            stats.cmps = float(np.asarray(res.n_cmps)[:B].mean())
            stats.expansions = float(np.asarray(res.n_exp)[:B].mean())
            stats.full_reads = float(kprime)
            return (self._to_doc_ids(np.asarray(ids))[:B],
                    np.asarray(dists)[:B], stats)

    @staticmethod
    def _pack_bits(mask: np.ndarray) -> np.ndarray:
        words = np.zeros(((len(mask) + 31) // 32,), np.uint32)
        idx = np.nonzero(mask)[0]
        np.bitwise_or.at(words, idx >> 5, np.uint32(1) << (idx & 31).astype(np.uint32))
        return words

    # -- pagination (§3.2 / §3.5 Continuations) ---------------------------
    def start_pagination(self, query: np.ndarray, L: Optional[int] = None,
                         backup_cap: int = PAGE_BACKUP_CAP) -> pgmod.PageState:
        L = L or self.cfg.L_search
        _, codes, versions, _, _ = self.pv.materialize(self.ctx)
        lut = self._luts(query[None, :])[0]
        return pgmod.start_pagination(
            self.cfg.capacity, L, backup_cap, codes, versions, lut,
            jnp.int32(self.medoid),
        )

    @staticmethod
    def page_stats(prev: pgmod.PageState, new: pgmod.PageState, k: int,
                   rerank: bool = True) -> QueryStats:
        """Per-page work delta from the cumulative PageState counters —
        feeds the same ``counters_for_ru`` / ``counters_for_latency`` split
        as the main search path, so a page is billed for the quantized
        comparisons and adjacency rows it actually fetched plus the k
        full-precision re-rank reads (a page is never free)."""
        return QueryStats(
            hops=float(int(new.hops) - int(prev.hops)),
            cmps=float(int(new.cmps) - int(prev.cmps)),
            expansions=float(int(new.exp) - int(prev.exp)),
            full_reads=float(k if rerank else 0),
            plan="paginated",
        )

    def next_page(
        self, query: np.ndarray, state: pgmod.PageState, k: int,
        rerank: bool = True, beam_width: Optional[int] = None,
        slot_filter: Optional[np.ndarray] = None,  # bool over doc slots
    ) -> tuple[np.ndarray, np.ndarray, pgmod.PageState]:
        """One page of k results. With ``slot_filter`` (a compiled predicate
        bitmap) non-matching slots are dropped from the page AFTER the
        traversal step, so the visited set still advances and later pages
        surface the matches the traversal hasn't reached yet — a filtered
        page may carry fewer than k rows, but the stream stays
        gap-free/repeat-free (the fan-out merge refetches empty pages)."""
        neighbors, codes, versions, live, vectors = self.pv.materialize(self.ctx)
        lut = self._luts(query[None, :])[0]
        ids, dists, state = pgmod.next_page(
            neighbors, codes, versions, live, lut, state, k=k,
            beam_width=int(beam_width or self.cfg.beam_width),
        )
        if slot_filter is not None:
            arr = np.asarray(ids)
            keep = (arr >= 0) & slot_filter[np.maximum(arr, 0)]
            ids = jnp.asarray(np.where(keep, arr, -1))
            dists = jnp.asarray(np.where(keep, np.asarray(dists), np.inf))
        self.last_page_tier = (0.0, 0.0)
        if rerank:
            tst = QueryStats()
            pinned = self._touch_tier(np.asarray(ids), tst, 1, pin=True)
            rids, rd = fmod.rerank(
                jnp.asarray(query[None, :]), ids[None, :], vectors,
                k=k, metric=self.cfg.metric,
            )
            self._unpin_tier(pinned)
            self.last_page_tier = (tst.tier_hits, tst.tier_misses)
            return self._to_doc_ids(np.asarray(rids))[0], np.asarray(rd)[0], state
        return self._to_doc_ids(np.asarray(ids[None, :]))[0], np.asarray(dists), state

    # ------------------------------------------------------------------
    # persistence (fault tolerance)
    # ------------------------------------------------------------------
    def snapshot(self) -> dict:
        return dict(
            neighbors=self.pv.neighbors.copy(),
            codes=self.pv.codes.copy(),
            versions=self.pv.versions.copy(),
            live=self.pv.live.copy(),
            vectors=self.pv.vectors.copy(),
            slot_to_doc=self.slot_to_doc.copy(),
            count=self.count,
            medoid=self.medoid,
            schemas=[np.asarray(s.codebooks) for s in self.schemas],
            graph_built=self._graph_built,
        )

    def restore(self, snap: dict):
        self.pv.neighbors[:] = snap["neighbors"]
        self.pv.codes[:] = snap["codes"]
        self.pv.versions[:] = snap["versions"]
        self.pv.live[:] = snap["live"]
        self.pv.vectors[:] = snap["vectors"]
        self.pv._dirty()
        self.slot_to_doc[:] = snap["slot_to_doc"]
        self.count = snap["count"]
        self.medoid = snap["medoid"]
        self.schemas = [
            pqmod.PQSchema(codebooks=jnp.asarray(cb), version=jnp.int32(i))
            for i, cb in enumerate(snap["schemas"])
        ]
        self._graph_built = snap["graph_built"]
        self.doc_to_slot = {
            int(d): int(s) for s, d in enumerate(self.slot_to_doc) if d >= 0
        }
