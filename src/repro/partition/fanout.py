"""Cross-partition query fan-out (§3.5 "SDK Query Plan", §4.3, Fig 10).

Two implementations of the same scatter/gather:

  * ``fanout_search`` — the client-side SDK path: issue the query to every
    physical partition (through its replica set), merge partial top-k
    results, track per-partition RU and the max-latency effect the paper
    highlights ("client end-to-end latency is sensitive to the worst
    latency on the server side"). Includes hedged requests: when a replica
    is slower than the hedge threshold, a duplicate request goes to another
    replica and the fastest answer wins — the standard tail-latency /
    straggler mitigation at fleet scale.

  * ``distributed_search_fn`` — the jitted `shard_map` path: one DiskANN
    shard per device, lockstep beam search over the local shard, local
    re-rank, then a global top-k merge via all_gather. This is what the
    multi-pod dry-run lowers for the production meshes.

  * ``SpmdFanout`` — the engine-facing SPMD dispatch
    (``EngineConfig.dispatch_mode="spmd"``): live partitions stack into
    per-partition arrays sharded over a mesh, and ONE jitted shard_map
    program runs every partition's bucketed search + re-rank as a single
    data-parallel call — bit-identical to the serial per-partition loop,
    RU metered on each partition's own meter, zero steady-state
    recompiles (`spmd_jit_cache_size` feeds the serving cache telemetry).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from ..core import flat as fmod
from ..core import paginate as pgmod
from ..core import pq as pqmod
from ..core import search as smod
from ..core.index import QueryStats
from ..store.faults import CrashError
from ..store.props import words_to_mask
from ..store.ru import counters_for_latency, counters_for_ru

INF = jnp.float32(jnp.inf)


class AllPartitionsFailed(RuntimeError):
    """Zero partitions answered a fan-out: nothing to degrade to — the
    only case where partial-result degradation still hard-fails."""


# ---------------------------------------------------------------------------
# client-side fan-out (host path)
# ---------------------------------------------------------------------------


def merge_topk(
    ids_list: Sequence[np.ndarray], dists_list: Sequence[np.ndarray], k: int
) -> tuple[np.ndarray, np.ndarray]:
    """Merge per-partition (B, k_i) partial results into global (B, k)."""
    ids = np.concatenate(ids_list, axis=1)
    dists = np.concatenate(dists_list, axis=1)
    dists = np.where(ids >= 0, dists, np.inf)
    order = np.argsort(dists, axis=1)[:, :k]
    return np.take_along_axis(ids, order, 1), np.take_along_axis(dists, order, 1)


def fanout_search(
    partitions,  # Sequence[PhysicalPartition] or Sequence[ReplicaSet]
    queries: np.ndarray,
    k: int,
    L: Optional[int] = None,
    latency_model=None,
    hedge_at_ms: Optional[float] = None,
    rng: Optional[np.random.RandomState] = None,
) -> tuple[np.ndarray, np.ndarray, dict]:
    """Scatter to all partitions, gather, merge. Returns (ids, dists, info).

    info: per-partition RU, modelled server latencies, client latency
    (= max over partitions), hedges issued.
    """
    rng = rng or np.random.RandomState(0)
    ids_l, dists_l, rus, lats = [], [], [], []
    hedges = 0
    hedge_ru = 0.0
    for p in partitions:
        ids, dists, ru = p.search(queries, k, L)
        ids_l.append(ids)
        dists_l.append(dists)
        rus.append(ru)
        if latency_model is not None:
            lat = latency_model(p, rng)
            if hedge_at_ms is not None and lat > hedge_at_ms:
                hedges += 1
                # a hedge is a SECOND server-side execution on another
                # replica: the fastest answer wins the latency race, but
                # both executions did the work — the duplicate bills too
                hedge_ru += ru
                lat = min(lat, latency_model(p, rng))  # hedged duplicate
            lats.append(lat)
    ids, dists = merge_topk(ids_l, dists_l, k)
    info = dict(
        ru_per_partition=rus,
        ru_total=float(np.sum(rus)) + hedge_ru,
        server_latencies_ms=lats,
        client_latency_ms=float(np.max(lats)) if lats else 0.0,
        hedges=hedges,
        hedge_ru=hedge_ru,
    )
    return ids, dists, info


def batched_fanout_search(
    partitions,  # Sequence[PhysicalPartition]
    queries: np.ndarray,  # (B, D) — a dense micro-batch of independent queries
    k: int,
    L: Optional[int] = None,
    batch_buckets: Optional[tuple[int, ...]] = None,
    beam_width: Optional[int] = None,
    health=None,  # optional callable(partition) -> bool (replica liveness)
) -> tuple[np.ndarray, np.ndarray, dict]:
    """Multi-query scatter/gather for the serving engine.

    Unlike ``fanout_search`` (one logical query, per-partition bookkeeping),
    this dispatches a whole micro-batch to every partition as ONE
    fixed-shape device call (padded to `batch_buckets`), then merges the
    per-partition top-k. info carries total RU, per-partition RU/stats, and
    the modelled worst-partition latency (client latency tracks the slowest
    partition, §4.3).

    The latency model is *round-structured* (``store.ru
    .counters_for_latency``): a beam-width round's quantized reads issue
    concurrently and its adjacency fetches coalesce into one round trip.
    RU, by contrast, still charges every read (see
    ``PhysicalPartition.search_batch``): W buys latency, not free work.
    """
    kw: dict = {}
    if batch_buckets is not None:
        kw = dict(pad_to_bucket=True, batch_buckets=batch_buckets)
    if beam_width is not None:
        kw["beam_width"] = beam_width
    ids_l, dists_l, rus, lat_ms = [], [], [], []
    stats_l = []
    failed: list[tuple[int, str]] = []
    for p in partitions:
        if health is not None and not health(p):
            failed.append((int(p.pid), "replica set down"))
            continue
        try:
            ids, dists, ru, stats = p.search_batch(queries, k, L, **kw)
        except (CrashError, jax.errors.JaxRuntimeError):
            # an injected process kill, or a device fault (a compile
            # failure, HBM exhausted), is not a partition fault
            raise
        except Exception as e:  # noqa: BLE001 — degrade, don't collapse
            failed.append((int(p.pid), f"{type(e).__name__}: {e}"))
            continue
        ids_l.append(ids)
        dists_l.append(dists)
        rus.append(ru)
        stats_l.append(stats)
        lat_ms.append(
            p.providers.meter.latency_ms(counters_for_latency(stats))
        )
    if failed and not ids_l:
        raise AllPartitionsFailed(
            f"all {len(list(partitions))} partitions failed: {failed}"
        )
    if ids_l:
        ids, dists = merge_topk(ids_l, dists_l, k)
    else:  # empty collection: nothing failed, nothing to merge
        ids = np.full((len(queries), k), -1, np.int64)
        dists = np.full((len(queries), k), np.inf, np.float32)
    info = dict(
        partition_ids=[int(p.pid) for p in partitions],
        ru_per_partition=rus,
        ru_total=float(np.sum(rus)) if rus else 0.0,
        stats_per_partition=stats_l,
        server_latencies_ms=lat_ms,
        service_latency_ms=float(np.max(lat_ms)) if lat_ms else 0.0,
        failed_partitions=failed,
        complete=not failed,
    )
    return ids, dists, info


def compile_partition_filter(p, predicate):
    """Compile ``predicate`` against one partition's property-term index.
    Returns (bool slot mask, packed uint32 words, posting reads billed);
    mask and words are None when the predicate matches nothing in this
    partition. Pure bitmap algebra over the inverted PROP_TERM postings,
    cached per (partition, canonical predicate) and invalidated by ingest
    epoch. Never touches the doc store or ``doc_to_slot``. The words are
    already in the ``filter_bits`` layout, so the β-search path consumes
    them directly without a re-pack."""
    words = p.props.compile(predicate)
    nreads = p.props.last_compile_reads
    if not words.any():
        return None, None, nreads
    return words_to_mask(words, p.index.cfg.capacity), words, nreads


def batched_filtered_fanout_search(
    partitions,  # Sequence[PhysicalPartition]
    queries: np.ndarray,  # (B, D) — a micro-batch sharing ONE predicate
    k: int,
    predicate,  # serve.predicate.Predicate (canonical, hashable)
    L: Optional[int] = None,
    batch_buckets: Optional[tuple[int, ...]] = None,
    beam_width: Optional[int] = None,
    health=None,  # optional callable(partition) -> bool (replica liveness)
) -> tuple[np.ndarray, np.ndarray, dict]:
    """Multi-query scatter/gather for FILTERED micro-batches: every lane
    shares the same canonical predicate (the engine groups by predicate
    key), so the predicate compiles to one bitmap per partition —
    broadcast through ``bucketed_batch_greedy_search`` via the
    ``filter_bits`` plumbing — instead of one O(capacity) document scan
    per query per partition (the legacy callable path).

    Empty partitions and partitions where the predicate matches nothing
    are skipped outright (no bitmap minted, no search run). info carries
    the per-partition plan aggregate as ``plan`` (e.g.
    ``filtered-batched[beta×2,qflat×1]``), RU/stats/latency in the same
    shape as ``batched_fanout_search``.
    """
    kw: dict = {}
    if batch_buckets is not None:
        kw = dict(pad_to_bucket=True, batch_buckets=batch_buckets)
    if beam_width is not None:
        kw["beam_width"] = beam_width
    B, k = len(queries), int(k)
    ids_l, dists_l, rus, lat_ms, stats_l = [], [], [], [], []
    pids: list[int] = []
    plans: dict[str, int] = {}
    compile_ru = 0.0
    failed: list[tuple[int, str]] = []
    answered = 0  # searched OR legitimately skipped (known-empty) partitions
    for p in partitions:
        if p.num_docs == 0:
            answered += 1
            continue
        if health is not None and not health(p):
            failed.append((int(p.pid), "replica set down"))
            continue
        try:
            mask, words, nreads = compile_partition_filter(p, predicate)
            if mask is None:
                # the compile still read postings (cache miss) — a no-match
                # partition is skipped, not free
                compile_ru += nreads * p.providers.meter.cfg.ru_per_prop_read
                answered += 1
                continue
            ids, dists, ru, stats = p.filtered_search_batch(
                queries, k, mask, L=L, term_reads=nreads,
                filter_words=words, **kw
            )
        except (CrashError, jax.errors.JaxRuntimeError):
            # an injected process kill, or a device fault (a compile
            # failure, HBM exhausted), is not a partition fault
            raise
        except Exception as e:  # noqa: BLE001 — degrade, don't collapse
            failed.append((int(p.pid), f"{type(e).__name__}: {e}"))
            continue
        answered += 1
        ids_l.append(ids)
        dists_l.append(dists)
        rus.append(ru)
        stats_l.append(stats)
        pids.append(int(p.pid))
        plans[stats.plan] = plans.get(stats.plan, 0) + 1
        lat_ms.append(
            p.providers.meter.latency_ms(counters_for_latency(stats))
        )
    if failed and answered == 0:
        raise AllPartitionsFailed(
            f"all candidate partitions failed: {failed}"
        )
    if not ids_l:  # predicate matches nothing in any answering partition
        ids = np.full((B, k), -1, np.int64)
        dists = np.full((B, k), np.inf, np.float32)
        plan = "filtered-batched[empty]"
    else:
        ids, dists = merge_topk(ids_l, dists_l, k)
        plan = "filtered-batched[" + ",".join(
            f"{name}×{count}" for name, count in sorted(plans.items())
        ) + "]"
    info = dict(
        partition_ids=pids,
        ru_per_partition=rus,
        ru_total=(float(np.sum(rus)) if rus else 0.0) + compile_ru,
        stats_per_partition=stats_l,
        server_latencies_ms=lat_ms,
        service_latency_ms=float(np.max(lat_ms)) if lat_ms else 0.0,
        plan=plan,
        partitions_searched=len(ids_l),
        compile_ru=compile_ru,
        failed_partitions=failed,
        complete=not failed,
    )
    return ids, dists, info


# ---------------------------------------------------------------------------
# cross-partition pagination (§3.5 "Continuations" — client-side merge)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class PartitionPageCursor:
    """One partition's slice of a cross-partition pagination.

    ``state`` is the partition-local ``PageState`` (dropped once the
    partition is exhausted, shrinking the token); ``buf_*`` hold results
    already fetched from the partition but not yet emitted in a merged
    page; ``fetch_hwm`` is the partition's high-water mark — the largest
    distance it has produced so far. A partition's page stream is
    ascending, so everything it will produce later is ≥ ``fetch_hwm``;
    the merge exploits that bound through its nonempty-buffer rule (see
    ``paged_fanout_search``), and the token decoder enforces the
    buffer-vs-hwm consistency a resumed token must satisfy.
    """

    pid: int
    state: Optional[pgmod.PageState]
    buf_ids: np.ndarray  # (n,) int64, ascending by buf_dists
    buf_dists: np.ndarray  # (n,) float32
    fetch_hwm: float = -np.inf
    exhausted: bool = False


@dataclasses.dataclass
class PagedQueryState:
    """The whole cross-partition continuation: one cursor per physical
    partition plus global merge bookkeeping. This object IS the token —
    ``serve.continuation`` round-trips it through a versioned, schema-
    checked numpy codec (never pickle: tokens are client-supplied bytes)."""

    shard_fp: int  # fingerprint of (shard_key, partition ids) at start
    emit_hwm: float  # largest distance emitted in any merged page
    pages: int  # merged pages emitted so far
    cursors: list[PartitionPageCursor]

    def exhausted(self) -> bool:
        return all(c.exhausted and len(c.buf_ids) == 0 for c in self.cursors)


def paged_fanout_fingerprint(shard_key, partitions, pred_key=None) -> int:
    """Bind a token to the routing that minted it: resuming under a
    different shard key — or after a split/merge changed the partition
    set, or under a DIFFERENT predicate (``pred_key`` = the predicate's
    canonical key bytes) — is rejected up front, not silently mis-merged."""
    from .partitioner import hash_key

    ident: tuple = (repr(shard_key), tuple(int(p.pid) for p in partitions))
    if pred_key is not None:
        ident += (pred_key,)
    return hash_key(ident)


def start_paged_fanout(partitions, query: np.ndarray, shard_key=None,
                       L: Optional[int] = None, pred_key=None,
                       slot_filters: Optional[Sequence] = None) -> PagedQueryState:
    """Open one pagination cursor per physical partition. With
    ``slot_filters`` (one compiled predicate mask — or None — per
    partition, index-aligned), partitions where the predicate matches
    nothing start exhausted: no cursor state is minted and no page is
    ever fetched from them."""
    query = np.asarray(query, np.float32)
    cursors = []
    for i, p in enumerate(partitions):
        dead = (slot_filters is not None and slot_filters[i] is None) \
            or p.num_docs == 0
        cursors.append(PartitionPageCursor(
            pid=int(p.pid),
            state=None if dead else p.start_pagination(query, L=L),
            buf_ids=np.zeros((0,), np.int64),
            buf_dists=np.zeros((0,), np.float32),
            exhausted=dead,
        ))
    return PagedQueryState(
        shard_fp=paged_fanout_fingerprint(shard_key, partitions, pred_key),
        emit_hwm=-np.inf, pages=0, cursors=cursors,
    )


def _fetch_partition_page(p, cur: PartitionPageCursor, query: np.ndarray,
                          k: int, beam_width: Optional[int],
                          slot_filter=None) -> tuple[float, float]:
    """Pull one page from partition ``p`` into the cursor's buffer.
    Returns (ru, modelled latency ms) for this fetch."""
    ids, dists, state, ru, stats = p.next_page(
        query, cur.state, k=k, beam_width=beam_width, slot_filter=slot_filter
    )
    lat_ms = p.providers.meter.latency_ms(counters_for_latency(stats))
    ids, dists = np.asarray(ids), np.asarray(dists)
    valid = (ids >= 0) & np.isfinite(dists)
    ids = ids[valid].astype(np.int64)
    dists = dists[valid].astype(np.float32)
    cur.state = state
    if len(ids):
        cur.fetch_hwm = max(cur.fetch_hwm, float(dists.max()))
        bi = np.concatenate([cur.buf_ids, ids])
        bd = np.concatenate([cur.buf_dists, dists])
        # re-sort: full-precision re-rank can jitter the tail ordering
        order = np.argsort(bd, kind="stable")
        cur.buf_ids, cur.buf_dists = bi[order], bd[order]
    # an empty page means "done" only on the unfiltered path: a filtered
    # page can legitimately carry zero matches while the traversal still
    # has unvisited region — exhaustion there is the traversal's call
    if (len(ids) == 0 and slot_filter is None) or bool(pgmod.exhausted(state)):
        cur.exhausted = True
        cur.state = None  # nothing left to resume — shrink the token
    return ru, lat_ms


def paged_fanout_search(
    partitions,  # Sequence[PhysicalPartition], index-aligned with cursors
    query: np.ndarray,  # (D,)
    pstate: PagedQueryState,
    page_size: int,
    beam_width: Optional[int] = None,
    slot_filters: Optional[Sequence] = None,  # per-partition masks or None
    executor=None,  # serve.executor.LaneExecutor: lane-scheduled refills
) -> tuple[np.ndarray, np.ndarray, dict]:
    """Produce the next globally-merged page across all partitions.

    Buffered k-way merge: before every emit, each non-exhausted partition
    holds a nonempty buffer, so the global buffer minimum is ≤ every
    partition's ``fetch_hwm`` — nothing still unfetched anywhere can beat
    it. Emitted results therefore never repeat and never skip, and the
    per-partition leftovers ride along in the continuation token.

    Refills run as multi-cursor ROUNDS: every starved partition pulls one
    ``next_page`` per round until all buffers are non-empty. With an
    ``executor`` each round books its fetches across the replica lanes
    and service latency is the lane horizon of the whole page — the max
    fetch per round with ≥P lanes, the host-loop sum with one lane;
    without one, the legacy accounting stands (max of per-partition
    sums). The fetch sequence per partition is identical either way, so
    results, cursors and RU never depend on the executor. info also
    carries the fixed per-request RU floor — a continuation request is
    never free, even when a page is served entirely from the token's
    buffers (§2.2: every request bills at least the request-processing
    charge).
    """
    assert len(partitions) == len(pstate.cursors), \
        "cursors must be index-aligned with the partition routing"
    query = np.asarray(query, np.float32)
    n = len(partitions)
    out_ids: list[int] = []
    out_dists: list[float] = []
    rus = [0.0] * n
    lat_sums = [0.0] * n
    fetches = 0
    exec_ms = 0.0
    rounds = 0
    # per-fetch log (round, pid, ru, lat_ms) — the trace plane turns each
    # entry into one child span of the page's lane span
    fetch_log: list[dict] = []

    def _refill_rounds():
        nonlocal fetches, exec_ms, rounds
        while True:
            round_lats = []
            for i, (p, cur) in enumerate(zip(partitions, pstate.cursors)):
                if cur.exhausted or len(cur.buf_ids):
                    continue
                ru, lat = _fetch_partition_page(
                    p, cur, query, page_size, beam_width,
                    slot_filter=None if slot_filters is None
                    else slot_filters[i],
                )
                rus[i] += ru
                lat_sums[i] += lat
                round_lats.append(lat)
                fetch_log.append(dict(round=rounds, pid=int(p.pid),
                                      ru=float(ru), lat_ms=float(lat)))
                fetches += 1
            if not round_lats:
                return
            rounds += 1
            if executor is not None:
                # schedule_round returns the lane horizon relative to the
                # (unmoving) clock; successive rounds stack on the same
                # lanes, so the LAST horizon is the page's total makespan
                # — taking the max, not the sum, avoids double counting
                exec_ms = max(exec_ms, executor.schedule_round(round_lats))

    while len(out_ids) < page_size:
        _refill_rounds()
        heads = [
            (float(cur.buf_dists[0]), i)
            for i, cur in enumerate(pstate.cursors) if len(cur.buf_ids)
        ]
        if not heads:
            break  # every partition exhausted and drained
        d, i = min(heads)
        cur = pstate.cursors[i]
        out_ids.append(int(cur.buf_ids[0]))
        out_dists.append(d)
        cur.buf_ids = cur.buf_ids[1:]
        cur.buf_dists = cur.buf_dists[1:]
        pstate.emit_hwm = max(pstate.emit_hwm, d)
    pstate.pages += 1

    ids = np.full((page_size,), -1, np.int64)
    dists = np.full((page_size,), np.inf, np.float32)
    ids[: len(out_ids)] = out_ids
    dists[: len(out_dists)] = out_dists
    request_ru = (
        partitions[0].providers.meter.cfg.ru_per_page_request if n else 0.0
    )
    info = dict(
        partition_ids=[int(p.pid) for p in partitions],
        ru_per_partition=rus,
        request_ru=request_ru,
        ru_total=float(np.sum(rus)) + request_ru,
        fetch_log=fetch_log,
        server_latencies_ms=lat_sums,
        service_latency_ms=(exec_ms if executor is not None
                            else float(np.max(lat_sums)) if lat_sums else 0.0),
        lane_scheduled=executor is not None,
        pages_fetched=fetches,
        emit_hwm=pstate.emit_hwm,  # how deep into the result set we are
        exhausted=pstate.exhausted(),
    )
    return ids, dists, info


# ---------------------------------------------------------------------------
# device-parallel fan-out (jitted shard_map path — used by the dry-run)
# ---------------------------------------------------------------------------


def distributed_search_fn(
    mesh: jax.sharding.Mesh,
    *,
    L: int,
    k: int,
    metric: str = "l2",
    shard_axes: tuple[str, ...] = ("data",),
    max_hops: int = 0,
    beam_width: int = 1,
):
    """Build the jitted cross-partition search step for a device mesh.

    The returned fn takes shard-stacked index arrays (leading axis = number
    of shards = product of `shard_axes` sizes) and a replicated query batch;
    each device searches its shard and the results merge with one
    all_gather — the SDK's scatter/gather as collectives.
    """
    spec_sharded = P(shard_axes)
    spec_repl = P()

    def local_search(neighbors, codes, versions, live, vectors, doc_ids,
                     medoid, codebooks, queries):
        # leading shard axis is 1 inside shard_map; codebooks are PER SHARD
        # (each partition quantizes independently, as in the paper — using
        # one shard's schema for all shards silently wrecks distances)
        neighbors, codes, versions = neighbors[0], codes[0], versions[0]
        live, vectors, doc_ids, medoid = live[0], vectors[0], doc_ids[0], medoid[0]

        schema = pqmod.PQSchema(codebooks=codebooks[0], version=jnp.int32(0))
        luts = jax.vmap(lambda q: pqmod.adc_lut(schema, q, metric))(queries)[:, None]
        res = smod.batch_greedy_search(
            neighbors, codes, versions, live, luts, medoid,
            L=L, max_hops=max_hops, beam_width=beam_width,
        )
        lids, ldists = fmod.rerank(queries, res.beam_ids[:, : 2 * k], vectors,
                                   k=k, metric=metric)
        gdoc = jnp.where(lids >= 0, doc_ids[jnp.maximum(lids, 0)], -1)

        # gather partial results from every shard and merge
        all_ids = gdoc
        all_d = jnp.where(lids >= 0, ldists, INF)
        for ax in shard_axes:
            all_ids = jax.lax.all_gather(all_ids, ax, axis=0, tiled=False)
            all_d = jax.lax.all_gather(all_d, ax, axis=0, tiled=False)
            all_ids = all_ids.reshape((-1,) + all_ids.shape[2:]) if all_ids.ndim > 3 else all_ids
            all_d = all_d.reshape((-1,) + all_d.shape[2:]) if all_d.ndim > 3 else all_d
        # (S, B, k) -> (B, S*k) -> top-k
        S = all_d.shape[0]
        flat_d = jnp.moveaxis(all_d, 0, 1).reshape(queries.shape[0], S * k)
        flat_i = jnp.moveaxis(all_ids, 0, 1).reshape(queries.shape[0], S * k)
        neg, pos = jax.lax.top_k(-flat_d, k)
        out_ids = jnp.take_along_axis(flat_i, pos, axis=1)
        return out_ids, -neg

    shmapped = jax.shard_map(
        local_search,
        mesh=mesh,
        in_specs=(
            spec_sharded, spec_sharded, spec_sharded, spec_sharded,
            spec_sharded, spec_sharded, spec_sharded, spec_sharded, spec_repl,
        ),
        out_specs=(spec_repl, spec_repl),
        check_vma=False,
    )
    return jax.jit(shmapped)


# ---------------------------------------------------------------------------
# engine-facing SPMD fan-out (EngineConfig.dispatch_mode="spmd")
# ---------------------------------------------------------------------------

_SPMD_PROGRAMS: list = []


def spmd_jit_cache_size() -> int:
    """Compiled-signature count across every SpmdFanout program. Feeds
    ``serve.vector_engine.serving_jit_cache_size`` so the zero-recompile
    contract covers the spmd dispatch path too."""
    return sum(int(f._cache_size()) for f in _SPMD_PROGRAMS)


class SpmdFanout:
    """One jitted shard_map dispatch driving every partition's search.

    Where ``batched_fanout_search`` loops partitions on the host — one
    device call per partition — this stacks the live partitions' provider
    arrays along a leading axis, shards that axis over ``mesh``, and runs
    the bucketed graph search + full-precision re-rank for ALL partitions
    as one data-parallel program (inner `vmap` over the device-local
    partitions). The per-partition merge stays on the host, in original
    partition order, so results are **bit-identical** to the serial loop:
    LUTs come from the very same host jitted calls (`DiskANNIndex._luts`
    on the bucket-padded queries), and a vmapped while_loop carries each
    lane's state through `select` once finished — the same numerics the
    serial path runs, just batched one level higher.

    Caching discipline (the zero-recompile contract):
      * programs are cached per (L_eff, k, k', W, metric) closure — shape
        changes (bucket, partition count, V) hit jit's own cache, and
        every program registers in `spmd_jit_cache_size`;
      * the stacked arrays are cached per partition-set and invalidated
        by each partition's ``providers.write_count`` epoch (plus count /
        schema-count / medoid, which can move without a provider write).

    Partitions whose graph isn't built (or that are empty) fall back to
    the host ``search_batch`` — the same call the serial path makes — and
    their results interleave back at their original merge position. RU is
    metered on each partition's own meter/governor exactly like
    ``PhysicalPartition.search_batch`` (work-based counters, per-lane).
    """

    def __init__(self, mesh: jax.sharding.Mesh):
        self.mesh = mesh
        self.n_devices = int(np.prod(mesh.devices.shape))
        self._programs: dict = {}
        self._stacks: dict = {}

    # -- stacked provider arrays (cached per write epoch) ----------------
    def _stacked(self, prog_parts, P_pad: int) -> dict:
        key = tuple(id(p) for p in prog_parts) + (P_pad,)
        stamp = tuple(
            (p.providers.write_count, p.index.count, len(p.index.schemas),
             int(p.index.medoid))
            for p in prog_parts
        )
        hit = self._stacks.get(key)
        if hit is not None and hit[0] == stamp:
            return hit[1]
        # pad the partition axis to the mesh size by repeating partition 0
        # (its results are computed and discarded — never merged)
        all_p = list(prog_parts) + [prog_parts[0]] * (P_pad - len(prog_parts))
        sharding = NamedSharding(self.mesh, P(tuple(self.mesh.axis_names)))

        def place(rows: list) -> jax.Array:
            # each device is handed only its own partitions' rows, straight
            # from host memory: no partition is staged on another chip
            return jax.make_array_from_callback(
                (len(rows),) + np.shape(rows[0]), sharding,
                lambda idx: np.stack(rows[idx[0]]))

        pvs = [p.index.pv for p in all_p]
        arrs = dict(
            neighbors=place([pv.neighbors for pv in pvs]),
            codes=place([pv.codes for pv in pvs]),
            versions=place([pv.versions for pv in pvs]),
            live=place([pv.live for pv in pvs]),
            vectors=place([pv.vectors for pv in pvs]),
            # x64 is disabled: the doc-id table rides along as int32 and
            # widens back to int64 on the host
            slot_to_doc=place([p.index.slot_to_doc.astype(np.int32)
                               for p in all_p]),
            medoid=place([np.int32(p.index.medoid) for p in all_p]),
        )
        self._stacks[key] = (stamp, arrs)
        return arrs

    # -- the jitted program (cached per static closure) ------------------
    def _program(self, L_eff: int, k: int, kprime: int, W: int, metric: str):
        key = (L_eff, k, kprime, W, metric)
        fn = self._programs.get(key)
        if fn is not None:
            return fn
        axes = tuple(self.mesh.axis_names)
        sh, rep = P(axes), P()

        def local(neighbors, codes, versions, live, vectors, s2d, medoid,
                  luts, queries):
            # block shapes: (P_local, ...) per device; queries replicated
            def one_partition(nb, cd, vr, lv, vc, sd, md, lt):
                res = smod.batch_greedy_search(
                    nb, cd, vr, lv, lt, md, L=L_eff, beam_width=W
                )
                ids, dists = fmod.rerank(
                    queries, res.beam_ids[:, :kprime], vc, k=k, metric=metric
                )
                doc = jnp.where(ids >= 0, sd[jnp.maximum(ids, 0)], -1)
                # beam ids ride back out so the host can meter the paged
                # vector tier on the SAME candidate set the rerank read
                return (doc, dists, res.n_hops, res.n_exp, res.n_cmps,
                        res.beam_ids[:, :kprime])

            return jax.vmap(one_partition)(
                neighbors, codes, versions, live, vectors, s2d, medoid, luts
            )

        fn = jax.jit(jax.shard_map(
            local, mesh=self.mesh,
            in_specs=(sh,) * 8 + (rep,),
            out_specs=(sh,) * 6,
            check_vma=False,
        ))
        self._programs[key] = fn
        _SPMD_PROGRAMS.append(fn)
        return fn

    # -- the engine entry point ------------------------------------------
    def search(
        self,
        partitions,  # Sequence[PhysicalPartition]
        queries: np.ndarray,  # (B, D)
        k: int,
        L: Optional[int] = None,
        batch_buckets: tuple[int, ...] = smod.BATCH_BUCKETS,
        beam_width: Optional[int] = None,
        rerank_multiplier: float = fmod.QUANTIZED_LIST_MULTIPLIER,
        health=None,  # optional callable(partition) -> bool
    ) -> tuple[np.ndarray, np.ndarray, dict]:
        """Drop-in for ``batched_fanout_search``: same (ids, dists, info)."""
        parts = list(partitions)
        queries = np.asarray(queries, np.float32)
        B, k = len(queries), int(k)
        n = len(parts)
        failed: list[tuple[int, str]] = []
        down = set()
        for i, p in enumerate(parts):
            if health is not None and not health(p):
                down.add(i)
                failed.append((int(p.pid), "replica set down"))
        prog_idx = [i for i, p in enumerate(parts)
                    if i not in down
                    and p.index._graph_built and p.num_docs > 0]
        in_prog = set(prog_idx)

        ids_by: list = [None] * n
        d_by: list = [None] * n
        rus: list = [0.0] * n
        stats_by: list = [None] * n
        lat_by: list = [0.0] * n

        # host fallback — identical to the serial loop's search_batch call
        W = int(beam_width) if beam_width is not None else None
        for i, p in enumerate(parts):
            if i in in_prog or i in down:
                continue
            kw: dict = dict(pad_to_bucket=True, batch_buckets=batch_buckets)
            if W is not None:
                kw["beam_width"] = W
            try:
                ids, dists, ru, stats = p.search_batch(queries, k, L, **kw)
            except (CrashError, jax.errors.JaxRuntimeError):
                # an injected process kill, or a device fault (a compile
                # failure, HBM exhausted), is not a partition fault
                raise
            except Exception as e:  # noqa: BLE001 — degrade, don't collapse
                down.add(i)
                failed.append((int(p.pid), f"{type(e).__name__}: {e}"))
                continue
            ids_by[i], d_by[i], rus[i], stats_by[i] = ids, dists, ru, stats
            lat_by[i] = p.providers.meter.latency_ms(
                counters_for_latency(stats))

        if prog_idx:
            prog_parts = [parts[i] for i in prog_idx]
            idx0 = prog_parts[0].index
            W_eff = W or idx0.cfg.beam_width
            L_req = int(L or idx0.cfg.L_search)
            kprime = max(k, int(round(rerank_multiplier * k)))
            L_eff = max(L_req, kprime)
            bucket = smod.next_bucket(B, batch_buckets)
            padded = smod.pad_batch_np(queries, bucket)

            # per-partition LUTs from the SAME host jitted calls the serial
            # path makes (identical inputs → identical tables, bit for bit);
            # the V axis pads to the widest schema set by repeating the last
            # table — padded tables are never selected (versions < V_p)
            luts = [p.index._luts(padded) for p in prog_parts]
            V_max = max(lt.shape[1] for lt in luts)
            luts = [
                lt if lt.shape[1] == V_max else jnp.concatenate(
                    [lt, jnp.broadcast_to(
                        lt[:, -1:],
                        (lt.shape[0], V_max - lt.shape[1]) + lt.shape[2:])],
                    axis=1)
                for lt in luts
            ]
            P_n = len(prog_parts)
            P_pad = -(-P_n // self.n_devices) * self.n_devices
            luts_st = jnp.stack(list(luts) + [luts[0]] * (P_pad - P_n))
            arrs = self._stacked(prog_parts, P_pad)
            fn = self._program(L_eff, k, kprime, int(W_eff),
                               idx0.cfg.metric)
            doc, dist, hops, exps, cmps, beams = fn(
                arrs["neighbors"], arrs["codes"], arrs["versions"],
                arrs["live"], arrs["vectors"], arrs["slot_to_doc"],
                arrs["medoid"], luts_st, jnp.asarray(padded),
            )
            doc, dist = np.asarray(doc), np.asarray(dist)
            hops, exps, cmps = (np.asarray(hops), np.asarray(exps),
                                np.asarray(cmps))
            beams = np.asarray(beams)
            for j, i in enumerate(prog_idx):
                p = parts[i]
                st = QueryStats(
                    hops=float(hops[j, :B].mean()),
                    cmps=float(cmps[j, :B].mean()),
                    expansions=float(exps[j, :B].mean()),
                    full_reads=float(kprime),
                    plan="graph-spmd",
                )
                # paged-tier metering on the identical candidate pages the
                # serial path touches (same pin→touch→unpin sequence, so
                # cache state and hit/miss counts match bit for bit)
                pages = getattr(p.providers, "pages", None)
                if pages is not None:
                    th, tm, pinned = pages.touch(beams[j, :B], pin=True)
                    pages.unpin(pinned)
                    st.tier_hits = th / max(B, 1)
                    st.tier_misses = tm / max(B, 1)
                # meter exactly like PhysicalPartition.search_batch: the
                # work ran on the mesh, but it is THIS partition's work
                pv = p.providers
                pv.begin_op()
                pv.op += counters_for_ru(st, lanes=B)
                ru, _ = pv.end_op()
                p.governor.request(ru)
                ids_by[i] = doc[j, :B].astype(np.int64)
                d_by[i] = dist[j, :B]
                rus[i], stats_by[i] = ru, st
                lat_by[i] = pv.meter.latency_ms(counters_for_latency(st))

        ok = [i for i in range(n) if ids_by[i] is not None]
        if failed and not ok:
            raise AllPartitionsFailed(
                f"all {n} partitions failed: {failed}"
            )
        if ok:
            ids, dists = merge_topk([ids_by[i] for i in ok],
                                    [d_by[i] for i in ok], k)
        else:
            ids = np.full((B, k), -1, np.int64)
            dists = np.full((B, k), np.inf, np.float32)
        info = dict(
            partition_ids=[int(p.pid) for p in parts],
            ru_per_partition=[rus[i] for i in ok],
            ru_total=float(np.sum([rus[i] for i in ok])) if ok else 0.0,
            stats_per_partition=[stats_by[i] for i in ok],
            server_latencies_ms=[lat_by[i] for i in ok],
            service_latency_ms=(float(np.max([lat_by[i] for i in ok]))
                                if ok else 0.0),
            spmd=dict(partitions_in_program=len(prog_idx),
                      mesh_devices=self.n_devices),
            failed_partitions=failed,
            complete=not failed,
        )
        return ids, dists, info
