"""One run of one cell: set-up, warm-up, the measured window, the check,
and the result line.

``run`` is what ``run.py`` calls once it has found the chips; the tests
call it on the CPU at a small size.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import math
import shutil
import sys
import tempfile
import time
from typing import Callable, Optional

import numpy as np

from . import check as chk
from . import data as dat
from .deploy import WriteStream, build_service, documents
from .spec import Cell, load_peaks, metric_reader
from .trace import find_xplane, read_xplane, summarize
from .window import Profile, WindowResult, drive, serve_batches

WARM_REPEATS = 2  # each batch bucket is served this many times in set-up
TRACE_S = 2.0  # --trace 1 traces this much of the end of the window
READBACK = 64  # acknowledged upserts (and deleted docs) read back per run


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


class CompileCounter:
    """Programs built (backend compiles and persistent-cache loads) and
    functions traced, from ``jax.monitoring``."""

    def __init__(self):
        from jax import monitoring

        self.compiles = self.cache_hits = self.traces = 0
        self.traced = collections.Counter()  # function name -> traces
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
        elif event == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1
            self.traced[_kw.get("fun_name", "?")] += 1

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> tuple[int, int, int]:
        return self.compiles, self.cache_hits, self.traces


class GcPauses:
    """Collections of the oldest generation (those that scan every live
    object) and their pauses, from ``gc.callbacks``."""

    def __init__(self):
        self.pauses: list[float] = []
        self._t = 0.0
        gc.callbacks.append(self._cb)

    def _cb(self, phase: str, info: dict) -> None:
        if info.get("generation") != 2:
            return
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses.append(time.perf_counter() - self._t)

    def close(self) -> None:
        gc.callbacks.remove(self._cb)


def percentile(values: np.ndarray, p: float) -> float:
    """Nearest-rank percentile: the smallest sample with at least ``p``%
    of the samples at or below it. Exact, no interpolation."""
    v = np.sort(np.asarray(values, np.float64))
    if not len(v):
        return math.nan
    return float(v[max(0, math.ceil(p / 100.0 * len(v)) - 1)])


def write_pattern(writes: dict) -> tuple[tuple[str, int], ...]:
    """One upsert of ``upsert_docs`` docs, then deletes of ``delete_docs``
    docs each until as many docs are deleted, so the live count stays put;
    without ``delete_docs`` (or with 0), upserts alone: a bulk ingest."""
    up, de = int(writes["upsert_docs"]), int(writes.get("delete_docs") or 0)
    if de <= 0:
        return (("upsert", up),)
    if up % de:
        raise ValueError(f"upsert_docs {up} is not a multiple of "
                         f"delete_docs {de}: the live count would drift")
    return (("upsert", up),) + (("delete", de),) * (up // de)


def query_filter(cell: Cell, corpus: dat.Corpus):
    """The traffic's ``filter``, such as ``{"category": 3}``: every query
    asks only for docs whose ``category`` property is 3 (docs carry
    ``id % doc_categories``, so the selectivity is 1 / doc_categories).
    Returns (the program's predicate, the positions it admits), or
    (None, None) for a mix without one."""
    flt = cell.traffic.get("filter")
    if not flt:
        return None, None
    if set(flt) != {"category"}:
        raise ValueError(f"filter {flt}: only {{'category': value}} is known")
    from repro.serve import F

    cats = int(cell.traffic["doc_categories"])
    v = int(flt["category"])
    if not 0 <= v < cats:
        raise ValueError(f"filter category {v} outside 0..{cats - 1}")
    return F.eq("category", v), corpus.ids % cats == v


@dataclasses.dataclass
class Setup:
    svc: object
    corpus: dat.Corpus
    stream: Optional[WriteStream]
    k: int
    L: int
    load_s: float
    predicate: object = None  # the queries' filter, None for none
    eligible: Optional[np.ndarray] = None  # the positions it admits


def set_up(cell: Cell, seed: int) -> Setup:
    """Data from the seed, the deployment, the load, and the warm-up of the
    shapes this cell's traffic uses."""
    cfg, tr = cell.config, cell.traffic
    writes = tr.get("writes")
    n_load = int(cfg["docs_loaded"])
    corpus = dat.make_corpus(seed, cfg["dim"], cfg["data"], n_load,
                             int(writes["stream_docs"]) if writes else 0,
                             cluster_order=tr["corpus_order"] == "clusters")
    svc = build_service(cfg)
    cats = int(tr.get("doc_categories", 0))
    t = time.perf_counter()
    svc.upsert(documents(corpus.ids[:n_load], cats), corpus.vectors[:n_load])
    load_s = time.perf_counter() - t
    log(f"load: {n_load} docs in {load_s:.3f}s "
        f"({n_load / load_s:.1f} docs/s)")
    stream = None
    if writes:
        stream = WriteStream(svc, corpus, write_pattern(writes), cats)
        # one turn of the pattern in set-up compiles the write path
        for _ in stream.pattern:
            stream.submit_next()
        svc.engine.flush_ingest()
        # the medoid is recomputed when a delete hits it: compile that
        # program now, not inside the window
        for p in svc.collection.partitions:
            p.index.recompute_medoid()
    k, L = int(cfg["graph"]["k"]), int(cfg["graph"]["L_search"])
    predicate, eligible = query_filter(cell, corpus)
    buckets = [b for b in svc.engine.cfg.batch_buckets
               if b <= svc.engine.cfg.max_batch]
    warm = dat.make_queries(seed, cfg["dim"], cfg["data"],
                            sum(buckets), stream=4)
    for _ in range(WARM_REPEATS):
        lo = 0
        for b in buckets:
            serve_batches(svc, warm[lo:lo + b], k, L, predicate=predicate)
            lo += b
    return Setup(svc, corpus, stream, k, L, load_s, predicate, eligible)


def window_queries(cell: Cell, setup: Setup, seed: int, seconds: float):
    tr = cell.traffic
    offsets = dat.arrival_offsets(seed, float(tr["query_rate_qps"]), seconds,
                                  tr.get("arrivals"))
    clusters = None
    if tr.get("queries_from") == "live_clusters":
        keep = int(float(tr["query_keep_from"]) * setup.corpus.n_load)
        clusters = dat.live_query_clusters(setup.corpus, keep)
    q = dat.make_queries(seed, cell.config["dim"], cell.config["data"],
                         len(offsets), clusters=clusters)
    return q, offsets


def read_back(cell: Cell, setup: Setup, seed: int):
    """After the window: a sample of acknowledged upserts and of deleted
    docs, each queried by its own vector through the same entry points
    with the service's exact plan. The guarantee is that the write reached
    the partition (and the delete its tombstone); whether the graph finds
    a doc is recall, which the window's answers measure."""
    svc, corpus, stream = setup.svc, setup.corpus, setup.stream
    svc.engine.flush_ingest()
    lo, hi = stream.live_range() if stream else (0, corpus.n_load)
    n = READBACK
    rng = dat._rng(seed, 5)
    if stream is not None and hi > corpus.n_load:
        fresh = np.arange(max(lo, corpus.n_load), hi)
        old = np.arange(lo, min(hi, corpus.n_load))
        acked = np.concatenate([
            rng.choice(fresh, min(n // 2, len(fresh)), replace=False),
            rng.choice(old, min(n - n // 2, len(old)), replace=False)])
    else:
        acked = rng.choice(np.arange(lo, hi), min(n, hi - lo), replace=False)
    gone = rng.choice(np.arange(0, lo), min(n, lo), replace=False)
    probe = np.concatenate([acked, gone]).astype(np.int64)
    found, _, _ = serve_batches(svc, corpus.vectors[probe], setup.k, setup.L,
                                exact=True)
    pos = positions(corpus, found)
    return acked, pos[:len(acked)], gone, pos[len(acked):]


def positions(corpus: dat.Corpus, ids: np.ndarray) -> np.ndarray:
    """Doc ids → corpus positions (a doc's id is its position), -1 for an
    id the corpus does not hold."""
    ids = np.asarray(ids, np.int64)
    return np.where((ids >= 0) & (ids < len(corpus.ids)), ids, -1)


@dataclasses.dataclass
class RunView:
    """What a per-layer metric reader may look at: the whole window's
    results, and the traced span's device trace and counts
    (``traced.batches``, ``traced.answered``, and the queries handed over
    in it, ``window.*[slice(*traced.sent)]``).

    The program's own counters and spans, by name, over the window
    (``window.counters``, ``window.spans``) and over the traced span
    (``traced.counters``, ``traced.spans``): counter name -> increase,
    span name -> (count, total ns), from ``window.program_state``; and
    its spans on the device trace's clock, ``trace.spans(name)`` and
    ``trace.span_s(prefixes)``. A metric that reads any of them is a
    file in ``metrics/`` and nothing else."""
    cell: Cell
    window: WindowResult
    trace: Optional[object]  # trace.TraceSummary of the traced span
    traced: Optional[Profile]
    peaks: dict


def layer_metrics(cell: Cell, view: RunView) -> dict:
    """The cell's per-layer metrics, each from its reader; a reader that
    finds nothing to read leaves its metric out."""
    out = {}
    for m in cell.per_layer:
        v = metric_reader(m.name)(view)
        if v is not None:
            out[m.name] = {"value": v, "unit": m.unit}
    return out


def end_to_end(cell: Cell, w: WindowResult, check: chk.Check,
               setup_s: float) -> dict:
    lat = np.where((w.status == 200) & w.complete, w.latency_ms, np.inf)
    values = {
        "query_p50_ms": lambda: percentile(lat, 50),
        "query_p95_ms": lambda: percentile(lat, 95),
        "recall_at_10": lambda: check.recall,
        "write_docs_per_s": lambda: w.write_ops / (w.writes_end - w.t0),
        "setup_s": lambda: setup_s,
    }
    out = {}
    for m in cell.end_to_end:
        if m.name not in values:
            raise ValueError(f"end-to-end metric {m.name!r} has no code")
        out[m.name] = {"value": values[m.name](), "unit": m.unit}
    return out


@dataclasses.dataclass
class Outcome:
    """Everything one run measured, before the check."""
    cell: Cell
    corpus: dat.Corpus
    queries: np.ndarray
    window: WindowResult
    readback: tuple
    setup_s: float
    peak_bytes: int
    trace: Optional[object]  # trace.TraceSummary
    profile: Optional[Profile]
    k: int
    eligible: Optional[np.ndarray]  # positions the queries' filter admits


def execute(cell: Cell, seed: int, seconds: float, trace: bool,
            t_process: float, counter: CompileCounter,
            after: Optional[Callable] = None) -> Outcome:
    """Set-up, the measured window (traced when ``trace``), and the
    read-back; then ``after(setup, queries)`` where given (the readings of
    ``control.py``). The program's state is dropped before this returns."""
    import jax

    devices = jax.devices()
    cfg = cell.config
    log(f"cell {cell.name}: config {cell.config_name}, traffic "
        f"{cell.traffic_name}, seed {seed}, {seconds}s, trace {int(trace)}")
    log(f"cut: reduced {cfg.get('reduced')}; assumed {cfg.get('assumed')}")
    setup = set_up(cell, seed)
    queries, offsets = window_queries(cell, setup, seed, seconds)
    profile = None
    if trace:
        span = min(TRACE_S, seconds / 2)
        profile = Profile(seconds - span, span,
                          tempfile.mkdtemp(prefix="chipbench-trace-"))
    # the deployment's objects (docs, terms, pages: ~2M at 30,000 docs)
    # live as long as the process, as in a server that has loaded its
    # partition: freeze them so that a full collection in the window does
    # not rescan them; the window's own garbage is still collected
    gc.collect()
    gc.freeze()
    pauses = GcPauses()
    c0 = counter.snapshot()
    traced0 = collections.Counter(counter.traced)
    w = drive(setup.svc, queries, offsets, seconds, setup.k, setup.L,
              n_live=setup.corpus.n_load, stream=setup.stream,
              profile=profile, predicate=setup.predicate)
    setup_s = w.t0 - t_process
    c1 = counter.snapshot()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices[:cell.chips])
    summary = None
    if trace:
        t_read = time.perf_counter()
        xplane = find_xplane(profile.log_dir)
        events = read_xplane(xplane)
        shutil.rmtree(profile.log_dir, ignore_errors=True)
        summary = summarize(events)
        log(f"trace: {profile.seconds:.1f}s of the window from "
            f"{profile.offset:.1f}s, {len(events)} events read and reduced "
            f"in {time.perf_counter() - t_read:.3f}s; {profile.batches} "
            f"micro-batches, {profile.counters.get('ingest_ops', 0)} docs "
            "written in it")
    log(f"set-up: {setup_s:.3f}s (load {setup.load_s:.3f}s)")
    log(f"window: {len(offsets)} queries due in {seconds}s, "
        f"{w.batches} micro-batches, {w.write_ops} write ops applied")
    log(f"compiles in window: {c1[0] - c0[0]} backend compiles, "
        f"{c1[1] - c0[1]} cache loads (expected 0); {c1[2] - c0[2]} "
        f"functions traced: {(counter.traced - traced0).most_common(6)}")
    late = w.wake_late * 1e3
    sent_lag = (w.sent - w.due) * 1e3
    log(f"generator: {len(late)} sleeps, wake-up lateness p50 "
        f"{percentile(late, 50):.3f} ms, p99 {percentile(late, 99):.3f} ms, "
        f"max {late.max(initial=0):.3f} ms; due-to-sent p50 "
        f"{percentile(sent_lag, 50):.3f} ms, max "
        f"{sent_lag.max(initial=0):.3f} ms")
    pauses.close()
    log(f"full collections in window: {len(pauses.pauses)}, longest "
        f"{max(pauses.pauses, default=0.0) * 1e3:.3f} ms "
        f"({gc.get_freeze_count()} objects frozen after set-up)")
    log(f"program state: {len(w.state_s)} snapshots, longest "
        f"{w.state_s.max(initial=0) * 1e6:.1f} us; over the window "
        f"{w.counters.get('answers', 0)} answers, "
        f"{w.counters.get('ingest_ops', 0)} docs written, "
        f"{w.counters.get('upload_bytes', 0)} bytes uploaded, "
        f"{w.counters.get('repair_rows', 0)} rows repaired")
    top = sorted(w.spans.items(), key=lambda kv: -kv[1][1])[:8]
    log("program spans over the window (count, total ms): " + ", ".join(
        f"{n} {c} {t * 1e-6:.1f}" for n, (c, t) in top))
    log(f"peak_bytes_in_use: {peak}")
    readback = read_back(cell, setup, seed)
    if after is not None:
        after(setup, queries)
    gc.unfreeze()
    return Outcome(cell, setup.corpus, queries, w, readback, setup_s,
                   int(peak), summary, profile, setup.k, setup.eligible)


def compare(o: Outcome) -> chk.Check:
    t = time.perf_counter()
    w = o.window
    check = chk.compare(o.queries, o.corpus.vectors,
                        positions(o.corpus, w.ids), w.dists, w.status,
                        w.complete, w.live_lo, w.live_hi, o.k,
                        o.cell.limits["recall_miss"], readback=o.readback,
                        eligible=o.eligible)
    log(f"reference: {len(o.queries)} queries in "
        f"{time.perf_counter() - t:.3f}s; recall@{o.k} {check.recall:.6f}")
    return check


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_process: float, counter: Optional[CompileCounter] = None) -> dict:
    """One run: the result line's object (``check`` last)."""
    import jax

    devices = jax.devices()
    peaks = load_peaks(devices[0].device_kind) if trace else {}
    o = execute(cell, seed, seconds, trace, t_process,
                counter or CompileCounter())
    check = compare(o)
    if trace:
        metrics = layer_metrics(cell, RunView(cell, o.window, o.trace,
                                              o.profile, peaks))
    else:
        metrics = end_to_end(cell, o.window, check, o.setup_s)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": o.peak_bytes}
    result = {"correct": check.correct, "attempted": len(o.queries),
              "failed": int(check.numbers["unanswered"][0]),
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = o.trace.busy_s
        device["window_s"] = o.trace.window_s
        result["breakdown"] = {"device_ops": o.trace.device_ops(),
                               "idle_gaps": o.trace.idle_gaps()}
    for name, (v, lim) in check.numbers.items():
        log(f"check {name}: {v} (limit {lim})")
    result["check"] = {name: {"value": v, "limit": lim}
                       for name, (v, lim) in check.numbers.items()}
    return result
