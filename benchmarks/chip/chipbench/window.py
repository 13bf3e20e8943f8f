"""The measured window: an open loop of queries, and optionally a write
backlog, driven through the service's public entry points.

How the loop decides when to call the engine (the engine's own batching
timer runs on its simulated clock and is never consulted):

* every query has a due time on the host clock (``time.perf_counter``);
* whenever queries are due, the loop hands the engine the oldest of them,
  at most ``max_batch``, through ``engine.submit_query`` and calls
  ``engine.pump(force=True)``: the engine dispatches them as one
  micro-batch and then applies one queued write request (its
  interleave);
  the loop then collects each answer with ``engine.pop_response``;
* when no query is due and writes are queued, ``engine.pump()`` applies
  one write request (the engine's idle ingest);
* otherwise it sleeps until the next due time.

At the window's two ends, and at the traced span's, the loop also takes
the program's counters and span totals (``program_state``); their
differences are what per-layer metrics read as ``counters`` and
``spans`` of the window and of the traced span.

A query's latency runs from its due time to the moment its answer, ids on
the host, has been popped: time it spent due but not yet handed over (the
engine was busy) counts. The loop records, for each query, which corpus
positions were live when its micro-batch was dispatched, so that the
reference can be computed over exactly those.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import numpy as np

from .deploy import WriteStream

BACKLOG_OPS = 1  # the write stream keeps this many ops queued


def program_state(eng) -> tuple[dict, dict]:
    """The program's counters and span totals now: (counters, spans).

    ``counters`` are the engine's numeric counters (``EngineMetrics``:
    ``answers``, ``answer_hold_ns``, ``ingest_ops``, ``hops_*``, ...) and
    the partitions' ``uploads``, ``upload_bytes`` and ``repair_rows``,
    summed as ``engine.snapshot()`` sums them (that call also builds
    read-outs the window has no use for); ``spans`` maps each span name of
    ``repro.core.spans.totals()`` to (count, total ns). Every one of them
    only grows, so the difference of two states is exact."""
    from repro.core.spans import totals

    counters = {k: v for k, v in vars(eng.metrics).items()
                if type(v) in (int, float)}
    parts = eng.collection.partitions
    counters["uploads"] = sum(p.providers.uploads for p in parts)
    counters["upload_bytes"] = sum(p.providers.upload_bytes for p in parts)
    counters["repair_rows"] = sum(p.index.repair_rows for p in parts)
    return counters, {n: (c, t) for n, (c, t, _) in totals().items()}


def state_delta(before: tuple[dict, dict], after: tuple[dict, dict]):
    """``after`` - ``before`` of two ``program_state``s: (counters,
    spans), with a name that first appears in ``after`` counted from 0."""
    (c0, s0), (c1, s1) = before, after
    counters = {k: v - c0.get(k, 0) for k, v in c1.items()}
    spans = {n: (c - s0.get(n, (0, 0))[0], t - s0.get(n, (0, 0))[1])
             for n, (c, t) in s1.items()}
    return counters, spans


def annotate(name: str):
    """A host span in the profiler's trace, named after the layer the
    harness is calling into."""
    import jax

    return jax.profiler.TraceAnnotation(name)


@dataclasses.dataclass
class Profile:
    """A profiler trace of the window's last ``seconds``: a long trace
    loses events, so only this span is traced, and the trace stops once
    the window has closed (stopping it takes long, and must not stall the
    loop). The counts inside the span are what per-layer metrics divide
    the span's device time by, or average over."""
    offset: float
    seconds: float
    log_dir: str
    started: bool = False
    ended: bool = False
    batches: int = 0  # micro-batches dispatched in the span
    answered: int = 0  # queries answered in the span
    sent: tuple = (0, 0)  # the queries handed over in the span: [lo, hi)
    counters: dict = dataclasses.field(default_factory=dict)
    spans: dict = dataclasses.field(default_factory=dict)
    _ann: object = None
    _at_start: dict = dataclasses.field(default_factory=dict)
    _state: tuple = ({}, {})

    def begin(self, counts: dict, state: tuple) -> None:
        self.start_trace()
        self._ann = annotate(TRACED)
        self._ann.__enter__()
        self._at_start = dict(counts)
        self._state = state
        self.started = True

    def end(self, counts: dict, state: tuple) -> None:
        self._ann.__exit__(None, None, None)
        self.stop_trace()
        self.counters, self.spans = state_delta(self._state, state)
        d = {key: counts[key] - self._at_start[key] for key in counts}
        self.batches, self.answered = d["batches"], d["answered"]
        self.sent = (self._at_start["sent"], counts["sent"])
        self.ended = True

    def start_trace(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1  # the harness's annotations and no more
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)

    def stop_trace(self) -> None:
        import jax

        jax.profiler.stop_trace()


TRACED = "bench.traced"


@dataclasses.dataclass
class WindowResult:
    due: np.ndarray  # (n,) host-clock due times
    sent: np.ndarray  # (n,) when the query was handed to the engine
    done: np.ndarray  # (n,) when its answer was popped
    ids: np.ndarray  # (n, k) int64 doc ids, -1 where missing
    dists: np.ndarray  # (n, k) float32
    status: np.ndarray  # (n,) int, 0 = never answered
    complete: np.ndarray  # (n,) bool
    batch: np.ndarray  # (n,) micro-batch size the answer came in
    live_lo: np.ndarray  # (n,) first live corpus position at dispatch
    live_hi: np.ndarray  # (n,) one past the last
    t0: float  # window start (first due time is at or after it)
    t_end: float  # t0 + seconds
    writes_end: float  # first loop turn at or after t_end
    ops_at_start: tuple[int, int]  # (upserts, deletes) applied at t0
    ops_at_end: tuple[int, int]  # ... at writes_end
    wake_late: np.ndarray  # seconds each sleep overshot its due time
    batches: int  # micro-batches dispatched in the window
    counters: dict  # the program's counters over the loop (state_delta)
    spans: dict  # its span totals over the loop: name -> (count, ns)
    state_s: np.ndarray  # seconds each program_state call took

    @property
    def latency_ms(self) -> np.ndarray:
        return (self.done - self.due) * 1e3

    @property
    def write_ops(self) -> int:
        """Write ops applied from the window's start to its end."""
        return sum(self.ops_at_end) - sum(self.ops_at_start)


def drive(svc, queries: np.ndarray, offsets: np.ndarray, seconds: float,
          k: int, L: int, n_live: int, stream: Optional[WriteStream] = None,
          profile: Optional[Profile] = None,
          predicate=None) -> WindowResult:
    """Run the window. ``offsets`` are due times in seconds after the
    window's start, ``t0``, taken as the loop begins; ``n_live`` is the
    number of loaded positions when no write stream runs; the write stream
    is topped up whenever fewer than ``BACKLOG_OPS`` ops are queued;
    ``profile`` traces the end of the window; ``predicate`` is every
    query's filter."""
    eng = svc.engine
    max_batch = eng.cfg.max_batch
    n = len(offsets)
    state_s: list[float] = []

    def state() -> tuple[dict, dict]:
        t = time.perf_counter()
        out = program_state(eng)
        state_s.append(time.perf_counter() - t)
        return out

    at_start = state()
    window = annotate("bench.window")
    window.__enter__()
    t0 = time.perf_counter()
    due = t0 + np.asarray(offsets, np.float64)
    t_end = t0 + seconds
    sent = np.zeros(n)
    done = np.zeros(n)
    ids = np.full((n, k), -1, np.int64)
    dists = np.full((n, k), np.inf, np.float32)
    status = np.zeros(n, np.int32)
    complete = np.zeros(n, bool)
    batch = np.zeros(n, np.int32)
    live_lo = np.zeros(n, np.int64)
    live_hi = np.full(n, n_live, np.int64)
    wake_late: list[float] = []
    ops_start = stream.applied()[:2] if stream else (0, 0)
    ops_end = None
    writes_end = t_end
    batches = 0
    i = 0
    answered = 0

    def counts() -> dict:
        return {"batches": batches, "answered": answered, "sent": i}

    try:
        while True:
            now = time.perf_counter()
            if (profile is not None and not profile.started
                    and now >= t0 + profile.offset):
                profile.begin(counts(), state())
            writing = stream is not None and now < t_end
            if ops_end is None and now >= t_end:
                ops_end = stream.applied()[:2] if stream else (0, 0)
                writes_end = now
            if writing:
                with annotate("service.write_submit"):
                    while eng.ingest_backlog < BACKLOG_OPS:
                        if not stream.submit_next():
                            break
            if i < n and due[i] <= now:
                j = i + 1
                while j < n and j - i < max_batch and due[j] <= now:
                    j += 1
                if stream is not None:
                    live_lo[i:j], live_hi[i:j] = stream.live_range()
                with annotate("engine.submit"):
                    rids = [eng.submit_query(queries[m], k=k, L=L,
                                             predicate=predicate)
                            for m in range(i, j)]
                sent[i:j] = time.perf_counter()
                with annotate("engine.pump"):
                    eng.pump(force=True)
                batches += 1
                with annotate("engine.pop"):
                    for m, rid in zip(range(i, j), rids):
                        r = eng.pop_response(rid)
                        if r is None:
                            continue
                        status[m] = r.status
                        complete[m] = r.complete
                        batch[m] = r.batch_size
                        if r.ids is not None:
                            ids[m] = r.ids[:k]
                            dists[m] = r.dists[:k]
                        done[m] = time.perf_counter()
                        answered += 1
                i = j
            elif writing and eng.ingest_backlog:
                with annotate("engine.pump"):
                    eng.pump()
            elif i < n:
                with annotate("harness.wait"):
                    time.sleep(max(0.0, due[i] - now))
                wake_late.append(time.perf_counter() - due[i])
            elif now >= t_end:
                break
            else:
                with annotate("harness.wait"):
                    time.sleep(min(0.001, t_end - now))
    finally:
        at_end = state()
        if profile is not None and profile.started:
            profile.end(counts(), at_end)
        window.__exit__(None, None, None)
    counters, spans = state_delta(at_start, at_end)
    return WindowResult(
        due=due, sent=sent, done=done, ids=ids, dists=dists, status=status,
        complete=complete, batch=batch, live_lo=live_lo, live_hi=live_hi,
        t0=t0, t_end=t_end, writes_end=writes_end,
        ops_at_start=tuple(ops_start), ops_at_end=tuple(ops_end),
        wake_late=np.asarray(wake_late), batches=batches,
        counters=counters, spans=spans, state_s=np.asarray(state_s),
    )


def serve_batches(svc, queries: np.ndarray, k: int, L: int,
                  exact: bool = False, predicate=None):
    """Answer ``queries`` through the same entry points, ``max_batch`` at a
    time (warm-up and read-back); ``exact`` asks for the service's exact
    plan (``VectorDistance(..., true)``); ``predicate`` filters every
    query. Returns (ids, statuses, batch sizes)."""
    eng = svc.engine
    out_ids = np.full((len(queries), k), -1, np.int64)
    st = np.zeros(len(queries), np.int32)
    bs = np.zeros(len(queries), np.int32)
    mb = eng.cfg.max_batch
    for lo in range(0, len(queries), mb):
        rids = [eng.submit_query(q, k=k, L=L, exact=exact,
                                 predicate=predicate)
                for q in queries[lo:lo + mb]]
        eng.pump(force=True)
        for m, rid in zip(range(lo, lo + len(rids)), rids):
            r = eng.pop_response(rid)
            if r is None:
                continue
            st[m], bs[m] = r.status, r.batch_size
            if r.ids is not None:
                out_ids[m] = r.ids[:k]
    return out_ids, st, bs
