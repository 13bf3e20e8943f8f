"""Request records and measured spans on the host clock: the serving
engine's trace plane.

Every time here is measured on the host clock, in seconds since
``repro.core.spans.EPOCH_NS``; none is the engine's SimClock, which stays
the latency *model* (``ServeResponse.latency_ms``, ``EngineMetrics``).

  * ``repro.core.spans.span`` is the one timing primitive. A span lands
    in the profiler's trace, on the device trace's clock, while a
    profiler records; it adds to always-on per-name totals
    (``Tracer.span_totals``, exported as ``serve_span_*`` by
    ``MetricsRegistry.to_prometheus_text``); and while this tracer is
    enabled the engine collects it, with its parent, into the record
    being built.

  * ``Trace`` / ``Span`` — one record per query, page, micro-batch,
    write request or policy action. The root spans of a query tile
    [submit → answer stored], so their durations sum to the record's
    host-clock ``latency_ms`` (``validate_trace_record`` checks it for
    every 200 and 408):

      admission   point event at submit: the RU reservation
      queue       [submit → its micro-batch leaves the queue]
      batch_form  point event then: batch size, bucket, plan, beam width
      lane        [leaves the queue → answer stored]
      deadline    point event (408 only): the deadline expired queued

    Point events under ``lane``: ``hedge`` and ``retry`` (the replica
    lanes are simulated, so their durations are not time), a partition a
    degraded fan-out lost, a page's per-partition fetch (``partition``).
    The measured spans of one micro-batch — ``serve.dispatch`` and what it
    runs: ``search.partition``, ``search.prepare``, ``search.graph``,
    ``search.rerank``, ``search.finish``, ``search.merge``,
    ``store.upload``, ``serve.respond`` — are recorded once, in a record
    of kind ``batch``; each query record names it by ``batch_id``. A
    write request's record (kind ``ingest``) holds ``serve.ingest`` and
    its ``write.*`` and ``store.upload`` spans. A program span's stage is
    its layer, the part of its name before the dot.

  * ``Tracer`` — the factory the engine owns. ``enabled=False`` makes
    ``begin`` return ``None``; every emission site guards on that, so a
    disabled tracer allocates and retains nothing (the span totals stay
    on: they are per name, not per call).

  * ``FlightRecorder`` — a bounded ring of recent records plus a
    separate bounded ring for anomalous ones (throttles, faults, hedges,
    SLO violations), so a burst of healthy traffic never evicts the
    interesting evidence.

  * Exporters — ``Tracer.dump_jsonl`` writes the retained records as
    JSON lines (``launch/serve.py --trace-out``);
    ``validate_trace_record`` is the schema contract.
"""
from __future__ import annotations

import dataclasses
import json
from collections import deque
from typing import Any, Optional

from ..core.spans import EPOCH_NS, now_s, totals

PROGRAM_STAGES = ("serve", "search", "write", "store")
STAGES = ("admission", "queue", "batch_form", "lane", "deadline",
          "partition", "hedge", "retry", "policy") + PROGRAM_STAGES

TRACE_KINDS = ("query", "page", "batch", "ingest", "policy")

# anomaly tags the flight recorder always captures
ANOMALY_THROTTLE = "throttle"
ANOMALY_HEDGE = "hedge"
ANOMALY_FAULT = "fault_retry"
ANOMALY_SLO = "slo_violation"
ANOMALY_DEADLINE = "deadline_exceeded"
ANOMALY_DEGRADED = "degraded"


@dataclasses.dataclass
class Span:
    """One stage of one record, in host-clock seconds."""

    name: str
    stage: str
    t0_s: float
    t1_s: float
    parent: int = -1  # index into the owning trace's span list
    attrs: dict = dataclasses.field(default_factory=dict)

    @property
    def dur_ms(self) -> float:
        return (self.t1_s - self.t0_s) * 1000.0


@dataclasses.dataclass
class Trace:
    """One record: a flat span list with parent links."""

    trace_id: int
    kind: str  # query | page | batch | ingest | policy
    tenant: Any
    rid: int
    t0_s: float = 0.0
    t1_s: float = 0.0
    status: int = 0
    ru: float = 0.0
    latency_ms: float = 0.0
    batch_id: int = -1  # a query's micro-batch record, -1 for none
    anomalies: list = dataclasses.field(default_factory=list)
    spans: list = dataclasses.field(default_factory=list)

    def span(self, name: str, stage: str, t0_s: float, t1_s: float,
             parent: int = -1, **attrs) -> int:
        """Append a span; returns its index (usable as a parent link)."""
        self.spans.append(Span(name, stage, float(t0_s), float(t1_s),
                               parent, attrs))
        return len(self.spans) - 1

    def add_program_spans(self, records: list) -> None:
        """Append the spans ``repro.core.spans.collect`` recorded, parent
        links kept."""
        base = len(self.spans)
        for name, t0, t1, parent, meta in records:
            self.spans.append(Span(
                name, name.partition(".")[0], (t0 - EPOCH_NS) * 1e-9,
                (t1 - EPOCH_NS) * 1e-9,
                parent + base if parent >= 0 else -1, dict(meta)))

    def stage_totals(self) -> dict:
        """Root-span duration per stage (ms). Root spans tile [t0, t1], so
        their sum reconciles with latency_ms; children are excluded."""
        out: dict[str, float] = {}
        for s in self.spans:
            if s.parent == -1:
                out[s.stage] = out.get(s.stage, 0.0) + s.dur_ms
        return out

    def has_stage(self, stage: str) -> bool:
        return any(s.stage == stage for s in self.spans)

    def to_record(self) -> dict:
        """The JSON-lines export shape (see ``validate_trace_record``)."""
        return dict(
            trace_id=self.trace_id,
            kind=self.kind,
            tenant=str(self.tenant),
            rid=self.rid,
            status=self.status,
            t0_s=self.t0_s,
            t1_s=self.t1_s,
            latency_ms=self.latency_ms,
            ru=self.ru,
            batch_id=self.batch_id,
            anomalies=list(self.anomalies),
            spans=[
                dict(name=s.name, stage=s.stage, t0_s=s.t0_s, t1_s=s.t1_s,
                     dur_ms=s.dur_ms, parent=s.parent, attrs=s.attrs)
                for s in self.spans
            ],
        )


class FlightRecorder:
    """Bounded retention of recent + anomalous traces.

    ``ring`` holds the last ``capacity`` traces of *any* outcome;
    ``anomalous`` is a separate ring that only anomalous traces enter, so
    throttles / faults / hedges / SLO violations survive arbitrarily long
    bursts of healthy traffic (they fall out only to newer anomalies).

    Retained entries are live ``Trace`` objects — serialization to the
    record dict happens lazily in ``records()`` (the export/read path),
    never on the per-request hot path.
    """

    def __init__(self, capacity: int = 256):
        self.capacity = int(capacity)
        self.ring: deque = deque(maxlen=self.capacity)
        self.anomalous: deque = deque(maxlen=self.capacity)
        self.recorded = 0
        self.anomalies_seen = 0

    def record(self, tr: "Trace"):
        self.recorded += 1
        self.ring.append(tr)
        if tr.anomalies:
            self.anomalies_seen += 1
            self.anomalous.append(tr)

    def records(self) -> list:
        """Every retained record dict, dedup'd by trace id (ring ∪
        anomalous), serialized on demand."""
        seen = set()
        out = []
        for tr in list(self.ring) + list(self.anomalous):
            if tr.trace_id in seen:
                continue
            seen.add(tr.trace_id)
            out.append(tr.to_record())
        out.sort(key=lambda r: r["trace_id"])
        return out


class Tracer:
    """The engine's record factory, on the host clock.

    Zero-overhead when disabled: ``begin`` returns ``None`` and callers
    guard span emission on that — no allocation, no retention. When
    enabled, ``finish`` derives anomaly tags (throttle / hedge / fault /
    SLO) and hands the record to the flight recorder.
    """

    def __init__(self, enabled: bool = True, capacity: int = 256,
                 slo_ms: Optional[float] = None):
        self.enabled = bool(enabled)
        self.slo_ms = slo_ms
        self._capacity = int(capacity)
        self.recorder = FlightRecorder(capacity)
        self.started = 0
        self.finished = 0
        self._next_id = 0

    def reset(self):
        """Fresh recorder + counters (benchmark warmup boundary). The span
        totals are process-wide and monotone; they do not reset."""
        self.recorder = FlightRecorder(self._capacity)
        self.started = 0
        self.finished = 0
        self._next_id = 0

    def begin(self, kind: str, tenant: Any, rid: int) -> Optional[Trace]:
        if not self.enabled:
            return None
        self.started += 1
        tid = self._next_id
        self._next_id += 1
        return Trace(trace_id=tid, kind=kind, tenant=tenant, rid=rid,
                     t0_s=now_s())

    def finish(self, tr: Trace, status: int, ru: float, latency_ms: float,
               t0_s: Optional[float] = None, t1_s: Optional[float] = None,
               anomalies: tuple = ()):
        tr.status = int(status)
        tr.ru = float(ru)
        tr.latency_ms = float(latency_ms)
        if t0_s is not None:
            tr.t0_s = float(t0_s)
        tr.t1_s = float(t1_s) if t1_s is not None else now_s()
        tags = list(anomalies)
        if status == 429 and ANOMALY_THROTTLE not in tags:
            tags.append(ANOMALY_THROTTLE)
        if status == 408 and ANOMALY_DEADLINE not in tags:
            tags.append(ANOMALY_DEADLINE)
        stages = {s.stage for s in tr.spans}
        if "hedge" in stages:
            tags.append(ANOMALY_HEDGE)
        if "retry" in stages:
            tags.append(ANOMALY_FAULT)
        if (self.slo_ms is not None and tr.kind in ("query", "page")
                and latency_ms > self.slo_ms):
            tags.append(ANOMALY_SLO)
        tr.anomalies = tags
        self.finished += 1
        self.recorder.record(tr)

    def finish_measured(self, tr: Trace, records: list, ru: float):
        """Finish a ``batch`` or ``ingest`` record from the spans
        ``repro.core.spans.collect`` recorded: its root span is the
        record's interval and latency."""
        tr.add_program_spans(records)
        root = tr.spans[0]
        self.finish(tr, status=200, ru=ru, latency_ms=root.dur_ms,
                    t0_s=root.t0_s, t1_s=root.t1_s)

    @staticmethod
    def span_totals() -> dict:
        """Per program span name: (count, total ns, largest ns) since the
        process started (``repro.core.spans.totals``), whether or not this
        tracer is enabled."""
        return totals()

    # ------------------------------------------------------------------
    # exporters
    # ------------------------------------------------------------------
    def dump_jsonl(self, path) -> int:
        """Write every retained trace record as one JSON object per line.
        Returns the number of records written."""
        recs = self.recorder.records()
        with open(path, "w") as f:
            for rec in recs:
                f.write(json.dumps(rec) + "\n")
        return len(recs)

    def stats(self) -> dict:
        return dict(
            enabled=self.enabled,
            started=self.started,
            finished=self.finished,
            recorded=self.recorder.recorded,
            retained=len(self.recorder.ring),
            anomalies_seen=self.recorder.anomalies_seen,
            anomalies_retained=len(self.recorder.anomalous),
            slo_ms=self.slo_ms,
        )


# ---------------------------------------------------------------------------
# schema contract (the benchmark gate re-validates every exported line)
# ---------------------------------------------------------------------------

_REQUIRED = {
    "trace_id": int, "kind": str, "tenant": str, "rid": int, "status": int,
    "t0_s": (int, float), "t1_s": (int, float),
    "latency_ms": (int, float), "ru": (int, float), "batch_id": int,
    "anomalies": list, "spans": list,
}

_SPAN_REQUIRED = {
    "name": str, "stage": str, "t0_s": (int, float), "t1_s": (int, float),
    "dur_ms": (int, float), "parent": int, "attrs": dict,
}

_NEST_TOL_S = 1e-9


def validate_trace_record(rec: dict) -> None:
    """Raise ``ValueError`` unless ``rec`` is a well-formed trace record.

    Beyond structural checks (keys, types, stage taxonomy, parent links),
    every child span lies inside its parent's interval (spans are
    measured, so a child cannot outlast the block it ran in), and for a
    served (status 200) or deadline-abandoned (status 408) record the
    root spans tile the record's interval: their summed duration equals
    ``latency_ms`` within clock resolution, so stages can never silently
    leak time.
    """
    if not isinstance(rec, dict):
        raise ValueError("trace record must be a dict")
    for key, typ in _REQUIRED.items():
        if key not in rec:
            raise ValueError(f"trace record missing key {key!r}")
        if not isinstance(rec[key], typ):
            raise ValueError(f"trace record key {key!r} has wrong type "
                             f"{type(rec[key]).__name__}")
    if rec["kind"] not in TRACE_KINDS:
        raise ValueError(f"unknown trace kind {rec['kind']!r}")
    if rec["t1_s"] < rec["t0_s"]:
        raise ValueError("trace t1_s < t0_s")
    spans = rec["spans"]
    if rec["status"] in (200, 408) and not spans:
        raise ValueError("served trace has no spans")
    for i, s in enumerate(spans):
        if not isinstance(s, dict):
            raise ValueError(f"span {i} is not a dict")
        for key, typ in _SPAN_REQUIRED.items():
            if key not in s:
                raise ValueError(f"span {i} missing key {key!r}")
            if not isinstance(s[key], typ):
                raise ValueError(f"span {i} key {key!r} has wrong type")
        if s["stage"] not in STAGES:
            raise ValueError(f"span {i} stage {s['stage']!r} not in taxonomy")
        if s["t1_s"] < s["t0_s"]:
            raise ValueError(f"span {i} t1_s < t0_s")
        if not -1 <= s["parent"] < i:
            raise ValueError(f"span {i} parent {s['parent']} must point at "
                             f"an earlier span (or -1)")
        if s["parent"] >= 0:
            p = spans[s["parent"]]
            if (s["t0_s"] < p["t0_s"] - _NEST_TOL_S
                    or s["t1_s"] > p["t1_s"] + _NEST_TOL_S):
                raise ValueError(f"span {i} ({s['name']}) lies outside its "
                                 f"parent {p['name']}")
    if rec["status"] in (200, 408):
        root_ms = sum(s["dur_ms"] for s in spans if s["parent"] == -1)
        tol = 1e-6 + 1e-9 * abs(rec["latency_ms"])
        if abs(root_ms - rec["latency_ms"]) > tol:
            raise ValueError(
                f"stage decomposition leaks time: root spans sum to "
                f"{root_ms:.9f} ms but latency_ms is "
                f"{rec['latency_ms']:.9f} ms"
            )
