"""Reduction of a profiler trace to device busy time, per-program device
time and idle gaps attributed to what the harness was doing.

The trace is read with ``jax.profiler.ProfileData`` into plain events
(plane, line, name, start, duration in ns). Device planes are those named
``/device:<accelerator>:<n>``; on them the ``XLA Ops`` line holds the
operations that ran and the ``XLA Modules`` line the compiled programs
(``jit_<function name>``). Host spans are the harness's
``TraceAnnotation``s; ``bench.traced`` marks the traced span of the
measured window.
"""
from __future__ import annotations

import dataclasses
import gzip
import itertools
import json
import re
from pathlib import Path
from typing import Iterable, Optional

WINDOW = "bench.traced"
HOST_PREFIXES = ("bench.", "engine.", "service.", "harness.")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and not name.startswith("/device:CPU")


def read_xplane(path: Path) -> list[Event]:
    """The events of one ``.xplane.pb`` that this reduction uses: device
    ops and modules, and the harness's host spans."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    out = []
    for plane in pd.planes:
        dev = is_device_plane(plane.name)
        for line in plane.lines:
            if dev and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for e in line.events:
                if not dev and not e.name.startswith(HOST_PREFIXES):
                    continue
                out.append(Event(plane.name, line.name, e.name,
                                 float(e.start_ns), float(e.duration_ns)))
    return out


def find_xplane(log_dir: Path) -> Path:
    found = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load_events(path: Path) -> list[Event]:
    with gzip.open(path, "rt") as f:
        return [Event(*row) for row in json.load(f)]


def union_length(intervals: list[tuple[float, float]]) -> float:
    return sum(e - s for s, e in _merge(intervals))


def _merge(intervals):
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def module_key(name: str) -> str:
    """``jit_rerank(123)`` → ``jit_rerank``."""
    return re.sub(r"\(\d+\)$", "", name).strip()


def _matches(name: str, fn: str) -> bool:
    return re.search(rf"(^|[^A-Za-z0-9_])jit_{re.escape(fn)}($|[^A-Za-z0-9_])",
                     module_key(name)) is not None


@dataclasses.dataclass
class TraceSummary:
    window_ns: tuple[float, float]
    devices: list[str]
    busy_ns: dict  # device plane -> busy ns inside the window
    modules: list[Event]  # device module events inside the window
    ops: list[Event]
    host: list[Event]

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the devices that ran anything."""
        if not self.busy_ns:
            return 0.0
        return sum(self.busy_ns.values()) / len(self.busy_ns) * 1e-9

    def program_s(self, functions: Iterable[str]) -> Optional[float]:
        """Device seconds of the programs compiled from ``functions`` (jit
        function names) that started in the span; None when none did."""
        fns = tuple(functions)
        lo, hi = self.window_ns
        hits = [e.dur_ns for e in self.modules if lo <= e.start_ns < hi
                and any(_matches(e.name, f) for f in fns)]
        if not hits:
            return None
        return sum(hits) * 1e-9 / max(len(self.busy_ns), 1)

    def device_ops(self, top: int = 10) -> list[list]:
        """The programs (or, without a module line, the operations) that
        took most device time in the window: [[name, seconds], ...]."""
        src = self.modules or self.ops
        agg: dict[str, float] = {}
        for e in src:
            key = module_key(e.name)
            agg[key] = agg.get(key, 0.0) + e.dur_ns * 1e-9
        return [[k, v] for k, v in
                sorted(agg.items(), key=lambda kv: -kv[1])[:top]]

    def idle_gaps(self, top: int = 10) -> list[list]:
        """Device idle time in the span, by cause, largest first: [[name,
        seconds], ...]. Idle time inside a running program (between its
        operations) is ``device.in_program``; idle time outside any
        program goes to the harness span open on the host then
        (``harness.loop`` where none was)."""
        lo, hi = self.window_ns
        ops = _merge(_clip([(e.start_ns, e.end_ns)
                            for e in self.ops or self.modules], lo, hi))
        progs = _merge(_clip([(e.start_ns, e.end_ns) for e in self.modules],
                             lo, hi))
        gaps = _complement(ops, lo, hi)
        outside = _subtract(gaps, progs)
        idle = sum(e - s for s, e in gaps)
        agg = {"device.in_program": idle - sum(e - s for s, e in outside)}
        # the harness's spans follow one another without nesting, so one
        # pointer walks both sorted lists
        spans = sorted((e.start_ns, e.end_ns, e.name) for e in self.host
                       if e.name != WINDOW)
        j = 0
        for gs, ge in outside:
            while j < len(spans) and spans[j][1] <= gs:
                j += 1
            covered = 0.0
            for s, e, name in itertools.islice(spans, j, None):
                if s >= ge:
                    break
                ov = min(e, ge) - max(s, gs)
                if ov > 0:
                    agg[name] = agg.get(name, 0.0) + ov
                    covered += ov
            agg["harness.loop"] = agg.get("harness.loop", 0.0) + (
                ge - gs - covered)
        return [[k, v * 1e-9] for k, v in
                sorted(agg.items(), key=lambda kv: -kv[1])[:top] if v > 0]


def _complement(merged, lo, hi):
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def _subtract(gaps, merged):
    """The parts of ``gaps`` outside the ``merged`` intervals (both sorted,
    each without overlaps)."""
    out, j = [], 0
    for gs, ge in gaps:
        while j < len(merged) and merged[j][1] <= gs:
            j += 1
        t, k = gs, j
        while k < len(merged) and merged[k][0] < ge:
            if merged[k][0] > t:
                out.append((t, merged[k][0]))
            t = max(t, merged[k][1])
            k += 1
        if t < ge:
            out.append((t, ge))
    return out


def summarize(events: list[Event]) -> TraceSummary:
    """Cut the events to the ``bench.traced`` span. Raises when the trace
    has no window span or no device plane."""
    wins = [e for e in events if e.name == WINDOW]
    if not wins:
        raise ValueError(f"trace has no {WINDOW!r} span")
    lo, hi = wins[0].start_ns, wins[0].end_ns
    inside = [e for e in events if e.end_ns > lo and e.start_ns < hi]
    dev = [e for e in inside if is_device_plane(e.plane)]
    if not dev:
        raise ValueError("trace has no device events in the window")
    ops = [e for e in dev if e.line == OPS_LINE]
    modules = [e for e in dev if e.line == MODULES_LINE]
    busy = {}
    for plane in sorted({e.plane for e in dev}):
        src = [e for e in (ops or modules) if e.plane == plane]
        busy[plane] = union_length(_clip([(e.start_ns, e.end_ns)
                                          for e in src], lo, hi))
    host = [e for e in inside if not is_device_plane(e.plane)]
    return TraceSummary((lo, hi), sorted(busy), busy, modules, ops, host)
