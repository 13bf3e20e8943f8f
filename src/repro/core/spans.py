"""Measured spans: the one timing primitive of the served path.

``span(name, **meta)`` times a block on the host clock
(``time.perf_counter_ns``) and does three things with it:

* it enters a ``jax.profiler.TraceAnnotation`` of the same name, so a
  profiler trace shows the block on the device trace's clock, with
  ``meta`` as the event's stats. ``meta`` is passed only while a
  profiler records: formatting it costs ~0.7 us a span otherwise;
* it adds to always-on per-name totals (count, total and largest
  nanoseconds) that ``totals()`` returns. Nothing is kept per call. The
  totals are process-wide, as a profiler's are: the core code that opens
  most spans has no handle on the engine that reads them. They are
  updated without a lock; the served path runs on one thread;
* while a collector is open on this thread (``collect()``, which the
  serving engine opens around each micro-batch and each write request
  when its tracer is enabled), it appends a record ``[name, start_ns,
  end_ns, parent, meta]``; ``parent`` is the index of the innermost span
  open on this thread, -1 for none.

A span adds no device synchronisation: it times the host, and a device
wait shows in it only where the code inside already waits.

Names are per layer: ``serve.*`` (the engine), ``search.*`` (fan-out and
index search), ``write.*`` (the index write path), ``store.*`` (the
providers' device arrays).
"""
from __future__ import annotations

import contextlib
import threading
from time import perf_counter_ns

from jax.profiler import TraceAnnotation

_profiling = TraceAnnotation.is_enabled
_totals: dict[str, list[int]] = {}  # name -> [count, total_ns, max_ns]

# request and record stamps count from here, so that seconds stay small
# enough for float sums to reconcile to the nanosecond
EPOCH_NS = perf_counter_ns()


def now_s() -> float:
    """Host-clock seconds since ``EPOCH_NS``: the time base of every
    request stamp and trace record."""
    return (perf_counter_ns() - EPOCH_NS) * 1e-9


class _Thread(threading.local):
    def __init__(self):
        self.records: list | None = None  # the open collector's records
        self.open: list[int] = []  # indices of the spans open in it


_thread = _Thread()


class span:
    """``with span("search.graph", W=4, L=100): ...`` (see the module
    docstring)."""

    __slots__ = ("name", "meta", "_ann", "_t0", "_rec")

    def __init__(self, name: str, **meta):
        self.name = name
        self.meta = meta

    def __enter__(self) -> "span":
        if self.meta and _profiling():
            self._ann = TraceAnnotation(self.name, **self.meta)
        else:
            self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        th = _thread
        self._rec = None
        if th.records is not None:
            self._rec = [self.name, 0, 0, th.open[-1] if th.open else -1,
                         self.meta]
            th.open.append(len(th.records))
            th.records.append(self._rec)
        self._t0 = perf_counter_ns()
        if self._rec is not None:
            self._rec[1] = self._t0
        return self

    def __exit__(self, *exc) -> bool:
        t1 = perf_counter_ns()
        self._ann.__exit__(*exc)
        dt = t1 - self._t0
        tot = _totals.get(self.name)
        if tot is None:
            _totals[self.name] = [1, dt, dt]
        else:
            tot[0] += 1
            tot[1] += dt
            if dt > tot[2]:
                tot[2] = dt
        if self._rec is not None:
            self._rec[2] = t1
            _thread.open.pop()
        return False


@contextlib.contextmanager
def collect():
    """Record every span that opens on this thread inside the block;
    yields the list the records go to, in the order the spans opened
    (a parent before its children)."""
    th = _thread
    saved = th.records, th.open
    th.records, th.open = [], []
    try:
        yield th.records
    finally:
        th.records, th.open = saved


def totals() -> dict[str, tuple[int, int, int]]:
    """Per span name since the process started: (count, total ns, largest
    ns). Monotone: nothing resets them."""
    return {name: (c, t, m) for name, (c, t, m) in _totals.items()}
