"""Host milliseconds per doc written in which the device waits on the
index write path: device-idle time whose innermost open span is one of
the program's ``write.*`` or ``store.*`` spans (``write.encode``,
``write.graph_insert``, ``write.prune``, ``write.readback``,
``write.repair``, ``write.diff``, ``store.upload``, ...), inside the
``serve.ingest`` spans (one per write request applied) that lie wholly in
the traced span, over the ``docs`` of those spans. A write cut by the
span's edges counts on neither side."""

PREFIXES = ("write.", "store.")


def read(run):
    if run.trace is None:
        return None
    writes = run.trace.spans("serve.ingest")
    docs = sum(int(e.stats["docs"]) for e in writes)
    if not docs:
        return None
    return run.trace.span_s(PREFIXES, within=writes) * 1e3 / docs
