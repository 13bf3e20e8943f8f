"""Host milliseconds per micro-batch in which the device waits on the
search layer: device-idle time whose innermost open span is one of the
program's ``search.*`` spans (``search.prepare``, ``search.graph``,
``search.rerank``, ``search.finish``, ``search.partition``,
``search.merge``), inside the ``serve.dispatch`` spans (one per
micro-batch) that lie wholly in the traced span, over the number of
those spans."""


def read(run):
    if run.trace is None:
        return None
    batches = run.trace.spans("serve.dispatch")
    if not batches:
        return None
    return run.trace.span_s(("search.",), within=batches) * 1e3 / len(batches)
