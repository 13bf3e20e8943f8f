"""Device milliseconds of the query path per dispatched micro-batch: the
graph search (``_batched_search_entry``) and the full-precision rerank
(``rerank``) programs that started in the traced span, over the
micro-batches the window dispatched in it."""

PROGRAMS = ("_batched_search_entry", "rerank")


def read(run):
    if run.trace is None or not run.traced.batches:
        return None
    s = run.trace.program_s(PROGRAMS)
    return None if s is None else s * 1e3 / run.traced.batches
