"""Device milliseconds of the index write path per doc written: the
insert search (``insert_candidates``), the prunes (``prune_batch``,
``prune_nodes``), the in-place delete (``inplace_delete``), the
consolidation sweep (``consolidate_chunk``), and the PQ encode and decode
the write path runs (``encode``, ``decode_versioned``), where they start
inside the ``serve.ingest`` spans (one per write request applied) that
lie wholly in the traced span, over the ``docs`` of those spans. A write
cut by the span's edges counts on neither side."""

PROGRAMS = ("insert_candidates", "prune_batch", "prune_nodes",
            "inplace_delete", "consolidate_chunk", "encode",
            "decode_versioned")


def read(run):
    if run.trace is None:
        return None
    writes = run.trace.spans("serve.ingest")
    docs = sum(int(e.stats["docs"]) for e in writes)
    if not docs:
        return None
    s = run.trace.program_s(PROGRAMS, within=writes)
    return None if s is None else s * 1e3 / docs
