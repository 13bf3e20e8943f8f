"""Device milliseconds of the index write path per doc applied in the
traced span: the insert search (``insert_candidates``), the prunes
(``prune_batch``, ``prune_nodes``), the in-place delete
(``inplace_delete``), the consolidation sweep (``consolidate_chunk``),
and the PQ encode and decode the write path runs (``encode``,
``decode_versioned``)."""

PROGRAMS = ("insert_candidates", "prune_batch", "prune_nodes",
            "inplace_delete", "consolidate_chunk", "encode",
            "decode_versioned")


def read(run):
    if run.trace is None or not run.traced.write_ops:
        return None
    s = run.trace.program_s(PROGRAMS)
    return None if s is None else s * 1e3 / run.traced.write_ops
