"""Share of the v5e roofline the rerank program (``rerank``) reaches.

The work any implementation of the rerank needs, for the queries
answered in the traced span: read k' = rerank_multiplier x k candidate
rows of ``dim`` float32 and the query row, and 3 operations per element
(subtract, multiply, add). The least time is the larger of bytes over the HBM
bandwidth and operations over the peak rate in ``peaks.json``; the bytes
bound it at these shapes. Share = least time / device time of the rerank
programs that started in the span. Padded lanes of a batch bucket are not
work the answers need, so they lower the share."""

RERANK_MULTIPLIER = 5.0  # the program's k' = 5 x k (the paper's Fig. 5)


def read(run):
    if run.trace is None:
        return None
    t = run.trace.program_s(("rerank",))
    if not t:
        return None
    cfg = run.cell.config
    dim, k = cfg["dim"], cfg["graph"]["k"]
    kprime = int(round(RERANK_MULTIPLIER * k))
    n = run.traced.answered
    nbytes = n * (kprime + 1) * dim * 4
    flops = n * kprime * dim * 3
    least = max(nbytes / run.peaks["hbm_bytes_per_s"],
                flops / run.peaks["bf16_flops_per_s"])
    return 100.0 * least / t
