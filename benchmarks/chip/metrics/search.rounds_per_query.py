"""Mean graph-search rounds per query over the micro-batches of the traced
span: the partition stats' ``hops`` as the engine accumulates them
(``note_hops``), a program counter."""


def read(run):
    if run.traced is None or not run.traced.hops_lanes:
        return None
    return run.traced.hops_weighted / run.traced.hops_lanes
