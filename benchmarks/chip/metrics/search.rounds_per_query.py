"""Mean graph-search rounds per query over the micro-batches of the traced
span: the partition stats' ``hops`` as the engine accumulates them
(``note_hops``), a program counter."""


def read(run):
    c = run.traced.counters if run.traced is not None else {}
    if not c.get("hops_lanes"):
        return None
    return c["hops_weighted"] / c["hops_lanes"]
