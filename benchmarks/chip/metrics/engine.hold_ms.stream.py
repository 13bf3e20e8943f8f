"""Milliseconds each answer waited, on average, inside ``engine.pump``
behind the write the same call applied after storing it: the engine's
``answer_hold_ns`` over its ``answers``, both counted over the window
(program counters). None where the window applied no write."""


def read(run):
    c = run.window.counters
    if not c.get("ingest_ops") or not c.get("answers"):
        return None
    return c["answer_hold_ns"] * 1e-6 / c["answers"]
