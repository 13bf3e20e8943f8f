"""Megabytes (10^6 B) copied from the host to the device per doc written:
the partitions' ``upload_bytes`` (every array ``store.upload`` copies)
over the docs the engine applied (``ingest_ops``: a delete of one doc is
one, an upsert of 8 docs eight), both counted over the window (program
counters)."""


def read(run):
    c = run.window.counters
    if not c.get("ingest_ops"):
        return None
    return c["upload_bytes"] * 1e-6 / c["ingest_ops"]
