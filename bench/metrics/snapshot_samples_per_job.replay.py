"""Snapshot (``pipeline/batch.py`` ``snapshot_batch``): trace samples the
snapshots assembled (the program's ``snapshot.samples`` counter), per job
decided in the window; it grows with the trace so far.  Moves
``decisions_per_s``."""


def read(layer):
    program = layer.get("program")
    if not program or "snapshot.samples" not in program["counters"] \
            or not layer["decisions"]:
        return None
    return program["counters"]["snapshot.samples"] / layer["decisions"]
