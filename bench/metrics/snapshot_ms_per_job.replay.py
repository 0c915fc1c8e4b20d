"""Snapshot (``pipeline/batch.py`` ``snapshot_batch``): wall time in the
program's ``classify.snapshot`` span (the per-engine spike-count gate and
the batched snapshot), per job decided in the window.  Moves
``decisions_per_s``."""


def read(layer):
    program = layer.get("program")
    if not program or "classify.snapshot" not in program["spans"] \
            or not layer["decisions"]:
        return None
    return 1e3 * program["spans"]["classify.snapshot"]["total_s"] \
        / layer["decisions"]
