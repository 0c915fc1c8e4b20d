"""Snapshot p90 (``pipeline/batch.py`` rank windows): of the profiles whose
p90 came from their slot's order statistics (the program's
``snapshot.pq_prefilled`` counter), the share whose rank window had to be
rebuilt from the whole trace (``snapshot.pq_rebuilds``), in percent.
Moves ``decisions_per_s``."""


def read(layer):
    program = layer.get("program")
    if not program:
        return None
    prefilled = program["counters"].get("snapshot.pq_prefilled", 0)
    if not prefilled:
        return None
    return 100.0 * program["counters"].get("snapshot.pq_rebuilds", 0) \
        / prefilled
