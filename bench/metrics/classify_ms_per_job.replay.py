"""Snapshot and classify (``pipeline/online.py``, ``core/classify.py``):
wall time in ``observe_fleet``, per job decided in the window.  Moves
``decisions_per_s``."""


def read(layer):
    t = layer["spans"].get("classify")
    if t is None or not layer["decisions"]:
        return None
    return 1e3 * t / layer["decisions"]
