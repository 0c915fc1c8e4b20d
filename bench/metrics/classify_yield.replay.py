"""Classifier sweep (``pipeline/online.py`` ``observe_fleet``): of the
profiles swept (the program's ``classify.swept`` counter), the share whose
confidence reached the gate (``classify.decided``), in percent.  Moves
``decisions_per_s``."""


def read(layer):
    program = layer.get("program")
    if not program:
        return None
    swept = program["counters"].get("classify.swept", 0)
    if not swept:
        return None
    return 100.0 * program["counters"].get("classify.decided", 0) / swept
