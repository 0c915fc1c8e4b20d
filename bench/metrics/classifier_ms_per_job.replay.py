"""Classifier sweep (``core/classify.py`` via
``classify_with_margin_batch``): wall time in the program's
``classify.sweep`` span, per job decided in the window.  Moves
``decisions_per_s``."""


def read(layer):
    program = layer.get("program")
    if not program or "classify.sweep" not in program["spans"] \
            or not layer["decisions"]:
        return None
    return 1e3 * program["spans"]["classify.sweep"]["total_s"] \
        / layer["decisions"]
