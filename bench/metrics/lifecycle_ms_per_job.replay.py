"""Job lifecycle (``fleet/controller.py`` ``admit_many`` and ``retire``):
wall time in the program's ``admit`` and ``retire`` spans together, the
retire's re-pack included, per job decided in the window.  Moves
``decisions_per_s``."""

SPANS = ("admit", "retire")


def read(layer):
    program = layer.get("program")
    if not program or not layer["decisions"]:
        return None
    found = [program["spans"][n]["total_s"] for n in SPANS
             if n in program["spans"]]
    if not found:
        return None
    return 1e3 * sum(found) / layer["decisions"]
