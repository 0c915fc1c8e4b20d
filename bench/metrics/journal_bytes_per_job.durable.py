"""Write-ahead journal (``store/journal.py``): bytes journaled in the
window (the program's ``journal.bytes`` counter), per job decided in it.
Moves ``decisions_per_s``."""


def read(layer):
    program = layer.get("program")
    if not program or "journal.bytes" not in program["counters"] \
            or not layer["decisions"]:
        return None
    return program["counters"]["journal.bytes"] / layer["decisions"]
