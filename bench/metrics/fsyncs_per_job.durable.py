"""Write-ahead journal (``store/journal.py``): fsync calls in the window
(the program's ``journal.fsyncs`` counter), per job decided in it.  Moves
``decisions_per_s``."""


def read(layer):
    program = layer.get("program")
    if not program or "journal.fsyncs" not in program["counters"] \
            or not layer["decisions"]:
        return None
    return program["counters"]["journal.fsyncs"] / layer["decisions"]
