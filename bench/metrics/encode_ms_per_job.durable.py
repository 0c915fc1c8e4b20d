"""Record encoding (``store/session_store.py`` ``SessionStore.record``):
wall time in the program's ``store.encode`` span (each record's payload
put in JSON-ready form before ``journal.append`` serializes it), per job
decided in the window.  Moves ``decisions_per_s``."""


def read(layer):
    program = layer.get("program")
    if not program or "store.encode" not in program["spans"] \
            or not layer["decisions"]:
        return None
    return 1e3 * program["spans"]["store.encode"]["total_s"] \
        / layer["decisions"]
