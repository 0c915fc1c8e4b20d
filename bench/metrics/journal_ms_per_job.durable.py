"""Write-ahead journal (``store/journal.py`` ``EventJournal.append``): wall
time in the program's ``journal.append`` span (checksum, encoding, write,
flush and fsync of each record), per job decided in the window.  Moves
``decisions_per_s``."""


def read(layer):
    program = layer.get("program")
    if not program or "journal.append" not in program["spans"] \
            or not layer["decisions"]:
        return None
    return 1e3 * program["spans"]["journal.append"]["total_s"] \
        / layer["decisions"]
