"""Stream-end decisions (``fleet/controller.py`` ``finalize_job``): wall
time in the program's ``finalize_job`` span, its classification and
re-pack included, per job decided in the window.  Moves
``decisions_per_s``."""


def read(layer):
    program = layer.get("program")
    if not program or "finalize_job" not in program["spans"] \
            or not layer["decisions"]:
        return None
    return 1e3 * program["spans"]["finalize_job"]["total_s"] \
        / layer["decisions"]
