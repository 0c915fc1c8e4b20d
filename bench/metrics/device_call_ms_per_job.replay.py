"""The device round trip (``pipeline/batch.py`` ``_flush_device``): wall
time in the program's ``engine.device`` span (building the padded buffer,
the ``spike_hist_packed`` call, the readback and the row add), per job
decided in the window.  Moves ``decisions_per_s``."""


def read(layer):
    program = layer.get("program")
    if not program or "engine.device" not in program["spans"] \
            or not layer["decisions"]:
        return None
    return 1e3 * program["spans"]["engine.device"]["total_s"] \
        / layer["decisions"]
