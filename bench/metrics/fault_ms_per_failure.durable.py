"""Device faults (``fleet/controller.py`` ``fail_device`` and
``restore_device``): wall time in the program's ``fault.fail`` and
``fault.restore`` spans together (journal, drain, migration and re-pack
included), per failure in the window, the ``fault.fail`` span's calls.
Moves ``decisions_per_s``."""

SPANS = ("fault.fail", "fault.restore")


def read(layer):
    program = layer.get("program")
    if not program or "fault.fail" not in program["spans"]:
        return None
    spans = program["spans"]
    return 1e3 * sum(spans[n]["total_s"] for n in SPANS if n in spans) \
        / spans["fault.fail"]["calls"]
