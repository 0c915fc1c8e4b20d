"""The fleet tick's own work (``fleet/controller.py``): wall time in
``ingest_tick`` less the engine ingest, the classify sweep and the packing
inside it, per job decided in the window.  Moves ``decisions_per_s``."""


def read(layer):
    spans = layer["spans"]
    if "tick" not in spans or not layer["decisions"]:
        return None
    own = (spans["tick"] - spans.get("engine", 0.0)
           - spans.get("classify", 0.0) - layer["pack_in_tick_s"])
    return 1e3 * own / layer["decisions"]
