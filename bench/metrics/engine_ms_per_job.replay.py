"""Engine ingest (``pipeline/batch.py``): wall time in
``BatchProfileEngine.ingest_batch``, its device histogram call included,
per job decided in the window.  Moves ``decisions_per_s``."""


def read(layer):
    t = layer["spans"].get("engine")
    if t is None or not layer["decisions"]:
        return None
    return 1e3 * t / layer["decisions"]
