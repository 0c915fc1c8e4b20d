"""Outside the program (the benchmark's loop, the collector, the
interpreter): device-idle time of the traced window in which no program
span (``minos.*``) was open, from the profiler trace, per job decided in
the window.  Moves ``decisions_per_s``."""


def read(layer):
    idle = layer.get("idle_by_span")
    if not layer.get("program") or not idle or not idle["devices"] \
            or not layer["decisions"]:
        return None
    return 1e3 * idle["unspanned_s"] / layer["decisions"]
