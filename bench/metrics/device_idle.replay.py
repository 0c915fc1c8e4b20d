"""The device: the share of the traced window in which no operation ran on
it, from the profiler trace (1 - busy union / window), in percent.  Moves
``decisions_per_s``."""


def read(layer):
    trace = layer["trace"]
    if trace is None or trace["window_s"] <= 0 or not trace["devices"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
