"""Algorithm 1 plans and packing (``sched/power_sched.py``): the growth of
``FleetCapController.repack_s`` over the window, per control-plane event
(arrival or retire) in it.  Moves ``event_p95_ms``."""


def read(layer):
    if not layer["events"]:
        return None
    return 1e3 * layer["repack_s"] / layer["events"]
