"""Algorithm 1 plans and packing (``sched/power_sched.py``): the growth of
``FleetCapController.repack_s`` over the window, per job decided in it.
Moves ``decisions_per_s``."""


def read(layer):
    if not layer["decisions"]:
        return None
    return 1e3 * layer["repack_s"] / layer["decisions"]
