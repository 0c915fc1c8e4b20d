"""A whole benchmark run on the CPU at a tiny size: the chip check and the
peaks table stand aside, the compile cache stays off, and the engine
counts its histograms through the Pallas kernel in interpret mode, as it
does compiled on the chip."""
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import device, harness, run  # noqa: E402

CELLS = ("hpc.replay", "hpc.steady")
PEAKS = {"hbm_bytes_per_s": 819e9}
# The open-loop cell is not in BENCHMARK.json until a sweep on the chip
# sets its rate; its mix, check settings and readers are in place, and
# the rehearsal runs it from this entry.
OPEN = {"name": "hpc.steady", "config": "helios", "traffic": "steady",
        "chips": 1}
OPEN_METRICS = {
    "end_to_end": [
        {"name": "decision_p95_ms", "unit": "ms", "better": "lower",
         "workloads": ["hpc.steady"]},
        {"name": "event_p95_ms", "unit": "ms", "better": "lower",
         "workloads": ["hpc.steady"]}],
    "per_layer": [
        {"name": "wire_late_p95_ms.steady", "unit": "ms", "better": "lower",
         "workloads": ["hpc.steady"]},
        {"name": "pack_ms_per_event.steady", "unit": "ms/event",
         "better": "lower", "workloads": ["hpc.steady"]}]}


# A durable, failure-heavy deployment has no cell yet: the rehearsal runs
# `helios` with a `store` and a `faults` section from these entries, under
# each loop, with hpc.replay's check settings and the limits of the checks
# those sections add.  The rate is set for the 36-node cluster of
# `small_spec`: about 3.6 failures per telemetry second, 4 of its 36
# devices down at a time.
DURABLE = {"hpc.durable": "replay", "hpc.durable_steady": "steady"}
STORE = {"fsync": True, "snapshot_every": 25, "rotate_every": 10000}
FAULTS = {"failures_per_device_s": 0.1, "repair_s": 1.0}
DURABLE_LIMITS = {"failed_placements": 0, "migration_classify_calls": 0,
                  "lost_runs": 0, "resume_gap": 0}


_load_cell = harness.load_cell     # before pretend_chip patches it


def load_cell(workload: str) -> dict:
    if workload in DURABLE:
        return durable_cell(workload)
    if workload != OPEN["name"]:
        return _load_cell(workload)
    spec = harness.load_json(ROOT, "BENCHMARK.json")
    for key, extra in OPEN_METRICS.items():
        spec[key] = spec[key] + extra
    return harness.cell_spec(spec, OPEN)


def durable_cell(workload: str) -> dict:
    """``helios`` with ``STORE`` and ``FAULTS`` under the entry's loop,
    reporting the loop's end-to-end metrics."""
    traffic = DURABLE[workload]
    base = load_cell({"replay": "hpc.replay", "steady": "hpc.steady"}[traffic])
    spec = base["spec"]
    for key in ("end_to_end", "per_layer"):
        spec[key] = [dict(m, workloads=m["workloads"] + [workload])
                     if base["cell"]["name"] in m.get("workloads", ())
                     else m for m in spec[key]]
    base["cell"] = {"name": workload, "config": "helios",
                    "traffic": traffic, "chips": 1}
    base["config"].update(store=dict(STORE), faults=dict(FAULTS))
    base["check"]["limits"].update(DURABLE_LIMITS)
    return base


def small_spec(workload: str, nodes: int = 36, rate: float = 40.0) -> dict:
    """The cell as committed on a cluster of ``nodes`` nodes (36: 50 live
    jobs), with a shorter library profile and a denser check sample."""
    spec = load_cell(workload)
    spec["config"]["cluster"]["nodes"] = nodes
    spec["config"]["devices"] = {"tpu-v5e": nodes}
    if spec["mix"]["loop"] == "open":
        spec["mix"]["rate_per_s"] = rate
    spec["config"]["library"]["profile_s"] = 0.5
    spec["check"]["sample"] = 16
    spec["check"]["watch_share"] = 0.5
    return spec



def pretend_chip(monkeypatch, spec_fn=small_spec, out=None):
    """Steer a run onto the CPU at a tiny size; with ``out``, the run's
    stores go there, apart from other tests' runs."""
    import jax

    import repro.api
    from repro.pipeline.batch import BatchProfileEngine
    init = BatchProfileEngine.__init__

    def pallas_init(self, *args, **kwargs):
        init(self, *args, **{**kwargs, "backend": "pallas"})

    monkeypatch.setattr(BatchProfileEngine, "__init__", pallas_init)
    monkeypatch.setattr(run, "require_chips", lambda n: jax.devices())
    monkeypatch.setattr(device, "peaks_for", lambda kind: PEAKS)
    monkeypatch.setattr(repro.api, "enable_compilation_cache",
                        lambda: "(off)")
    monkeypatch.setattr(harness, "load_cell", spec_fn)
    if out is not None:
        monkeypatch.setattr(harness, "OUT", str(out))


def result(capsys, argv) -> dict:
    """Run ``bench/run.py`` in this process; its last stdout line."""
    assert run.main(argv) == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


def args(workload: str, seed: int = 7, seconds: float = 1.5,
         trace: int = 0) -> list:
    return ["--workload", workload, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace)]
