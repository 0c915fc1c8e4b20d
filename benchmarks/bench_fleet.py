"""Fleet-scale online capping: throughput, budget safety, and reclaimed
provisioning headroom on a heterogeneous variability-aware pod.

A seeded ``DeviceInventory`` (three chip generations, per-device silicon
variability) runs a seeded job mix through one ``repro.api.MinosSession``:
every job is a ``submit`` of its single low-cost profiling run, and
``session.run()`` multiplexes the telemetry, caps early per job, and
re-packs the shared power budget on every decision.  The resulting
placement is then validated against ground truth: each placed job is
re-simulated *at its cap on its device* and the time-aligned aggregate
fleet power is checked against the budget.

Emits one ``emit()`` row and writes ``results/fleet.json``:
  * ``jobs_per_s``          — classification throughput of the fleet feed;
  * ``budget_violations``   — samples where the sustained (50-sample rolling
    mean) aggregate exceeds the budget — expected **0**;
  * ``headroom_reclaimed_w`` — nameplate TDP provisioning minus the packed
    p99 plan: the watts Minos hands back to the facility.

``--smoke`` runs a micro-zoo configuration for CI.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

from benchmarks.common import RESULTS, emit, reference_library
from repro.api import (DeviceInventory, MinosSession, ReferenceLibrary,
                       TPUPowerModel, VariabilityModel,
                       enable_compilation_cache, fleet_job_mix,
                       micro_gemm, micro_idle_burst, micro_spmv_compute,
                       micro_spmv_memory, micro_stencil, simulate,
                       stream_profile_workload)

SUSTAIN_WINDOW = 50              # samples (~50 ms at 1 kHz) for the rolling mean
BUDGET_FRACTION = 0.75           # of nameplate: the oversubscription target


def _sustained(agg: np.ndarray, window: int = SUSTAIN_WINDOW) -> np.ndarray:
    if len(agg) < window:
        return np.array([agg.mean()]) if len(agg) else np.zeros(1)
    kernel = np.ones(window) / window
    return np.convolve(agg, kernel, mode="valid")


def run(smoke: bool = False) -> dict:
    if smoke:
        counts = {"tpu-v5e": 2, "tpu-v5p": 1}
        streams = [micro_gemm(), micro_spmv_memory(), micro_spmv_compute(),
                   micro_idle_burst(), micro_stencil()]
        model = TPUPowerModel()
        lib = ReferenceLibrary(
            (stream_profile_workload(s, model, (0.6, 0.8, 1.0),
                                     model.spec.tdp_w, seed=i,
                                     target_duration=1.0)
             for i, s in enumerate(streams)),
            built_on=model.spec.name)
        jobs = [(s, 4 * (i % 3 + 1)) for i, s in enumerate(streams)]
        target_duration = 1.0
    else:
        counts = {"tpu-v5e": 6, "tpu-v5p": 3, "tpu-v6e": 3}
        lib = reference_library()
        jobs = fleet_job_mix(16, seed=11)
        target_duration = 2.0

    inventory = DeviceInventory.generate(counts, VariabilityModel(), seed=7)
    # round-robin jobs over devices; budget oversubscribes total nameplate
    assigned = [(s, chips, inventory[i % len(inventory)])
                for i, (s, chips) in enumerate(jobs)]
    nameplate = sum(chips * dev.nameplate_w for _, chips, dev in assigned)
    budget = BUDGET_FRACTION * nameplate

    session = MinosSession(lib, inventory=inventory, budget_w=budget,
                           objective="powercentric", quantile="p99",
                           min_confidence=0.2)
    for i, (stream, chips, dev) in enumerate(assigned):
        session.submit(stream, device=dev, chips=chips,
                       job_id=f"j{i:02d}:{stream.name}", seed=500 + i,
                       target_duration=target_duration)

    t0 = time.perf_counter()
    report = session.run()
    elapsed = time.perf_counter() - t0
    jobs_per_s = len(assigned) / elapsed

    # ground truth: re-simulate every *placed* job at its cap on its device,
    # sum the time-aligned per-chip traces, and check sustained power.
    # Plans carry the exact job_id, so matching is unambiguous even when
    # the with-replacement mix repeats a workload on a device.
    placed = {p.job_id: p for p in report.schedule.placed}
    traces = []
    for i, (stream, chips, dev) in enumerate(assigned):
        plan = placed.pop(f"j{i:02d}:{stream.name}", None)
        if plan is None:
            continue                       # deferred: draws no power
        tr = simulate(stream, plan.cap, dev.power_model(), seed=500 + i,
                      target_duration=target_duration)
        traces.append(plan.chips * tr.power_filtered)
    assert not placed, f"unmatched placed plans: {sorted(placed)}"
    if traces:
        # align to the LONGEST window: the workloads are periodic, so a
        # shorter trace is tiled (the job keeps running its pattern) — no
        # tail samples escape the budget check
        n = max(len(t) for t in traces)
        aggregate = np.sum([np.resize(t, n) for t in traces], axis=0)
    else:
        aggregate = np.zeros(1)            # everything deferred: no draw
    sustained = _sustained(aggregate)
    violations = int(np.sum(sustained > budget))

    out = {
        "config": {
            "smoke": smoke,
            "devices": {m: len(inventory.by_model(m))
                        for m in inventory.models},
            "n_jobs": len(assigned),
            "budget_w": round(budget, 1),
            "budget_fraction_of_nameplate": BUDGET_FRACTION,
            "provision_quantile": report.quantile,
        },
        "jobs_per_s": round(jobs_per_s, 2),
        "early_decisions": report.early_decisions,
        "repacks": report.repacks,
        "chunks_dropped": report.chunks_dropped,
        "placed": len(report.schedule.placed),
        "deferred": len(report.schedule.deferred),
        "planned_power_w": round(report.schedule.planned_power_w, 1),
        "nameplate_power_w": round(report.schedule.nameplate_power_w, 1),
        "headroom_reclaimed_w": round(report.schedule.headroom_reclaimed_w, 1),
        "budget_violations": violations,
        "peak_sustained_w": round(float(sustained.max()), 1),
        "peak_instant_w": round(float(aggregate.max()), 1),
        "decisions": {
            job_id: {"cap": d.cap, "early": d.early,
                     "fraction": round(d.fraction, 3),
                     "device": d.device_id,
                     "neighbor": d.selection.power_neighbor}
            for job_id, d in sorted(report.decisions.items())
        },
    }
    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(RESULTS, "fleet.json"), "w") as f:
        json.dump(out, f, indent=1)
    emit("fleet_online_cap", elapsed * 1e6,
         f"jobs/s={jobs_per_s:.1f};violations={violations};"
         f"headroom_kW={out['headroom_reclaimed_w'] / 1e3:.1f}")
    assert violations == 0, (
        f"fleet exceeded its power budget in {violations} sustained windows "
        f"(peak {sustained.max():.0f} W vs budget {budget:.0f} W)")
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="micro-zoo configuration for CI")
    args = ap.parse_args()
    enable_compilation_cache()
    print(json.dumps(run(smoke=args.smoke), indent=1))


if __name__ == "__main__":
    main()
