"""Chaos-tested recovery: seeded failure injection on an elastic fleet.

A heterogeneous, variability-aware fleet runs a seeded job mix through one
``repro.api.MinosSession`` under a 75%-of-nameplate power budget while the
harness kills, degrades, and restores devices mid-stream (a seeded schedule
— every run replays the same chaos).  Each injected failure must recover by
**migration, never re-classification**: affected jobs are re-planned onto
surviving healthy devices straight from their cached ``CapDecision``
selections (device-portable classification makes the cross-model move
free), and a multi-chip job that loses part of its device span shrinks
through the elastic re-mesh instead.

Emits one ``emit()`` row and writes ``results/chaos.json``:
  * ``recovery_ms``            — wall-clock per injected fail/degrade event
    (migrate + repack), mean and max;
  * ``migrations``             — jobs moved or elastically shrunk;
  * ``classifier_calls_chaos`` — classifier invocations during all
    fail/degrade/restore handling — asserted **0**;
  * ``budget_violations``      — sustained (50-sample rolling mean) samples
    where the re-simulated surviving placement exceeds the budget —
    asserted **0**.

``--smoke`` runs a micro-zoo configuration for CI.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import time

import numpy as np

from benchmarks.common import RESULTS, emit, reference_library
from repro.api import (DeviceInventory, FleetTelemetryMux, MinosSession,
                       ReferenceLibrary, StragglerMonitor, TPUPowerModel,
                       VariabilityModel, count_classifier_calls,
                       enable_compilation_cache, fleet_job_mix, micro_gemm,
                       micro_idle_burst,
                       micro_spmv_compute, micro_spmv_memory, micro_stencil,
                       simulate, stream_profile_workload, stream_telemetry)

SUSTAIN_WINDOW = 50              # samples (~50 ms at 1 kHz) for the rolling mean
BUDGET_FRACTION = 0.75           # of nameplate: the oversubscription target
CHUNK_SAMPLES = 100


def _sustained(agg: np.ndarray, window: int = SUSTAIN_WINDOW) -> np.ndarray:
    if len(agg) < window:
        return np.array([agg.mean()]) if len(agg) else np.zeros(1)
    kernel = np.ones(window) / window
    return np.convolve(agg, kernel, mode="valid")


def _chaos_schedule(total_chunks: int, inventory, assigned, seed: int):
    """Seeded (chunk-index, action, device_id) schedule: kill one loaded
    device a quarter of the way in, degrade another at the midpoint,
    restore the killed one at three quarters, kill a second near the end."""
    rng = np.random.default_rng(seed)
    loaded = sorted({dev.device_id for _, _, dev in assigned})
    victims = [loaded[int(rng.integers(len(loaded)))]]
    rest = [d for d in loaded if d not in victims]
    degraded = rest[int(rng.integers(len(rest)))]
    second = [d for d in rest if d != degraded]
    victims.append(second[int(rng.integers(len(second)))])
    return [
        (int(0.25 * total_chunks), "fail", victims[0]),
        (int(0.50 * total_chunks), "degrade", degraded),
        (int(0.70 * total_chunks), "restore", victims[0]),
        (int(0.80 * total_chunks), "fail", victims[1]),
    ]


def run(smoke: bool = False) -> dict:
    if smoke:
        counts = {"tpu-v5e": 3, "tpu-v5p": 2}
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
    assigned = [(s, chips, inventory[i % len(inventory)])
                for i, (s, chips) in enumerate(jobs)]
    nameplate = sum(chips * dev.nameplate_w for _, chips, dev in assigned)
    budget = BUDGET_FRACTION * nameplate

    session = MinosSession(lib, inventory=inventory, budget_w=budget,
                           objective="powercentric", quantile="p99",
                           min_confidence=0.2,
                           stragglers=StragglerMonitor())
    mux = FleetTelemetryMux()
    handles = {}
    for i, (stream, chips, dev) in enumerate(assigned):
        meta, chunks = stream_telemetry(
            stream, 1.0, dev.power_model(), seed=700 + i,
            target_duration=target_duration, chunk_samples=CHUNK_SAMPLES,
            device_id=dev.device_id)
        handle = session.submit(meta, device=dev, chips=chips,
                                job_id=f"j{i:02d}:{stream.name}")
        handles[handle.job_id] = handle
        mux.add_job(handle.job_id, meta, chunks)
    total_chunks = sum(math.ceil(h.meta.n_samples / CHUNK_SAMPLES)
                       for h in handles.values())

    schedule = _chaos_schedule(total_chunks, inventory, assigned, seed=23)
    injected = [dict(at_chunk=at, action=a, device=d) for at, a, d in schedule]
    calls = count_classifier_calls(session.classifier)

    recovery_ms = []
    chaos_calls = 0
    failed_now: set[str] = set()
    t_run = time.perf_counter()
    n = 0
    pending = list(schedule)
    for fchunk in mux:
        while pending and n >= pending[0][0]:
            _, action, device_id = pending.pop(0)
            before = calls["n"]
            t0 = time.perf_counter()
            if action == "fail":
                session.fail_device(device_id)
                mux.drop_device(device_id)     # the wire goes silent too
                failed_now.add(device_id)
            elif action == "degrade":
                session.degrade_device(device_id)
            else:
                session.restore_device(device_id)
                failed_now.discard(device_id)
            dt_ms = (time.perf_counter() - t0) * 1e3
            chaos_calls += calls["n"] - before
            if action in ("fail", "degrade"):
                recovery_ms.append(dt_ms)
        n += 1
        if fchunk.device_id in failed_now:
            continue               # in-flight chunk from dead silicon
        handles[fchunk.job_id].feed(fchunk.chunk)
    for _, action, device_id in pending:       # stream ended first: apply
        before = calls["n"]
        if action == "fail":
            session.fail_device(device_id)
        elif action == "degrade":
            session.degrade_device(device_id)
        else:
            session.restore_device(device_id)
        chaos_calls += calls["n"] - before

    # mid-profile migrants lost their partial trace with their device:
    # restart their profiling runs on the silicon they landed on, then let
    # the session drain + finalize everything
    reprofiled = 0
    for i, (stream, chips, dev) in enumerate(assigned):
        handle = handles[f"j{i:02d}:{stream.name}"]
        if not handle.decided and handle.fraction == 0.0:
            handle.reprofile(stream, seed=900 + i,
                             target_duration=target_duration,
                             chunk_samples=CHUNK_SAMPLES)
            reprofiled += 1
    report = session.run()
    elapsed = time.perf_counter() - t_run

    # no placed job may sit on a currently-failed device
    health = session.device_health
    on_dead = [p.job_id for p in report.schedule.placed
               if health.get(p.device_id) == "failed"]
    assert not on_dead, f"jobs placed on failed devices: {on_dead}"

    # ground truth: re-simulate every placed job at its cap on its FINAL
    # device (migrations included) and check the sustained aggregate
    placed = {p.job_id: p for p in report.schedule.placed}
    traces = []
    for i, (stream, chips, dev) in enumerate(assigned):
        plan = placed.pop(f"j{i:02d}:{stream.name}", None)
        if plan is None:
            continue                       # deferred/stranded: draws no power
        final_dev = inventory.get(plan.device_id)
        tr = simulate(stream, plan.cap, final_dev.power_model(), seed=700 + i,
                      target_duration=target_duration)
        traces.append(plan.chips * tr.power_filtered)
    assert not placed, f"unmatched placed plans: {sorted(placed)}"
    if traces:
        m = max(len(t) for t in traces)
        aggregate = np.sum([np.resize(t, m) for t in traces], axis=0)
    else:
        aggregate = np.zeros(1)
    sustained = _sustained(aggregate)
    violations = int(np.sum(sustained > budget))

    out = {
        "config": {
            "smoke": smoke,
            "devices": {mname: len(inventory.by_model(mname))
                        for mname in inventory.models},
            "n_jobs": len(assigned),
            "budget_w": round(budget, 1),
            "budget_fraction_of_nameplate": BUDGET_FRACTION,
            "provision_quantile": report.quantile,
            "chaos_schedule": injected,
        },
        "recovery_ms": {
            "mean": round(float(np.mean(recovery_ms)), 3),
            "max": round(float(np.max(recovery_ms)), 3),
            "events": [round(r, 3) for r in recovery_ms],
        },
        "failures": report.failures,
        "migrations": report.migrations,
        "events": [{"kind": e.kind, "device": e.device_id, "job": e.job_id,
                    "to": e.to_device_id, "detail": e.detail}
                   for e in report.events],
        "device_health": health,
        "classifier_calls_chaos": chaos_calls,
        "reprofiled_jobs": reprofiled,
        "repacks": report.repacks,
        "placed": len(report.schedule.placed),
        "deferred": len(report.schedule.deferred),
        "planned_power_w": round(report.schedule.planned_power_w, 1),
        "budget_violations": violations,
        "peak_sustained_w": round(float(sustained.max()), 1),
        "elapsed_s": round(elapsed, 3),
    }
    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(RESULTS, "chaos.json"), "w") as f:
        json.dump(out, f, indent=1)
    emit("fleet_chaos_recovery", float(np.mean(recovery_ms)) * 1e3,
         f"migrations={report.migrations};violations={violations};"
         f"clf_calls={chaos_calls}")
    assert chaos_calls == 0, (
        f"chaos handling classified {chaos_calls} times; migrations must "
        f"re-plan from cached decisions only")
    assert violations == 0, (
        f"surviving fleet exceeded its power budget in {violations} "
        f"sustained windows (peak {sustained.max():.0f} W vs budget "
        f"{budget:.0f} W)")
    assert report.migrations > 0, "chaos schedule migrated nothing"
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
