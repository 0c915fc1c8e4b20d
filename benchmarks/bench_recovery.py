"""Crash-recovery: SIGKILL a durable session mid-stream, resume, re-plan.

A child process runs a seeded job mix through a ``MinosSession`` backed by a
durable store (write-ahead journal + snapshots), fails one loaded device
mid-stream, and then **SIGKILLs itself** between chunk feeds — no cleanup,
no flush beyond what the journal already guaranteed.  The parent then calls
``MinosSession.resume`` on the store directory and must get the session
back:

  * **zero classifier calls** during resume (cached decisions and plans are
    adopted from the journal, never recomputed) — asserted;
  * decided jobs keep their caps and placements; mid-profile jobs come back
    flagged ``needs_reprofile`` and restart their runs on their current
    device;
  * after the drain, the surviving placement shows **zero sustained budget
    violations** (50-sample rolling mean over re-simulated ground truth) —
    asserted.

Emits one ``emit()`` row (resume latency) and writes
``results/recovery.json`` plus a copy of the journal at
``results/recovery_journal.jsonl`` for artifact upload.

``--smoke`` runs a shorter micro configuration for CI; ``--child`` is the
internal crash-target entry point.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time

import numpy as np

from benchmarks.common import RESULTS, emit
from repro.api import (DeviceInventory, FleetTelemetryMux, MinosSession,
                       ReferenceLibrary, TPUPowerModel, VariabilityModel,
                       count_classifier_calls, enable_compilation_cache,
                       micro_gemm, micro_idle_burst,
                       micro_spmv_compute, micro_spmv_memory, micro_stencil,
                       simulate, stream_profile_workload, stream_telemetry)

SUSTAIN_WINDOW = 50              # samples for the rolling-mean violation test
BUDGET_FRACTION = 0.75           # of nameplate: the oversubscription target
CHUNK_SAMPLES = 100
FAIL_FRACTION = 0.40             # inject the device failure at 40% of chunks
CRASH_FRACTION = 0.55            # SIGKILL at 55% of chunks


def _sustained(agg: np.ndarray, window: int = SUSTAIN_WINDOW) -> np.ndarray:
    if len(agg) < window:
        return np.array([agg.mean()]) if len(agg) else np.zeros(1)
    kernel = np.ones(window) / window
    return np.convolve(agg, kernel, mode="valid")


def _setup(smoke: bool):
    """Deterministic scenario shared by the crash child and the resuming
    parent: same library, inventory, job mix, and budget in both processes."""
    streams = [micro_gemm(), micro_spmv_memory(), micro_spmv_compute(),
               micro_idle_burst(), micro_stencil()]
    target_duration = 1.0 if smoke else 2.0
    model = TPUPowerModel()
    lib = ReferenceLibrary(
        (stream_profile_workload(s, model, (0.6, 0.8, 1.0),
                                 model.spec.tdp_w, seed=i,
                                 target_duration=target_duration)
         for i, s in enumerate(streams)),
        built_on=model.spec.name)
    jobs = [(s, 4 * (i % 3 + 1)) for i, s in enumerate(streams)]
    if not smoke:
        jobs += [(s, 2) for s in streams[:3]]
    inventory = DeviceInventory.generate({"tpu-v5e": 3, "tpu-v5p": 2},
                                         VariabilityModel(), seed=7)
    assigned = [(s, chips, inventory[i % len(inventory)])
                for i, (s, chips) in enumerate(jobs)]
    nameplate = sum(chips * dev.nameplate_w for _, chips, dev in assigned)
    return lib, inventory, assigned, BUDGET_FRACTION * nameplate, \
        target_duration


def _job_id(i: int, stream) -> str:
    return f"j{i:02d}:{stream.name}"


def child(store: str, smoke: bool) -> None:
    """The crash target: run the scenario against a durable store, fail a
    device mid-stream, then SIGKILL self between chunk feeds."""
    lib, inventory, assigned, budget, target_duration = _setup(smoke)
    session = MinosSession(lib, inventory=inventory, budget_w=budget,
                           min_confidence=0.2, store=store)
    mux = FleetTelemetryMux()
    handles = {}
    for i, (stream, chips, dev) in enumerate(assigned):
        meta, chunks = stream_telemetry(
            stream, 1.0, dev.power_model(), seed=700 + i,
            target_duration=target_duration, chunk_samples=CHUNK_SAMPLES,
            device_id=dev.device_id)
        handle = session.submit(meta, device=dev, chips=chips,
                                job_id=_job_id(i, stream))
        handles[handle.job_id] = handle
        mux.add_job(handle.job_id, meta, chunks)
    total = sum(int(np.ceil(h.meta.n_samples / CHUNK_SAMPLES))
                for h in handles.values())
    fail_at, crash_at = int(FAIL_FRACTION * total), int(CRASH_FRACTION * total)
    victim = assigned[0][2].device_id
    failed = False
    for n, fchunk in enumerate(mux):
        if n >= crash_at:
            os.kill(os.getpid(), signal.SIGKILL)   # the crash under test
        if not failed and n >= fail_at:
            session.fail_device(victim)
            mux.drop_device(victim)
            failed = True
        if failed and fchunk.device_id == victim:
            continue
        handles[fchunk.job_id].feed(fchunk.chunk)
    raise AssertionError("stream drained before the scheduled crash")


def run(smoke: bool = False) -> dict:
    store = os.path.join(tempfile.mkdtemp(prefix="minos-recovery-"), "store")

    # -- crash: the child takes SIGKILL mid-stream -----------------------
    # the child's engine needs the chip, which belongs to one process: it
    # runs before this process touches a JAX backend
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child", store]
        + (["--smoke"] if smoke else []))
    assert proc.returncode == -signal.SIGKILL, (
        f"crash child exited {proc.returncode}, expected "
        f"-{int(signal.SIGKILL)} (SIGKILL)")
    assert os.path.exists(os.path.join(store, "journal.jsonl")), \
        "crashed session left no journal behind"

    # -- resume: zero classifier calls, decisions adopted from the journal
    lib, inventory, assigned, budget, target_duration = _setup(smoke)
    classifier = lib.classifier()
    calls = count_classifier_calls(classifier)
    t0 = time.perf_counter()
    session = MinosSession.resume(store, references=classifier)
    resume_ms = (time.perf_counter() - t0) * 1e3
    resume_calls = calls["n"]

    decided = [jid for jid, h in session.jobs.items() if h.decided]
    reprofiled = 0
    for i, (stream, chips, dev) in enumerate(assigned):
        handle = session.jobs[_job_id(i, stream)]
        if not handle.decided:
            # the partial trace died with the process: restart profiling on
            # whatever device the job sits on now
            handle.reprofile(stream, seed=900 + i,
                             target_duration=target_duration,
                             chunk_samples=CHUNK_SAMPLES)
            reprofiled += 1
    report = session.run()
    session.close()

    health = session.device_health
    on_dead = [p.job_id for p in report.schedule.placed
               if health.get(p.device_id) == "failed"]
    assert not on_dead, f"resume placed jobs on failed devices: {on_dead}"

    # ground truth: re-simulate every placed job at its cap on its FINAL
    # device and check the sustained aggregate against the budget
    placed = {p.job_id: p for p in report.schedule.placed}
    traces = []
    for i, (stream, chips, dev) in enumerate(assigned):
        plan = placed.get(_job_id(i, stream))
        if plan is None:
            continue
        final_dev = inventory.get(plan.device_id)
        tr = simulate(stream, plan.cap, final_dev.power_model(),
                      seed=700 + i, target_duration=target_duration)
        traces.append(plan.chips * tr.power_filtered)
    if traces:
        m = max(len(t) for t in traces)
        aggregate = np.sum([np.resize(t, m) for t in traces], axis=0)
    else:
        aggregate = np.zeros(1)
    sustained = _sustained(aggregate)
    violations = int(np.sum(sustained > budget))

    with open(os.path.join(store, "journal.jsonl"), "rb") as f:
        journal_records = sum(1 for _ in f)

    out = {
        "config": {
            "smoke": smoke,
            "devices": {mname: len(inventory.by_model(mname))
                        for mname in inventory.models},
            "n_jobs": len(assigned),
            "budget_w": round(budget, 1),
            "budget_fraction_of_nameplate": BUDGET_FRACTION,
            "fail_fraction": FAIL_FRACTION,
            "crash_fraction": CRASH_FRACTION,
        },
        "resume_latency_ms": round(resume_ms, 3),
        "classifier_calls_resume": resume_calls,
        "journal_records": journal_records,
        "decisions_recovered": len(decided),
        "reprofiled_jobs": reprofiled,
        "migrations": report.migrations,
        "device_health": health,
        "placed": len(report.schedule.placed),
        "deferred": len(report.schedule.deferred),
        "planned_power_w": round(report.schedule.planned_power_w, 1),
        "budget_violations": violations,
        "peak_sustained_w": round(float(sustained.max()), 1),
    }
    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(RESULTS, "recovery.json"), "w") as f:
        json.dump(out, f, indent=1)
    shutil.copyfile(os.path.join(store, "journal.jsonl"),
                    os.path.join(RESULTS, "recovery_journal.jsonl"))
    emit("fleet_crash_recovery", resume_ms * 1e3,
         f"clf_calls={resume_calls};decisions={len(decided)};"
         f"violations={violations}")
    assert resume_calls == 0, (
        f"resume classified {resume_calls} times; recovery must adopt "
        f"journaled decisions without re-classification")
    assert len(decided) > 0, "crash landed before any decision was journaled"
    assert violations == 0, (
        f"recovered fleet exceeded its power budget in {violations} "
        f"sustained windows (peak {sustained.max():.0f} W vs budget "
        f"{budget:.0f} W)")
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="short micro configuration for CI")
    ap.add_argument("--child", metavar="STORE",
                    help=argparse.SUPPRESS)   # internal crash-target mode
    args = ap.parse_args()
    enable_compilation_cache()
    if args.child:
        child(args.child, smoke=args.smoke)
        return
    print(json.dumps(run(smoke=args.smoke), indent=1))


if __name__ == "__main__":
    main()
