"""Fleet-scale stress: 10k+ concurrent jobs through the batched columnar
profiling engine — one stacked pass over the whole fleet's telemetry.

The PR 3 fleet loop stepped jobs one at a time through per-job
``ProfileBuilder``s and topped out around 300–370 jobs/s; production GPU
fleets run thousands of concurrent jobs (arXiv:2502.18680).  This bench
admits a serving-weighted 10k-job mix onto a zero-variability three-
generation inventory and drains the multiplexed feed through
``FleetCapController``: every mux tick advances all live jobs in one
``BatchProfileEngine.ingest_batch`` columnar pass, and every landed
decision re-packs incrementally.

Telemetry is pre-generated once per distinct (workload, chip model) pair
and shared across jobs — chunks are immutable, so 10k builders can read the
same arrays; generation cost is excluded from the timed region (the bench
measures the *profiling engine*, not the simulator).

Emits one ``emit()`` row and writes ``results/fleet_scale.json``:
  * ``jobs_per_s``          — admitted jobs / wall-clock of admit+run, best
    of N identical attempts (the drive is deterministic: every attempt
    lands the same decisions, so the fastest attempt is the engine and the
    rest is co-tenant scheduler noise);
  * ``budget_violations``   — sustained (50-sample rolling mean) aggregate
    samples above the budget, from per-group ground-truth re-simulation —
    expected **0**;
  * ``clf_calls_on_repack`` — classifier invocations triggered by a
    post-run ``set_budget`` re-pack — expected **0** (cached plans only).

ISSUE 8 splits the control-plane cost out of the aggregate number:
  * ``admit_jobs_per_s``    — bulk-admission rate through ONE
    ``FleetCapController.admit_many`` call (validate whole batch, one
    coalesced journal flush), with its own floor;
  * ``repack``              — a replay of the drained plan population
    through the maintained ``IncrementalPacker`` vs a from-scratch
    ``pack()`` per control-plane event (the pre-ISSUE-8 cost model):
    total wall-clock for both, the speedup (floored), and a byte-identity
    check that the maintained placement equals the full pack's.

``--smoke`` runs a 2 000-job micro-zoo configuration with a conservative
throughput floor for CI; the full run asserts >= 10 000 concurrent jobs at
>= 3 500 jobs/s (>= 10x the PR 3 per-job loop) and a >= 10x repack-cost
reduction.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

from benchmarks.common import RESULTS, emit, reference_library
from repro.api import (DeviceInventory, ReferenceLibrary, TPUPowerModel,
                       VariabilityModel, count_classifier_calls,
                       enable_compilation_cache, fleet_job_mix, micro_gemm,
                       micro_idle_burst, micro_spmv_compute,
                       micro_spmv_memory, micro_stencil, simulate,
                       stream_profile_workload, stream_telemetry)
from repro.fleet import FleetCapController, FleetTelemetryMux

SUSTAIN_WINDOW = 50              # samples for the sustained rolling mean
BUDGET_FRACTION = 0.75           # of nameplate: the oversubscription target
GATES = dict(min_confidence=0.2, min_fraction=0.1, min_spike_samples=50)


def _sustained(agg: np.ndarray, window: int = SUSTAIN_WINDOW) -> np.ndarray:
    if len(agg) < window:
        return np.array([agg.mean()]) if len(agg) else np.zeros(1)
    kernel = np.ones(window) / window
    return np.convolve(agg, kernel, mode="valid")


def _repack_microbench(scheduler, plans, budget: float):
    """Replay the drained population as a control-plane event stream — one
    admission per plan plus a budget squeeze-and-release — through the
    maintained packer and through a from-scratch ``pack()`` per event (what
    every repack cost before the incremental path).  Both sides produce a
    repack answer after every event — the packer's deferred re-flow is
    forced by the per-event ``stats()`` read, so the comparison stays
    apples-to-apples.  Returns the two wall-clocks and both final
    placements for the byte-identity check."""
    plans = list(plans)
    t0 = time.perf_counter()
    packer = scheduler.packer(budget)
    for plan in plans:
        packer.insert(plan)
        packer.stats()
    packer.set_budget(budget * 0.9)
    packer.stats()
    packer.set_budget(budget)
    t_incremental = time.perf_counter() - t0
    incremental = packer.result()

    t0 = time.perf_counter()
    live = []
    for plan in plans:
        live.append(plan)
        full = scheduler.pack(live, budget)
    scheduler.pack(live, budget * 0.9)
    full = scheduler.pack(live, budget)
    t_full = time.perf_counter() - t0
    return t_incremental, t_full, incremental, full


def run(smoke: bool = False) -> dict:
    if smoke:
        counts = {"tpu-v5e": 4, "tpu-v5p": 2}
        streams = [micro_gemm(), micro_spmv_memory(), micro_spmv_compute(),
                   micro_idle_burst(), micro_stencil()]
        model = TPUPowerModel()
        lib = ReferenceLibrary(
            (stream_profile_workload(s, model, (0.6, 0.8, 1.0),
                                     model.spec.tdp_w, seed=i,
                                     target_duration=1.0)
             for i, s in enumerate(streams)),
            built_on=model.spec.name)
        jobs = [(streams[i % len(streams)], 32) for i in range(2_000)]
        floor_jobs_per_s = 500.0
        min_concurrent = 2_000
    else:
        counts = {"tpu-v5e": 32, "tpu-v5p": 16, "tpu-v6e": 16}
        lib = reference_library()
        jobs = fleet_job_mix(10_000, seed=11)
        floor_jobs_per_s = 3_500.0
        min_concurrent = 10_000
    floor_admit_jobs_per_s = 3_000.0 if smoke else 5_000.0
    floor_repack_speedup = 5.0 if smoke else 10.0
    target_duration = 0.4

    # zero variability: devices of one model share a power frame, so
    # telemetry and ground truth cache per (workload, chip model)
    inventory = DeviceInventory.generate(counts, VariabilityModel.none(),
                                         seed=7)
    assigned = [(s, chips, inventory[i % len(inventory)])
                for i, (s, chips) in enumerate(jobs)]
    nameplate = sum(chips * dev.nameplate_w for _, chips, dev in assigned)
    budget = BUDGET_FRACTION * nameplate

    # pre-generate each distinct (workload, model) telemetry stream ONCE;
    # chunks are immutable, so every job of that pair shares the arrays
    seeds = {name: 500 + i
             for i, name in enumerate(sorted({s.name for s, _, _ in
                                              assigned}))}
    telemetry = {}
    for stream, _, dev in assigned:
        key = (stream.name, dev.model)
        if key not in telemetry:
            meta, chunks = stream_telemetry(
                stream, 1.0, dev.power_model(), seed=seeds[stream.name],
                target_duration=target_duration, chunk_samples=256)
            telemetry[key] = (meta, list(chunks))

    # best-of-N attempts: the fleet drive is fully deterministic (same
    # streams, same seeds — every attempt lands the identical decisions),
    # so the fastest wall-clock is the engine's throughput and the slower
    # attempts are co-tenant scheduler noise
    attempts = 2 if smoke else 3
    best = None
    for _ in range(attempts):
        fleet = FleetCapController(lib, budget_w=budget,
                                   provision_quantile="p99", **GATES)
        mux = FleetTelemetryMux()
        t0 = time.perf_counter()
        # bulk admission: the whole fleet lands through ONE validated call
        job_ids = fleet.admit_many(
            dict(device=dev, meta=telemetry[(stream.name, dev.model)][0],
                 chips=chips, job_id=f"j{i:05d}:{stream.name}")
            for i, (stream, chips, dev) in enumerate(assigned))
        t_admit = time.perf_counter() - t0
        for (stream, chips, dev), job_id in zip(assigned, job_ids):
            meta, chunks = telemetry[(stream.name, dev.model)]
            mux.add_job(job_id, meta, chunks, device_id=dev.device_id)
        result = fleet.run(mux)
        elapsed = time.perf_counter() - t0
        if best is None or elapsed < best[0]:
            best = (elapsed, t_admit, fleet, result)
    elapsed, t_admit, fleet, result = best
    jobs_per_s = len(assigned) / elapsed
    admit_jobs_per_s = len(assigned) / t_admit
    drive_repack_s = fleet.repack_s          # incremental path, whole drive

    # repacks must never re-classify: cached JobPlans only
    calls = count_classifier_calls(fleet.clf)
    fleet.set_budget(budget * 0.9)
    fleet.set_budget(budget)
    clf_calls_on_repack = calls["n"]
    final = fleet.repacks[-1]

    # ground truth: one re-simulation per (workload, model, cap) group at
    # the group's decided cap, weighted by its total placed chips
    placed = {p.job_id: p for p in final.placed}
    group_chips: dict[tuple, int] = {}
    for i, (stream, chips, dev) in enumerate(assigned):
        plan = placed.get(f"j{i:05d}:{stream.name}")
        if plan is None:
            continue                       # deferred: draws no power
        key = (stream.name, dev.model, plan.cap)
        group_chips[key] = group_chips.get(key, 0) + plan.chips
    sim_streams = {s.name: s for s, _, _ in assigned}
    sim_models = {dev.model: dev.power_model() for _, _, dev in assigned}
    traces = [n_chips * simulate(sim_streams[name], cap, sim_models[model],
                                 seed=seeds[name],
                                 target_duration=target_duration
                                 ).power_filtered
              for (name, model, cap), n_chips in sorted(group_chips.items())]
    if traces:
        n = max(len(t) for t in traces)
        aggregate = np.sum([np.resize(t, n) for t in traces], axis=0)
    else:
        aggregate = np.zeros(1)
    sustained = _sustained(aggregate)
    violations = int(np.sum(sustained > budget))

    # repack-cost split: maintained packer vs full pack per event over the
    # drained population, plus the byte-identity bar the tentpole promises
    decided_plans = [j.plan for j in fleet.jobs.values()
                     if j.plan is not None]
    t_inc, t_full, inc_res, full_res = _repack_microbench(
        fleet.scheduler, decided_plans, budget)
    repack_speedup = t_full / t_inc if t_inc > 0 else float("inf")
    packs_identical = (
        [p.job_id for p in inc_res.placed]
        == [p.job_id for p in full_res.placed]
        and inc_res.deferred == full_res.deferred)

    engine = fleet.engine
    slot_bytes = sum(h.itemsize * h.shape[1] for h in engine._hist.values())
    out = {
        "config": {
            "smoke": smoke,
            "devices": {m: len(inventory.by_model(m))
                        for m in inventory.models},
            "n_jobs": len(assigned),
            "chunk_samples": 256,
            "budget_w": round(budget, 1),
            "budget_fraction_of_nameplate": BUDGET_FRACTION,
            "attempts": attempts,
        },
        "jobs_per_s": round(jobs_per_s, 1),
        "admit_jobs_per_s": round(admit_jobs_per_s, 1),
        "admit_s": round(t_admit, 3),
        "run_s": round(elapsed - t_admit, 3),
        "repack": {
            "events": len(decided_plans) + 2,
            "incremental_s": round(t_inc, 4),
            "full_s": round(t_full, 4),
            "speedup": round(repack_speedup, 1),
            "byte_identical": packs_identical,
            "drive_repack_s": round(drive_repack_s, 4),
        },
        "early_decisions": result.early_decisions,
        "decisions": len(result.decisions),
        "repacks": result.repacks,
        "chunks_dropped": result.chunks_dropped,
        "placed": len(final.placed),
        "deferred": len(final.deferred),
        "planned_power_w": round(final.planned_power_w, 1),
        "headroom_reclaimed_w": round(final.headroom_reclaimed_w, 1),
        "clf_calls_on_repack": clf_calls_on_repack,
        "budget_violations": violations,
        "peak_sustained_w": round(float(sustained.max()), 1),
        "engine_slots": engine.capacity,
        "hist_bytes_per_slot": slot_bytes,
    }
    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(RESULTS, "fleet_scale.json"), "w") as f:
        json.dump(out, f, indent=1)
    emit("fleet_scale_batched", elapsed * 1e6,
         f"jobs={len(assigned)};jobs/s={jobs_per_s:.0f};"
         f"admit/s={admit_jobs_per_s:.0f};repack_x={repack_speedup:.0f};"
         f"violations={violations};clf_on_repack={clf_calls_on_repack}")
    assert len(assigned) >= min_concurrent
    assert len(result.decisions) == len(assigned), (
        f"only {len(result.decisions)}/{len(assigned)} jobs decided")
    assert clf_calls_on_repack == 0, (
        f"re-pack re-classified {clf_calls_on_repack} times")
    assert violations == 0, (
        f"fleet exceeded its power budget in {violations} sustained windows "
        f"(peak {sustained.max():.0f} W vs budget {budget:.0f} W)")
    assert jobs_per_s >= floor_jobs_per_s, (
        f"throughput regression: {jobs_per_s:.0f} jobs/s < floor "
        f"{floor_jobs_per_s:.0f}")
    assert admit_jobs_per_s >= floor_admit_jobs_per_s, (
        f"bulk-admission regression: {admit_jobs_per_s:.0f} jobs/s < floor "
        f"{floor_admit_jobs_per_s:.0f}")
    assert packs_identical, (
        "incremental packer diverged from the full pack on the drained "
        "population")
    assert repack_speedup >= floor_repack_speedup, (
        f"repack-cost regression: incremental path is only "
        f"{repack_speedup:.1f}x cheaper than full packs (floor "
        f"{floor_repack_speedup:.0f}x)")
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="2k-job micro-zoo configuration for CI")
    args = ap.parse_args()
    enable_compilation_cache()
    print(json.dumps(run(smoke=args.smoke), indent=1))


if __name__ == "__main__":
    main()
