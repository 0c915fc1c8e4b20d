#!/usr/bin/env python3
"""Bring-up smoke test: the Minos served path on one TPU chip.

Drives the deployment of the full ``benchmarks/bench_fleet_scale.py`` run
through the session facade, with the profiling engine's spike histograms
counted on the device:

  * ``MinosSession.from_config`` over 32 v5e + 16 v5p + 16 v6e chips, zero
    variability, seed 7, a budget of 0.75 x the nameplate of the admitted
    jobs, the p99 quantile and the fleet-scale gates;
  * a reference library of 28 profiles built fresh from code into this
    script's own store, never read from an earlier run;
  * ``submit_many`` of ``fleet_job_mix(10_000, seed=11)`` with 0.4 s of
    telemetry per job in 256-sample chunks from fixed seeds, then ``run()``
    to every decision after the engine's shape buckets are warmed up.

The same deployment then runs on the host through the plain references —
one ``ProfileBuilder`` per job (``FleetCapController(engine="perjob")``)
and ``PowerAwareScheduler.pack`` — and every job's cap and the final placed
and deferred lists must match.  Re-simulating the placed jobs at their caps
must show zero sustained budget violations.

Prints one line per phase, then one JSON object as the last line.  Exits
non-zero, printing no result, when JAX finds no TPU: it never falls back to
the CPU.  Usage::

    python chip_smoke.py
"""
from __future__ import annotations

import json
import os
import shutil
import sys
import time
from itertools import zip_longest

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
STORE = os.path.join(ROOT, "results", "chip_smoke", "reference_store")
FLEET = {"tpu-v5e": 32, "tpu-v5p": 16, "tpu-v6e": 16}
N_JOBS = 10_000
BUDGET_FRACTION = 0.75
GATES = dict(min_confidence=0.2, min_fraction=0.1, min_spike_samples=50)
TELEMETRY_S = 0.4
CHUNK_SAMPLES = 256
SUSTAIN_WINDOW = 50
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def require_tpu():
    """The devices JAX found, or ``SystemExit`` when they are not TPUs."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU found (JAX platform "
            f"{devices[0].platform!r}); this smoke runs only on a TPU")
    return devices


class CompileCounter:
    """Counts backend compiles (and persistent-cache loads) with JAX's
    monitoring events."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.compiles += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1


def _sustained(agg: np.ndarray, window: int = SUSTAIN_WINDOW) -> np.ndarray:
    if len(agg) < window:
        return np.array([agg.mean()]) if len(agg) else np.zeros(1)
    return np.convolve(agg, np.ones(window) / window, mode="valid")


def _violations(placed, assigned, job_ids, seeds, budget):
    """Sustained (rolling-mean) samples of the re-simulated placed fleet
    above the budget: one simulation per (workload, chip model, cap)."""
    from repro.api import simulate
    group_chips: dict[tuple, int] = {}
    for (stream, _, dev), jid in zip(assigned, job_ids):
        plan = placed.get(jid)
        if plan is not None:
            key = (stream.name, dev.model, plan.cap)
            group_chips[key] = group_chips.get(key, 0) + plan.chips
    streams = {s.name: s for s, _, _ in assigned}
    models = {dev.model: dev.power_model() for _, _, dev in assigned}
    traces = [n * simulate(streams[name], cap, models[model],
                           seed=seeds[name],
                           target_duration=TELEMETRY_S).power_filtered
              for (name, model, cap), n in sorted(group_chips.items())]
    if traces:
        n = max(len(t) for t in traces)
        aggregate = np.sum([np.resize(t, n) for t in traces], axis=0)
    else:
        aggregate = np.zeros(1)
    sustained = _sustained(aggregate)
    return int(np.sum(sustained > budget)), float(sustained.max())


def _mismatches(a: list, b: list) -> list:
    return [(x, y) for x, y in zip_longest(a, b) if x != y]


def drive(n_jobs: int = N_JOBS, fleet=FLEET, store: str = STORE,
          library_duration: float = 3.0, compiles=None) -> dict:
    """Run the deployment through the session and through the references;
    returns every phase's figures.  ``compiles`` (a ``CompileCounter``)
    splits compiles into warm-up and drive; without one both read 0."""
    from repro.api import (DeviceInventory, FleetCapController,
                           FleetTelemetryMux, MinosSession, ReferenceLibrary,
                           TPUPowerModel, VariabilityModel,
                           build_reference_library, fleet_job_mix,
                           stream_telemetry)
    out: dict = {}

    def n_compiles() -> int:
        return compiles.compiles if compiles else 0

    t0 = time.perf_counter()
    lib = build_reference_library(TPUPowerModel(),
                                  target_duration=library_duration)
    shutil.rmtree(store, ignore_errors=True)
    lib.save(store)
    out["library"] = dict(profiles=len(lib),
                          seconds=time.perf_counter() - t0)

    # the deployment: round-robin placement over the seeded inventory, and
    # telemetry generated once per (workload, chip model) from fixed seeds
    inventory = DeviceInventory.generate(fleet, VariabilityModel.none(),
                                         seed=7)
    jobs = fleet_job_mix(n_jobs, seed=11)
    assigned = [(s, chips, inventory[i % len(inventory)])
                for i, (s, chips) in enumerate(jobs)]
    budget = BUDGET_FRACTION * sum(chips * dev.nameplate_w
                                   for _, chips, dev in assigned)
    seeds = {name: 500 + i for i, name in
             enumerate(sorted({s.name for s, _, _ in assigned}))}
    telemetry = {}
    for stream, _, dev in assigned:
        key = (stream.name, dev.model)
        if key not in telemetry:
            meta, chunks = stream_telemetry(
                stream, 1.0, dev.power_model(), seed=seeds[stream.name],
                target_duration=TELEMETRY_S, chunk_samples=CHUNK_SAMPLES)
            telemetry[key] = (meta, list(chunks))
    job_ids = [f"j{i:05d}:{s.name}" for i, (s, _, _) in enumerate(assigned)]
    sources = [telemetry[(s.name, dev.model)] for s, _, dev in assigned]

    session = MinosSession.from_config({
        "library": store, "devices": fleet, "variability": "none",
        "seed": 7, "budget_w": budget, "quantile": "p99", "gates": GATES})
    assert [d.device_id for d in session.inventory] \
        == [d.device_id for d in inventory]
    before, t0 = n_compiles(), time.perf_counter()
    shapes = session.engine.warmup(n_jobs, CHUNK_SAMPLES)
    out["warmup"] = dict(shapes=shapes, seconds=time.perf_counter() - t0,
                         compiles=n_compiles() - before)

    before, t0 = n_compiles(), time.perf_counter()
    handles = session.submit_many(sources, chips=[c for _, c, _ in assigned],
                                  job_ids=job_ids)
    report = session.run()
    engine = session.engine
    out["drive"] = dict(
        jobs=len(handles), seconds=time.perf_counter() - t0,
        device_calls=engine.device_calls,
        device_shapes=sorted(engine.device_shapes),
        compiles=n_compiles() - before)
    schedule = report.schedule
    out["decisions"] = dict(decided=len(report.decisions),
                            placed=len(schedule.placed),
                            deferred=len(schedule.deferred))
    n_viol, peak = _violations({p.job_id: p for p in schedule.placed},
                               assigned, job_ids, seeds, budget)
    out["budget"] = dict(violations=n_viol, peak_sustained_w=peak,
                         budget_w=budget)

    # the plain references on the host: one ProfileBuilder per job, one
    # full first-fit-decreasing pack at the end
    t0 = time.perf_counter()
    ref = FleetCapController(
        ReferenceLibrary.load(store), budget_w=budget,
        provision_quantile="p99", engine="perjob", repack="tick",
        packer="full", **GATES)
    ref.admit_many(dict(device=dev, meta=src[0], chips=chips, job_id=jid)
                   for (_, chips, dev), src, jid
                   in zip(assigned, sources, job_ids))
    mux = FleetTelemetryMux()
    for (_, _, dev), (meta, chunks), jid in zip(assigned, sources, job_ids):
        mux.add_job(jid, meta, chunks, device_id=dev.device_id)
    ticks = 0
    for batch in mux.ticks():
        ref.ingest_tick(batch)
        ticks += 1
    ref.finalize()
    ref_pack = ref.scheduler.pack(
        (j.plan for j in ref.jobs.values() if j.plan is not None), budget)
    caps = {jid: d.cap for jid, d in report.decisions.items()}
    ref_caps = {jid: job.decision.cap for jid, job in ref.jobs.items()
                if job.decision is not None}
    cap_diff = [(jid, caps.get(jid), ref_caps.get(jid)) for jid in job_ids
                if caps.get(jid) != ref_caps.get(jid)]
    placed_diff = _mismatches([(p.job_id, p.cap) for p in schedule.placed],
                              [(p.job_id, p.cap) for p in ref_pack.placed])
    deferred_diff = _mismatches(list(schedule.deferred),
                                list(ref_pack.deferred))
    out["drive"]["ticks"] = ticks
    out["reference"] = dict(
        seconds=time.perf_counter() - t0,
        cap_mismatches=len(cap_diff), first_cap_mismatches=cap_diff[:3],
        placement_mismatches=len(placed_diff) + len(deferred_diff),
        first_placement_mismatches=(placed_diff + deferred_diff)[:3])
    return out


def failures(out: dict, n_jobs: int) -> list[str]:
    """What makes the smoke fail, one message per broken expectation."""
    bad = []
    if out["decisions"]["decided"] != n_jobs:
        bad.append(f"only {out['decisions']['decided']}/{n_jobs} jobs "
                   f"decided")
    if out["drive"]["device_calls"] <= 0:
        bad.append("no device histogram call ran")
    if out["drive"]["compiles"]:
        bad.append(f"{out['drive']['compiles']} compiles inside the drive")
    if out["reference"]["cap_mismatches"]:
        bad.append(f"{out['reference']['cap_mismatches']} cap mismatches")
    if out["reference"]["placement_mismatches"]:
        bad.append(f"{out['reference']['placement_mismatches']} placement "
                   f"mismatches")
    if out["budget"]["violations"]:
        bad.append(f"{out['budget']['violations']} sustained budget "
                   f"violations")
    return bad


def main() -> int:
    devices = require_tpu()
    import jax
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.api import enable_compilation_cache
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind!r} "
          f"count={len(devices)} jax={jax.__version__}", flush=True)
    print(f"compile cache: {enable_compilation_cache()}", flush=True)
    compiles = CompileCounter()
    out = drive(compiles=compiles)
    lib, warm, run = out["library"], out["warmup"], out["drive"]
    dec, bud, ref = out["decisions"], out["budget"], out["reference"]
    print(f"library: {lib['profiles']} profiles built in "
          f"{lib['seconds']!r} s")
    print(f"compiles: {compiles.compiles} programs in "
          f"{compiles.seconds!r} s ({compiles.cache_hits} from the "
          f"persistent cache); warm-up: {warm['compiles']} over "
          f"{warm['shapes']} histogram shapes; drive: {run['compiles']}")
    print(f"ticks: {run['ticks']} mux ticks, {run['device_calls']} device "
          f"histogram calls over shapes {run['device_shapes']}")
    print(f"jobs: {dec['decided']} decided, {dec['placed']} placed, "
          f"{dec['deferred']} deferred")
    print(f"budget: {bud['violations']} sustained violations (peak "
          f"{bud['peak_sustained_w']!r} W, budget {bud['budget_w']!r} W)")
    print(f"reference: {ref['cap_mismatches']} cap mismatches "
          f"{ref['first_cap_mismatches']}, {ref['placement_mismatches']} "
          f"placement mismatches {ref['first_placement_mismatches']} "
          f"against per-job ProfileBuilder + pack()")
    print(f"smoke timing (not a benchmark metric): drive "
          f"{run['seconds']!r} s wall, warm-up {warm['seconds']!r} s, "
          f"reference {ref['seconds']!r} s", flush=True)
    bad = failures(out, N_JOBS)
    if bad:
        print("chip_smoke FAILED: " + "; ".join(bad), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
