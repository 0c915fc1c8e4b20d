"""Online class discovery: novel production traffic grows the library.

The scenario mirrors the discovery subsystem's acceptance contract
(ISSUE PR 9).  A reference library is built from the micro zoo; the
``novel_streams`` families (encoder-decoder, SSM, MoE, hybrid prefills —
deliberately absent from the library) then arrive as production jobs:

  * **baseline** — in-library jobs: cap agreement against full-profile
    ground truth (``truth_selection``) and mean decided fraction;
  * **novel_before** — the novel families against the shipped library:
    the same metrics, pre-discovery;
  * **discovery** — the same novel traffic quarantined (low margin
    confidence), re-clustered, shadow-evaluated, and promoted; the live
    fleet classifier is spied across the swap — **zero calls** asserted;
  * **novel_after** — fresh arrivals of the same families against the
    promoted library: cap agreement must be within noise of the
    in-library baseline, and the arrivals must classify to the
    discovered classes;
  * **resume** — the promotion replayed from the durable store with zero
    classifier queries;
  * **discovery-off** — a session without the ``discovery`` key is
    byte-identical run-to-run, and a quarantine-only discovery session
    changes none of its decisions (inert-by-default) — asserted.

Writes ``results/discovery.json``; ``--smoke`` runs 2 novel families
with shorter profiles for CI.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import tempfile
import time

from benchmarks.common import RESULTS, emit
from repro.api import (MinosSession, ReferenceLibrary, TPUPowerModel,
                       count_classifier_calls, enable_compilation_cache,
                       micro_gemm, micro_idle_burst,
                       micro_spmv_compute, micro_spmv_memory, micro_stencil,
                       novel_streams, resolve_objective,
                       stream_profile_workload, stream_profiler,
                       stream_telemetry, to_json, truth_selection)

GATES = dict(min_confidence=0.2, min_fraction=0.1, min_spike_samples=50)
# margin confidence measures ambiguity, not wrongness: a novel family can
# match an existing class decisively-but-wrongly at ~0.7-0.9, so the
# quarantine threshold sits above that band
DISCOVERY = {"quarantine_below": 0.9, "min_cluster": 3,
             "recluster_every": 1000, "promote_agreement": 0.5,
             "cluster_distance": 0.5}
FREQS = (0.6, 0.8, 1.0)
SEEDS_PER_FAMILY = 3             # arrivals per novel family (>= min_cluster)


def _setup(smoke: bool):
    model = TPUPowerModel()
    target_duration = 0.5 if smoke else 1.0
    library_streams = [micro_gemm(), micro_spmv_memory(),
                       micro_spmv_compute(), micro_idle_burst(),
                       micro_stencil()]
    novel = novel_streams()[:2 if smoke else 4]
    lib = ReferenceLibrary(
        (stream_profile_workload(s, model, FREQS, model.spec.tdp_w, seed=i,
                                 target_duration=target_duration)
         for i, s in enumerate(library_streams)),
        built_on=model.spec.name)
    # full-profile ground truth for the novel families: what a production
    # profiling run would measure, and what the shadow evaluator scores
    # candidates against
    truth = {s.name: stream_profile_workload(
        s, model, FREQS, model.spec.tdp_w, seed=50 + i,
        target_duration=target_duration)
        for i, s in enumerate(novel)}
    return model, lib, library_streams, novel, truth, target_duration


def _submit_all(session, streams, model, seeds, target_duration, chips=2):
    """Run one job per (stream, seed) pair; returns the decided handles."""
    handles = []
    for i, stream in enumerate(streams):
        for j in seeds:
            meta = stream_telemetry(stream, 1.0, model,
                                    seed=1000 * (i + 1) + j,
                                    target_duration=target_duration)
            h = session.submit(meta, chips=chips)
            h.run()
            handles.append(h)
    return handles


def _score(handles, truth_by_name, objective) -> dict:
    """Cap agreement vs full-profile ground truth + decision stats."""
    hits, fracs, confs = 0, [], []
    for h in handles:
        d = h.decision()
        truth_cap = objective.cap(truth_selection(
            truth_by_name[h.meta.name], d.selection.bin_size))
        hits += int(d.cap == truth_cap)
        fracs.append(d.fraction)
        confs.append(d.confidence)
    n = len(handles)
    return {"n_jobs": n,
            "cap_agreement": round(hits / n, 4) if n else 0.0,
            "mean_fraction": round(sum(fracs) / n, 4) if n else 0.0,
            "mean_confidence": round(sum(confs) / n, 4) if n else 0.0}


def _decisions(handles) -> list[tuple]:
    return [(d.target, d.cap, d.early, round(d.fraction, 6))
            for d in (h.decision() for h in handles)]


def run(smoke: bool = False) -> dict:
    model, lib, library_streams, novel, truth, target_duration = _setup(smoke)
    objective = resolve_objective("powercentric")
    seeds = range(SEEDS_PER_FAMILY)
    truth_in_library = {p.name: p for p in lib}

    # -- baseline: in-library traffic ------------------------------------
    plain = MinosSession(lib, **GATES)
    baseline = _score(_submit_all(plain, library_streams, model, seeds,
                                  target_duration), truth_in_library,
                      objective)

    # -- novel families against the shipped library ----------------------
    before_session = MinosSession(lib, **GATES)
    novel_before = _score(_submit_all(before_session, novel, model, seeds,
                                      target_duration), truth, objective)

    # -- the discovery loop, durable, with the live classifier spied -----
    store = os.path.join(tempfile.mkdtemp(prefix="minos-discovery-"),
                         "store")
    session = MinosSession(lib, store=store, discovery=DISCOVERY, **GATES)
    _submit_all(session, novel, model, seeds, target_duration)
    quarantined = len(session.discovery.pool)
    session.discovery.profiler = stream_profiler(
        novel, model, FREQS, model.spec.tdp_w,
        target_duration=target_duration)
    live_calls = count_classifier_calls(session._fleet.clf)
    t0 = time.perf_counter()
    promo = session.discover(force=True)
    swap_ms = (time.perf_counter() - t0) * 1e3
    swap_calls = live_calls["n"]
    promoted = promo["classes"] if promo else []

    # -- fresh arrivals of the same families, post-promotion -------------
    after_handles = _submit_all(session, novel, model,
                                [100 + s for s in seeds], target_duration)
    novel_after = _score(after_handles, truth, objective)
    absorbed = sum(1 for h in after_handles
                   if h.decision().selection.power_neighbor in promoted)

    # -- crash-resume across the version bump: zero classifier queries ---
    # every classifier ANY library mints during resume is spied: discovery
    # resume rebuilds versioned libraries, so the spy must cover them all
    session.close()
    spies = []
    orig_classifier = ReferenceLibrary.classifier

    def spied_classifier(self, *a, **k):
        clf = orig_classifier(self, *a, **k)
        spies.append(count_classifier_calls(clf))
        return clf

    ReferenceLibrary.classifier = spied_classifier
    try:
        t0 = time.perf_counter()
        resumed = MinosSession.resume(store, references=lib)
        resume_ms = (time.perf_counter() - t0) * 1e3
    finally:
        ReferenceLibrary.classifier = orig_classifier
    resume_calls = sum(s["n"] for s in spies)
    resumed_version = (resumed.discovery.version
                       if resumed.discovery else 1)
    resumed.close()
    shutil.rmtree(os.path.dirname(store), ignore_errors=True)

    # -- inert-by-default: no discovery key => byte-identical ------------
    def _plain_report():
        s = MinosSession(lib, **GATES)
        handles = _submit_all(s, library_streams, model, seeds,
                              target_duration)
        return to_json(s.report()), _decisions(handles)

    rep_a, dec_a = _plain_report()
    rep_b, dec_b = _plain_report()
    quarantine_only = MinosSession(lib, discovery=DISCOVERY, **GATES)
    dec_c = _decisions(_submit_all(quarantine_only, library_streams, model,
                                   seeds, target_duration))
    discovery_off_identical = (rep_a == rep_b and dec_a == dec_b
                               and dec_a == dec_c)

    out = {
        "config": {"smoke": smoke, "novel_families": [s.name for s in novel],
                   "seeds_per_family": SEEDS_PER_FAMILY,
                   "discovery": DISCOVERY},
        "baseline": baseline,
        "novel_before": novel_before,
        "novel_after": novel_after,
        "quarantined": quarantined,
        "promoted": promoted,
        "absorbed_by_promoted": absorbed,
        "swap_latency_ms": round(swap_ms, 3),
        "swap_classifier_calls": swap_calls,
        "resume_latency_ms": round(resume_ms, 3),
        "resume_classifier_calls": resume_calls,
        "resumed_library_version": resumed_version,
        "discovery_off_identical": bool(discovery_off_identical),
    }
    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(RESULTS, "discovery.json"), "w") as f:
        json.dump(out, f, indent=1)
    emit("class_discovery", swap_ms * 1e3,
         f"promoted={len(promoted)};agree_after="
         f"{novel_after['cap_agreement']};swap_calls={swap_calls}")
    assert promoted, (
        f"no class promoted from {quarantined} quarantined novel arrivals")
    assert swap_calls == 0, (
        f"library swap made {swap_calls} live classifier calls; adoption "
        f"must be zero-call")
    assert resume_calls == 0, (
        f"resume across the version bump made {resume_calls} classifier "
        f"calls; discovery records must replay without re-classification")
    assert resumed_version >= 2, (
        f"resume came back at library version {resumed_version}; the "
        f"journaled promotion was not re-adopted")
    assert discovery_off_identical, (
        "a session without the discovery key is not byte-identical "
        "run-to-run, or a quarantine-only session changed decisions")
    assert novel_after["cap_agreement"] >= baseline["cap_agreement"] - 0.25, (
        f"post-promotion novel agreement {novel_after['cap_agreement']} "
        f"fell more than 0.25 below the in-library baseline "
        f"{baseline['cap_agreement']}")
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="2 novel families, shorter profiles (CI)")
    args = ap.parse_args()
    enable_compilation_cache()
    out = run(smoke=args.smoke)
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
