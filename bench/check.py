"""Decide ``correct``: what the window produced against the plain reference.

Compared after the window has closed, on a sample of the decisions the
window made (drawn from the seed among the watched jobs, with the longest
trace always in it), and on the whole live population for the packing:

  * ``hist_gap``: summed absolute difference of the six committed spike
    histograms the engine holds for each sampled job still live at the
    close (a decided job's histograms stay as they were at its decision),
    against the reference's at the same sample count;
  * ``decision_gap``: sampled decisions whose chunk, bin size, power or
    utilisation neighbour, or cap differ from the reference's;
  * ``confidence_gap``: the largest confidence difference among the
    decisions that agree;
  * ``placement_gap``: jobs placed or deferred differently by the final
    packing than by the reference's first fit decreasing over the same
    decided plans;
  * ``violations``: sustained (50-sample mean) samples of the re-simulated
    placed fleet above the budget, which the configuration guarantees at 0;
  * ``window_compiles``: programs compiled inside the window (0), and
    ``device_calls``: device histogram calls in it (at least 1), with at
    least one decision sampled and one histogram compared.

Decisions are compared on the trace and device of the run they came from;
placements and power where the program binds each job now
(``FleetJob.device``), so that a job that migrated is held to where it
runs.  A configuration with a ``faults`` section adds, each with its limit
in the cell's check file:

  * ``failed_placements``: jobs whose plan (placed or deferred) binds
    them to a device that is down, after each ``fail_device`` and
    ``restore_device`` call and at the close;
  * ``migration_classify_calls``: classifier calls made inside
    ``fail_device`` and ``restore_device`` (counted as
    ``repro.core.classify.count_classifier_calls`` counts them): migration
    re-costs cached decisions and never classifies;
  * ``lost_runs``: streams that ended with no answer because the program
    never took back a run that a migration cut, and undecided live jobs
    whose engine holds another number of samples than the wire sent them
    in their current run;
  * ``failures`` and ``restarts`` in the window: at least 1 each.

A ``store`` section adds ``resume_gap``: after the window, with the store
neither closed nor flushed (a crash), ``MinosSession.resume`` rebuilds the
session; live jobs whose decision (cap and neighbours), device, or placed
or deferred status differ between the two, plus classifier calls made
during the resume.  Every acknowledged write has to be read back.
"""
from __future__ import annotations

import time
import traceback

import numpy as np

from bench import reference as ref
from bench.traffic import generator as gen

UPPER = ("hist_gap", "decision_gap", "confidence_gap", "placement_gap",
         "violations", "window_compiles")
FAULT_UPPER = ("failed_placements", "migration_classify_calls", "lost_runs")


def effective_tdp(device) -> float:
    return device.spec.tdp_w * device.spec.power_scale


def reference_library(cfg: dict, dtype=np.float64) -> ref.Library:
    """The reference profiles, made from the zoo's streams on the nominal
    chip with the library's seeds, through the benchmark's own generator."""
    from repro.telemetry.power_model import TPUPowerModel
    from repro.telemetry.workloads import reference_streams
    lib = cfg["library"]
    model = TPUPowerModel()
    dt, dur = float(cfg["telemetry"]["sample_dt_s"]), float(lib["profile_s"])
    profiles = []
    for i, s in enumerate(reference_streams()):
        runs, util = {}, None
        for j, f in enumerate(sorted(lib["freqs"])):
            ev = gen.event_trace(s, f, model, dt, dur)
            e = gen.energy_counter(ev, float(cfg["telemetry"]["noise"]),
                                   (int(lib["seed"]) + i) * 1009 + j)
            runs[f] = (e, ev.busy_ctr, ev.exec_time)
            util = (ev.app_dram_util, ev.app_sm_util)
        profiles.append(ref.build_profile(s.name, model.spec.tdp_w, util,
                                          runs, dt, dtype))
    return ref.Library(profiles, dtype)


def job_view(job) -> dict:
    t = job.tele
    return dict(energy=t.energy_ctr, busy=t.busy_ctr,
                n_samples=t.ev.n_samples, chunk_end=t.chunk_end,
                tdp=effective_tdp(t.device), name=t.stream.name,
                util=(t.ev.app_dram_util, t.ev.app_sm_util))


def engine_histograms(fleet, jid: str):
    """The committed spike histograms the engine holds for ``jid``, or
    ``None`` once the job has retired and its slot is gone."""
    fj = fleet.jobs.get(jid)
    if fj is None:
        return None
    b = fj.builder
    return {c: np.rint(b.spike_vector(c) * b.spike_count(c))
            for c in b.bin_sizes}


def program_decision(decision, hist) -> dict:
    sel = decision.selection
    return dict(n=int(decision.n_samples), early=bool(decision.early),
                bin_size=float(sel.bin_size),
                power_neighbor=sel.power_neighbor,
                util_neighbor=sel.util_neighbor, cap=float(decision.cap),
                confidence=float(decision.confidence),
                hist=None if hist is None else {
                    float(c): np.asarray(h, np.float64)
                    for c, h in hist.items()})


KEYS = ("n", "early", "bin_size", "power_neighbor", "util_neighbor", "cap")


def compare(got: list[dict], want: list[dict], want_hist: list[dict]):
    """(hist_gap, decision_gap, confidence_gap) of decisions ``got``
    against the reference's ``want``, with the reference's histograms at
    ``got``'s sample counts."""
    hist_gap, decision_gap, conf_gap = 0.0, 0, 0.0
    for g, w, wh in zip(got, want, want_hist):
        if g["hist"] is not None:
            hist_gap += sum(float(np.abs(g["hist"][c] - wh[c]).sum())
                            for c in ref.BIN_SIZES)
        if any(g[k] != w[k] for k in KEYS):
            decision_gap += 1
        else:
            conf_gap = max(conf_gap, abs(g["confidence"] - w["confidence"]))
    return hist_gap, decision_gap, conf_gap


def sample(watched: list, k: int, seed: int) -> list:
    """``k`` watched decisions drawn from the seed, the longest included."""
    if len(watched) <= k:
        return list(watched)
    rng = np.random.default_rng([seed, 2])
    longest = max(range(len(watched)),
                  key=lambda i: watched[i][1].n_samples)
    rest = [i for i in range(len(watched)) if i != longest]
    pick = rng.choice(len(rest), size=k - 1, replace=False)
    return [watched[longest]] + [watched[rest[i]] for i in sorted(pick)]


def decisions_check(cell, rec, lib: ref.Library, control: ref.Library | None
                    = None) -> dict:
    """hist, decision and confidence gaps of the sampled decisions.  With
    ``control`` (the reference in a lower precision), the control's own
    decisions on the same jobs stand in for the program's."""
    cfg = cell.cfg
    dt = float(cfg["telemetry"]["sample_dt_s"])
    picked = sample(rec.watched, int(cell.check["sample"]), cell.seed)
    got, want, want_hist = [], [], []
    for job, decision in picked:
        view = job_view(job)
        w = ref.decide(view, cfg["gates"], lib, dt, cfg["objective"])
        if control is None:
            g = program_decision(decision,
                                 engine_histograms(cell.fleet, job.jid))
        else:
            g = ref.decide(view, cfg["gates"], control, dt, cfg["objective"])
        got.append(g)
        want.append(w)
        want_hist.append(ref.committed_histograms(view, g["n"], g["early"],
                                                  lib, dt))
    hist_gap, decision_gap, conf_gap = compare(got, want, want_hist)
    return dict(sampled=len(picked),
                hist_sampled=sum(g["hist"] is not None for g in got),
                hist_gap=hist_gap,
                decision_gap=decision_gap, confidence_gap=conf_gap)


def placement_check(cell, lib: ref.Library) -> tuple[dict, list]:
    """The final packing against the reference's over the same plans."""
    fleet = cell.fleet
    quantile = cell.cfg["provision_quantile"]
    plans = []
    for jid, fj in fleet.jobs.items():
        if fj.decision is None:
            continue
        job = cell.jobs[jid]
        dev = fj.device
        plans.append(dict(job_id=jid, name=job.tele.stream.name,
                          device_id=dev.device_id, chips=job.chips,
                          cap=float(fj.decision.cap),
                          power_neighbor=fj.decision.selection.power_neighbor,
                          effective_tdp=effective_tdp(dev)))
    placed, deferred = ref.pack(plans, cell.budget_w, lib, quantile)
    last = fleet.repacks[-1] if fleet.repacks else None
    got_placed = getattr(last, "placed", None) if last is not None else []
    if got_placed is None:
        return dict(placement_gap=len(plans), plans=len(plans)), placed
    got = {p.job_id for p in got_placed}
    gap = len(got ^ set(placed))
    a, b = sorted(last.deferred), sorted(deferred)
    gap += sum(1 for x, y in zip(a, b) if x != y) + abs(len(a) - len(b))
    return dict(placement_gap=gap, plans=len(plans),
                placed=len(placed)), placed


def violations_check(cell, placed: list) -> dict:
    """Re-simulate the placed jobs at their caps, one trace per (workload,
    device spec, cap), and count sustained samples above the budget."""
    tele = cell.cfg["telemetry"]
    dt, dur = float(tele["sample_dt_s"]), float(tele["profile_s"])
    groups: dict = {}
    for jid in placed:
        job = cell.jobs[jid]
        fj = cell.fleet.jobs[jid]
        dev = fj.device
        key = (job.tele.stream.name, dev.model, dev.spec.perf_scale,
               dev.spec.power_scale, float(fj.decision.cap))
        g = groups.setdefault(key, [job.tele.stream, dev, 0])
        g[2] += fj.chips
    traces = []
    for i, (key, (stream, dev, chips)) in enumerate(sorted(
            groups.items(), key=lambda x: x[0])):
        ev = gen.event_trace(stream, key[-1], dev.power_model(), dt, dur)
        e = gen.energy_counter(ev, float(tele["noise"]), [cell.seed, 3, i])
        filt, busy = ref.filtered(e, ev.busy_ctr, ev.n_samples, dt,
                                  np.float64)
        traces.append((ref.trimmed(filt, busy, ev.n_samples), chips))
    n, peak = ref.sustained_violations(traces, cell.budget_w)
    return dict(violations=n, peak_sustained_w=peak, groups=len(groups))


def lost_runs(cell, rec) -> int:
    """Streams that ended unanswered, and undecided live jobs whose engine
    holds another number of samples than the wire sent in their run."""
    lost = rec.unanswered
    for jid, job in cell.jobs.items():
        if job.decided:
            continue
        sent = int(job.tele.chunk_end[job.k - 1]) if job.k else 0
        fj = cell.fleet.jobs[jid]
        lost += fj.needs_reprofile or fj.builder.n_ingested != sent
    return lost


def resume_check(cell) -> dict:
    """Resume the session from its store as a crash leaves it, and count
    the live jobs the resumed session holds otherwise, plus classifier
    calls made by the resume."""
    from repro.api import MinosSession
    from repro.core.classify import MinosClassifier, count_classifier_calls
    counts = []
    init = MinosClassifier.__init__

    def counted_init(self, *args, **kwargs):
        init(self, *args, **kwargs)
        counts.append(count_classifier_calls(self))

    MinosClassifier.__init__ = counted_init
    t = time.perf_counter()
    try:
        resumed = MinosSession.resume(cell.store_path, references=cell.lib)
    except Exception:
        # a store that cannot be resumed has lost every live job
        traceback.print_exc()
        return dict(resume_gap=len(cell.fleet.jobs) + 1,
                    resume_s=time.perf_counter() - t,
                    resume_classify_calls=None)
    finally:
        MinosClassifier.__init__ = init
    resume_s = time.perf_counter() - t
    calls = sum(c["n"] for c in counts)
    handles = resumed.jobs
    live, back = cell.fleet, resumed.report().schedule

    def statuses(schedule) -> dict:
        if schedule is None:
            return {}
        out = {p.job_id: "placed" for p in schedule.placed}
        out.update((jid, "deferred") for jid in schedule.deferred)
        return out

    def decision(d):
        if d is None:
            return None
        sel = d.selection
        return (float(d.cap), sel.power_neighbor, sel.util_neighbor)

    was = statuses(live.repacks[-1] if live.repacks else None)
    now = statuses(back)
    gap = len(set(handles) - set(live.jobs))
    for jid, fj in live.jobs.items():
        h = handles.get(jid)
        gap += h is None or (
            decision(fj.decision) != decision(h.decision(finalize=False))
            or fj.device.device_id != h.device.device_id
            or was.get(jid) != now.get(jid))
    resumed.store.close()
    return dict(resume_gap=gap + calls, resume_s=resume_s,
                resume_classify_calls=calls)


def run_checks(cell, rec, limits: dict, control_dtype=None
               ) -> tuple[bool, dict, dict]:
    """(correct, compared numbers with their limits, other figures).
    With ``control_dtype`` the reference in that precision stands in for
    the program's decisions: the control, which has to come out false."""
    resumed = resume_check(cell) if cell.store is not None else None
    lib = reference_library(cell.cfg)
    control = (None if control_dtype is None
               else reference_library(cell.cfg, control_dtype))
    dec = decisions_check(cell, rec, lib, control)
    pl, placed = placement_check(cell, lib)
    vio = violations_check(cell, placed)
    values = dict(hist_gap=dec["hist_gap"], decision_gap=dec["decision_gap"],
                  confidence_gap=dec["confidence_gap"],
                  placement_gap=pl["placement_gap"],
                  violations=vio["violations"],
                  window_compiles=rec.compiles)
    upper = list(UPPER)
    at_least = [("device_calls", rec.device_calls),
                ("sampled", dec["sampled"]),
                ("hist_sampled", dec["hist_sampled"])]
    other = dict(plans=pl["plans"], placed=pl.get("placed"),
                 peak_sustained_w=vio["peak_sustained_w"],
                 budget_w=cell.budget_w, groups=vio["groups"])
    if cell.faults is not None:
        values.update(
            failed_placements=cell.failed_placements + cell.plans_on_down(),
            migration_classify_calls=cell.fault_classify_calls,
            lost_runs=lost_runs(cell, rec))
        upper += FAULT_UPPER
        at_least += [("failures", rec.failures), ("restarts", rec.restarts)]
        other.update(down_at_close=len(cell.faults.failed))
    if resumed is not None:
        values["resume_gap"] = resumed["resume_gap"]
        upper.append("resume_gap")
        other.update(resume_s=resumed["resume_s"],
                     resume_classify_calls=resumed["resume_classify_calls"])
    checks = {}
    ok = True
    for name in upper:
        lim = float(limits[name])
        checks[name] = dict(value=values[name], limit=lim)
        ok &= values[name] <= lim
    for name, value in at_least:
        checks[name] = dict(value=value, limit=1, at_least=True)
        ok &= value >= 1
    return bool(ok), checks, other
