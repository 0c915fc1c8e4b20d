"""Plain reference of what the served path decides, for the ``correct`` check.

Straightforward NumPy over whole arrays, written from the paper's
description (Minos, arXiv:2604.03591, sections 4.1 and 4.3) and imports
nothing of the program:

  * profile: power from the energy counter (de/dt), EMA with alpha 0.5
    (``scipy.signal.lfilter``, one pass, seeded with the first sample), idle
    trim to [first busy, last busy], spike histograms of P/TDP >= 0.5 in
    bins of width c over [0.5, 2.0);
  * the online gate: committed spikes (samples in whole 256-sample filter
    blocks, up to the last busy sample among them) >= ``min_spike_samples``,
    ingested fraction >= ``min_fraction``, margin confidence 1 - d1/d2 >=
    ``min_confidence``, tried after every chunk, else decided at stream end;
  * Algorithm 1: per bin size the nearest reference by cosine distance on
    spike vectors (same-name references excluded), the bin size whose
    neighbour's p90 is nearest the target's, the utilisation neighbour by
    Euclidean distance, and the power-centric cap (highest frequency whose
    neighbour p90 stays under 1.3 x TDP);
  * packing: per-chip need = the neighbour's provisioning quantile at the
    nearest profiled frequency x the device's effective TDP, placed first
    fit decreasing by (-need, name, device, job) in exact rational sums.

``dtype`` selects the precision of every float step: float64 is the
reference, float32 the control that a correct check must refuse.
"""
from __future__ import annotations

from dataclasses import dataclass
from fractions import Fraction

import numpy as np
from scipy.signal import lfilter

SPIKE_LO, SPIKE_HI = 0.5, 2.0
ALPHA = 0.5
BLOCK = 256                       # samples per committed filter block
BIN_SIZES = (0.05, 0.1, 0.15, 0.2, 0.25, 0.5)
POWER_BOUND = 1.3


def n_bins(c: float) -> int:
    return int(round((SPIKE_HI - SPIKE_LO) / c))


def ema(p: np.ndarray) -> np.ndarray:
    """out_0 = p_0, out_i = alpha p_i + (1 - alpha) out_{i-1}."""
    if not len(p):
        return p
    dt = p.dtype.type
    out, _ = lfilter([dt(ALPHA)], [dt(1), dt(ALPHA - 1)], p,
                     zi=[dt(1 - ALPHA) * p[0]])
    return out


def filtered(energy_ctr, busy_ctr, n: int, sample_dt: float, dtype):
    """EMA-filtered power and busy flags of the first ``n`` samples."""
    e = np.asarray(energy_ctr[:n + 1], dtype)
    p = np.diff(e) / dtype(sample_dt)
    busy = np.diff(np.asarray(busy_ctr[:n + 1], np.float64)) > 0
    return ema(p), busy


def trimmed(filt, busy, end: int):
    """Samples from the first busy one to the last busy one before ``end``."""
    nz = np.nonzero(busy[:end])[0]
    if not len(nz):
        return filt[:0]
    return filt[nz[0]:nz[-1] + 1]


def histogram(trace, tdp: float, c: float) -> np.ndarray:
    dt = trace.dtype.type
    r = trace / dt(tdp)
    r = r[r >= dt(SPIKE_LO)]
    idx = np.clip(((r - dt(SPIKE_LO)) / dt(c)).astype(np.int64), 0,
                  n_bins(c) - 1)
    return np.bincount(idx, minlength=n_bins(c)).astype(np.float64)


def histograms(trace, tdp: float) -> dict:
    return {c: histogram(trace, tdp, c) for c in BIN_SIZES}


@dataclass
class Profile:
    """One reference-library workload as the reference sees it."""
    name: str
    trace: np.ndarray             # filtered, trimmed, at the top frequency
    tdp: float
    util: tuple                   # (dram, sm)
    p90: dict                     # freq -> p90 / tdp
    p99: dict
    exec_time: dict               # freq -> seconds per iteration


def build_profile(name: str, tdp: float, util, runs: dict,
                  sample_dt: float, dtype=np.float64) -> Profile:
    """``runs``: freq -> (energy_ctr, busy_ctr, exec_time) of one full
    profiling run per frequency."""
    p90, p99, ex, traces = {}, {}, {}, {}
    for f in sorted(runs):
        e, b, exec_time = runs[f]
        n = len(e) - 1
        filt, busy = filtered(e, b, n, sample_dt, dtype)
        tr = trimmed(filt, busy, n)
        traces[f] = tr
        p90[f] = quantile(tr, tdp, 90)
        p99[f] = quantile(tr, tdp, 99)
        ex[f] = exec_time
    return Profile(name, traces[max(runs)], tdp, tuple(util), p90, p99, ex)


def quantile(trace, tdp: float, q: float) -> float:
    if not len(trace):
        return 0.0
    return float(np.percentile(trace, q) / trace.dtype.type(tdp))


class Library:
    """The reference profiles with their spike vectors per bin size."""

    def __init__(self, profiles, dtype=np.float64):
        self.profiles = list(profiles)
        self.names = np.array([p.name for p in self.profiles])
        self.by_name = {p.name: p for p in self.profiles}
        self.dtype = dtype
        self.vecs = {c: np.stack([unit(histogram(np.asarray(p.trace, dtype),
                                                 p.tdp, c), dtype)
                                  for p in self.profiles])
                     for c in BIN_SIZES}
        self.p90_trace = np.array([quantile(np.asarray(p.trace, dtype),
                                            p.tdp, 90)
                                   for p in self.profiles])
        self.utils = np.array([p.util for p in self.profiles], np.float64)


def unit(h, dtype) -> np.ndarray:
    h = np.asarray(h, dtype)
    n = np.sqrt(np.sum(h * h))
    return h / n if n > 0 else h


def cap_power_centric(ref: Profile) -> float:
    for f in sorted(ref.p90, reverse=True):
        if ref.p90[f] < POWER_BOUND:
            return f
    return min(ref.p90)


def cap_perf_centric(ref: Profile, bound: float = 0.05) -> float:
    base = ref.exec_time[max(ref.exec_time)]
    for f in sorted(ref.exec_time):
        if ref.exec_time[f] / base - 1.0 <= bound:
            return f
    return max(ref.exec_time)


def classify(trace, tdp: float, name: str, util, lib: Library,
             objective: str = "powercentric") -> dict:
    """Algorithm 1 with the margin confidence, on one (partial) profile."""
    dt = lib.dtype
    trace = np.asarray(trace, dt)
    own = lib.names == name
    p_t = quantile(trace, tdp, 90)
    best = None
    for c in BIN_SIZES:
        h = histogram(trace, tdp, c)
        v = unit(h / h.sum() if h.sum() > 0 else h, dt)
        cos = lib.vecs[c] @ v
        d = 1.0 - np.clip(cos, -1.0, 1.0)
        if not np.any(v):
            d[:] = 1.0
        d[~np.any(lib.vecs[c], axis=1)] = 1.0
        d = np.where(own, np.inf, d)
        j = int(np.argmin(d))
        err = abs(p_t - lib.p90_trace[j])
        if best is None or err < best[0]:
            best = (err, c, j, d)
    _, c, j, d = best
    d1 = d[j]
    d2 = np.partition(d, 1)[1] if len(d) > 1 else np.inf
    if d2 == 0.0:
        conf = 0.0
    elif d2 == np.inf:
        conf = 1.0
    else:
        conf = max(0.0, 1.0 - float(d1) / float(d2))
    du = np.sqrt(np.sum((lib.utils - np.asarray(util, np.float64)) ** 2,
                        axis=1))
    u = int(np.argmin(np.where(own, np.inf, du)))
    pwr, utl = lib.profiles[j], lib.profiles[u]
    f_pwr, f_perf = cap_power_centric(pwr), cap_perf_centric(utl)
    return dict(bin_size=c, power_neighbor=pwr.name, util_neighbor=utl.name,
                cap=f_pwr if objective == "powercentric" else f_perf,
                confidence=conf)


def decide(job, gates: dict, lib: Library, sample_dt: float,
           objective: str = "powercentric") -> dict:
    """The decision the served path owes one job: tried after every chunk
    (``job["chunk_end"]``), else made from the whole trace at its end.
    Returns the chunk end it decided at, the classification and the
    committed spike histograms there."""
    dt = lib.dtype
    n_all = int(job["n_samples"])
    filt, busy = filtered(job["energy"], job["busy"], n_all, sample_dt, dt)
    tdp, name, util = job["tdp"], job["name"], job["util"]
    for n in job["chunk_end"]:
        n = int(n)
        committed = trimmed(filt[:n], busy[:n], (n // BLOCK) * BLOCK)
        r = committed / dt(tdp)
        if int(np.sum(r >= dt(SPIKE_LO))) < gates["min_spike_samples"]:
            continue
        if n / max(n_all, 1) < gates["min_fraction"]:
            continue
        # the filter of a prefix is the prefix of the filter
        snap = trimmed(filt[:n], busy[:n], n)
        if not len(snap):
            continue
        out = classify(snap, tdp, name, util, lib, objective)
        if out["confidence"] >= gates["min_confidence"]:
            return dict(out, n=n, early=True,
                        hist=histograms(committed, tdp))
    full = trimmed(filt, busy, n_all)
    out = classify(full, tdp, name, util, lib, objective)
    return dict(out, n=n_all, early=False, hist=histograms(full, tdp))


def committed_histograms(job, n: int, early: bool, lib: Library,
                         sample_dt: float) -> dict:
    """The committed spike histograms after ``n`` samples (all of them
    when the decision came at stream end)."""
    dt = lib.dtype
    filt, busy = filtered(job["energy"], job["busy"], n, sample_dt, dt)
    end = (n // BLOCK) * BLOCK if early else n
    return histograms(trimmed(filt, busy, end), job["tdp"])


def need(plan: dict, lib: Library, quantile_name: str = "p99") -> float:
    """Watts one plan reserves: the neighbour's quantile at the profiled
    frequency nearest the cap, times effective TDP, times chips."""
    ref = lib.by_name[plan["power_neighbor"]]
    table = getattr(ref, quantile_name)
    f = min(table, key=lambda x: abs(x - plan["cap"]))
    return table[f] * plan["effective_tdp"] * plan["chips"]


def pack(plans: list[dict], budget_w: float, lib: Library,
         quantile_name: str = "p99"):
    """First fit decreasing in exact sums: (placed job ids, deferred
    names)."""
    keyed = []
    for p in plans:
        per_chip = need(dict(p, chips=1), lib, quantile_name)
        keyed.append(((-per_chip * p["chips"], p["name"], p["device_id"],
                       p["job_id"]), per_chip * p["chips"], p))
    keyed.sort(key=lambda x: x[0])
    used, budget = Fraction(0), Fraction(budget_w)
    placed, deferred = [], []
    for _, w, p in keyed:
        if used + Fraction(w) <= budget:
            used += Fraction(w)
            placed.append(p["job_id"])
        else:
            deferred.append(p["name"])
    return placed, deferred


def sustained_violations(traces, budget_w: float, window: int = 50):
    """Samples of the rolling ``window``-sample mean of the summed fleet
    power above the budget; ``traces`` are (watts, weight) pairs."""
    if not traces:
        return 0, 0.0
    n = max(len(t) for t, _ in traces)
    agg = np.sum([w * np.resize(t, n) for t, w in traces], axis=0)
    if len(agg) >= window:
        agg = np.convolve(agg, np.ones(window) / window, mode="valid")
    else:
        agg = np.array([agg.mean()])
    return int(np.sum(agg > budget_w)), float(agg.max())
