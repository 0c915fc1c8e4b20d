"""The benchmark's own telemetry wire: job mix, arrivals and per-job counters.

One general generator serves every traffic mix; a mix is a JSON file of
parameters under ``bench/traffic/mixes/``.  Kept apart from the system under
test so that a change to ``repro.telemetry`` cannot move the yardstick: the
event trace, the sensor noise, the counters and the chunking are copied
here from the simulator, and only the zoo's ``KernelStream`` definitions and
the power model are read from the program, as data.

Sizes are the same for every seed and only their order changes with it:
the job mix is a fixed multiset of (workload, chips) drawn by exact weight,
inter-arrival gaps and lifetimes are fixed sets of exponential quantiles,
and the seed permutes them and draws each job's sensor noise.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass

import numpy as np

MIXES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "mixes")

OVERSHOOT_KAPPA = 1.1        # repro.telemetry.power_model, as data
OVERSHOOT_TAU = 1.0e-3
OVERSHOOT_MIN_STEP = 30.0
T_LAUNCH = 2e-6
MAX_ITERATIONS = 2000


def load_mix(name: str) -> dict:
    """The traffic mix ``name``: its parameters from ``mixes/<name>.json``."""
    path = os.path.join(MIXES, f"{name}.json")
    if not os.path.isfile(path):
        raise SystemExit(f"no traffic mix {name!r} ({path})")
    with open(path) as f:
        return json.load(f)


def stream_kind(name: str) -> str:
    """The mix class of a zoo stream, from its name."""
    for kind in ("decode", "prefill", "long"):
        if f":{kind}" in name:
            return kind
    return "train" if ":" in name else "hpc"


def zoo_streams(mix: dict) -> list:
    """The zoo's kernel streams that ``mix`` (a configuration's
    ``job_mix``) draws, with their integer weights."""
    from repro.telemetry.workloads import holdout_streams, reference_streams
    out = []
    for s in reference_streams() + holdout_streams():
        if any(s.name.startswith(p) for p in mix["exclude"]):
            continue
        w = int(mix["weights"][stream_kind(s.name)])
        if w > 0:
            out.append((s, w))
    return out


def exact_counts(weights, n: int) -> np.ndarray:
    """``n`` split in proportion to ``weights`` by largest remainder."""
    w = np.asarray(weights, np.float64)
    share = n * w / w.sum()
    counts = np.floor(share).astype(np.int64)
    order = np.argsort(-(share - counts), kind="stable")
    counts[order[:n - int(counts.sum())]] += 1
    return counts


def mean_chips(mix: dict) -> float:
    """The mean job size of ``mix`` (a configuration's ``job_mix``)."""
    sizes = np.array([int(c) for c in mix["chips"]], np.float64)
    w = np.array(list(mix["chips"].values()), np.float64)
    return float((sizes * w).sum() / w.sum())


def job_multiset(mix: dict, n: int, rng: np.random.Generator):
    """``n`` (stream, chips) jobs: every stream and every job size appears
    in exact proportion to its weight, and the seed only shuffles the two
    lists."""
    streams = zoo_streams(mix)
    picks = np.repeat(np.arange(len(streams)),
                      exact_counts([x[1] for x in streams], n))
    chips = np.repeat(np.array([int(c) for c in mix["chips"]], np.int64),
                      exact_counts(list(mix["chips"].values()), n))
    rng.shuffle(picks)
    rng.shuffle(chips)
    return [(streams[int(i)][0], int(c)) for i, c in zip(picks, chips)]


def exponential_set(n: int, mean: float, rng: np.random.Generator):
    """``n`` draws of an exponential of ``mean`` as a fixed set of its
    quantiles, in an order the seed picks."""
    q = (np.arange(n) + 0.5) / n
    out = -mean * np.log1p(-q)
    rng.shuffle(out)
    return out


# -- the event trace, copied from repro.telemetry.simulator ------------------
@dataclass
class EventTrace:
    energy_increments: np.ndarray   # noiseless energy per sample (J)
    busy_ctr: np.ndarray            # cumulative busy seconds at each edge
    n_samples: int
    exec_time: float
    app_sm_util: float
    app_dram_util: float
    kernel_rows: list


def integrate_events(t0, t1, pw, edges) -> np.ndarray:
    """Cumulative integral of overlapping box signals sampled at ``edges``."""
    if len(t0) == 0:
        return np.zeros(len(edges))
    times = np.concatenate([t0, t1])
    deltas = np.concatenate([pw, -np.asarray(pw)])
    uniq, inv = np.unique(times, return_inverse=True)
    rate_delta = np.zeros(len(uniq))
    np.add.at(rate_delta, inv, deltas)
    rate = np.cumsum(rate_delta)
    cum = np.empty(len(uniq))
    cum[0] = 0.0
    np.cumsum(np.diff(uniq) * rate[:-1], out=cum[1:])
    return np.interp(edges, uniq, cum)


def kernel_execs(stream, freq: float, model):
    """Duration, compute and memory utilisation, and steady power of every
    kernel: ``TPUPowerModel.exec_kernel`` over arrays, the same float
    expressions in the same order."""
    s = model.spec
    flops = np.array([k.flops for k in stream.kernels], np.float64)
    byts = np.array([k.bytes for k in stream.kernels], np.float64)
    f = min(max(freq, s.f_min), s.f_max)
    fc = s.peak_flops_bf16 * (f / s.f_max) * model.mxu_eff * s.perf_scale
    bm = s.hbm_bw * model.hbm_eff * s.perf_scale
    t_c = np.where(flops != 0, flops / fc, 0.0)
    t_m = np.where(byts != 0, byts / bm, 0.0)
    t = np.maximum(np.maximum(t_c, t_m), T_LAUNCH)
    util_c, util_m = t_c / t, t_m / t
    v = s.voltage(f)
    power = (s.idle_w + model.A_c * util_c * (f / s.f_max) * v * v
             + model.A_m * util_m) * s.power_scale
    return t, util_c, util_m, power


def event_trace(stream, freq: float, model, sample_dt: float,
                target_duration: float) -> EventTrace:
    """Kernel stream -> power events -> noiseless counters at sample edges
    (the simulator's ``_event_trace``, vectorised over kernels and
    overshoots)."""
    durs, util_c, util_m, pows = kernel_execs(stream, freq, model)
    nk = len(durs)
    gaps = np.array([k.gap_s for k in stream.kernels])
    step_time = float(np.sum(gaps) + np.sum(durs))
    iters = int(np.clip(np.ceil(target_duration / max(step_time, 1e-9)),
                        1, MAX_ITERATIONS))
    idle = model.idle_w
    seg_d = np.empty(2 * nk)
    seg_p = np.empty(2 * nk)
    seg_busy = np.empty(2 * nk)
    seg_d[0::2], seg_d[1::2] = gaps, durs
    seg_p[0::2], seg_p[1::2] = idle, pows
    seg_busy[0::2], seg_busy[1::2] = 0.0, 1.0
    pad = max(10 * sample_dt, 0.01)
    d = np.concatenate([[pad], np.tile(seg_d, iters), [pad]])
    p = np.concatenate([[idle], np.tile(seg_p, iters), [idle]])
    busy_flag = np.concatenate([[0.0], np.tile(seg_busy, iters), [0.0]])
    keep = d > 0
    d, p, busy_flag = d[keep], p[keep], busy_flag[keep]
    t_edges = np.concatenate([[0.0], np.cumsum(d)])
    starts, ends = t_edges[:-1], t_edges[1:]
    prev_p = np.concatenate([[idle], p[:-1]])
    step = p - prev_p
    up = np.nonzero(step >= OVERSHOOT_MIN_STEP)[0]
    amp = np.minimum(p[up] + OVERSHOOT_KAPPA * step[up],
                     model.spec.max_excursion * model.spec.tdp_w)
    t0 = np.concatenate([starts, starts[up]])
    t1 = np.concatenate([ends, starts[up] + np.minimum(OVERSHOOT_TAU, d[up])])
    pw = np.concatenate([p, amp - p[up]])
    n_samples = int(t_edges[-1] / sample_dt)
    edges = np.arange(n_samples + 1) * sample_dt
    energy = integrate_events(t0, t1, pw, edges)
    busy = busy_flag > 0
    busy_ctr = integrate_events(starts[busy], ends[busy],
                                np.ones(int(busy.sum())), edges)
    tot_d = durs.sum()
    app_sm = float((durs * util_c).sum() / max(tot_d, 1e-12))
    app_dr = float((durs * util_m).sum() / max(tot_d, 1e-12))
    rows = list(zip(durs.tolist(), util_c.tolist(), util_m.tolist()))
    return EventTrace(np.diff(energy), busy_ctr, n_samples, step_time,
                      app_sm, app_dr, rows)


def noisy_increments(ev: EventTrace, noise: float, seed) -> np.ndarray:
    """Per-sample energy increments with sensor noise and outliers, in the
    simulator's RNG call order."""
    rng = np.random.default_rng(seed)
    de = ev.energy_increments * (1.0 + noise * rng.standard_normal(
        ev.n_samples))
    out_mask = rng.random(ev.n_samples) < 0.01
    return np.where(out_mask, de * (1.0 + 0.5 * rng.random(ev.n_samples)),
                    de)


def energy_counter(ev: EventTrace, noise: float, seed) -> np.ndarray:
    """The cumulative energy counter a daemon polls, one reading per edge."""
    return np.concatenate([[0.0], np.cumsum(noisy_increments(ev, noise,
                                                             seed))])


# -- per-job telemetry -------------------------------------------------------
@dataclass
class Telemetry:
    """One pre-made job trace: its stream, device, counters and chunks."""
    stream: object
    device: object
    energy_ctr: np.ndarray
    busy_ctr: np.ndarray
    ev: EventTrace
    chunks: list          # TelemetryChunk views, in order
    chunk_end: np.ndarray  # sample index after each chunk

    def meta(self):
        """A fresh ``TraceMeta`` for one job that streams this trace."""
        from repro.telemetry.simulator import TraceMeta
        ev = self.ev
        return TraceMeta(name=self.stream.name, domain=self.stream.domain,
                         sample_dt=self.chunks[0].sample_dt,
                         n_samples=ev.n_samples, exec_time=ev.exec_time,
                         app_sm_util=ev.app_sm_util,
                         app_dram_util=ev.app_dram_util,
                         kernel_rows=ev.kernel_rows,
                         device_id=self.device.device_id)


def make_pool(jobs, devices, tele: dict, seed: int,
              noise=None) -> list[Telemetry]:
    """One trace per job: the event trace is shared per (workload, device
    spec) and each job draws its own noise from ``(seed, job index)``, or
    from ``(seed, noise[i])`` where ``noise`` gives the indices."""
    from repro.telemetry.simulator import TelemetryChunk
    dt, cs = float(tele["sample_dt_s"]), int(tele["chunk_samples"])
    events: dict = {}
    pool = []
    for i, ((stream, _), dev) in enumerate(zip(jobs, devices)):
        key = (stream.name, dev.model, dev.spec.perf_scale,
               dev.spec.power_scale)
        ev = events.get(key)
        if ev is None:
            ev = events[key] = event_trace(stream, 1.0, dev.power_model(),
                                           dt, float(tele["profile_s"]))
        e = energy_counter(ev, float(tele["noise"]),
                           [seed, i if noise is None else int(noise[i])])
        b = ev.busy_ctr
        starts = range(0, ev.n_samples, cs)
        chunks = [TelemetryChunk(energy_j=e[j + 1:min(j + cs, ev.n_samples)
                                            + 1],
                                 busy_s=b[j + 1:min(j + cs, ev.n_samples) + 1],
                                 sample_dt=dt, start_index=j)
                  for j in starts]
        ends = np.minimum(np.arange(1, len(chunks) + 1) * cs, ev.n_samples)
        pool.append(Telemetry(stream, dev, e, b, ev, chunks, ends))
    return pool
