"""Power-trace processing + spike-distribution vectors (paper §4.1, §5.3.1).

Pipeline (exactly the paper's):
  1. instantaneous power from the energy accumulator: P_inst = de/dt
  2. EMA filter with alpha = 0.5
  3. trim idle head/tail via the busy-cycles counter
  4. spike detection at P >= 0.5*TDP, relative magnitude r = P/TDP
  5. bin r into [0.5, 2.0) with width c; normalize -> spike vector v
"""
from __future__ import annotations

import numpy as np

SPIKE_LO = 0.5
SPIKE_HI = 2.0


def power_from_energy(energy_counter: np.ndarray, sample_dt_s: float) -> np.ndarray:
    """P_inst ~= delta_e / delta_t from an accumulating energy counter (J)."""
    de = np.diff(energy_counter.astype(np.float64))
    return (de / sample_dt_s).astype(np.float64)


def ema_filter(power: np.ndarray, alpha: float = 0.5,
               backend: str | None = None) -> np.ndarray:
    """P_filt(t) = alpha*P(t) + (1-alpha)*P_filt(t-1)   (paper uses 0.5).

    The recurrence (filter state seeded with P(0)) is evaluated without a
    per-sample Python loop by prefix-doubling: with w = 1-alpha and
    c = alpha*P (c_0 = P_0, absorbing the seed state), the fixpoint of
    ``out[s:] += w^s * out[:-s]`` for s = 1, 2, 4, ... is exactly
    out_i = sum_j c_j w^(i-j) — O(n log n) vectorized NumPy ops, and the
    loop short-circuits once w^s underflows to 0 (s ~ 50 for alpha = 0.5).

    ``backend`` selects the implementation: ``"numpy"`` (float64 host path),
    ``"pallas"`` (the ``repro.kernels.ema_scan`` TPU scan kernel, float32),
    or ``None`` to autodetect — the kernel on a TPU backend, NumPy elsewhere.
    """
    power = np.asarray(power, np.float64)
    if backend not in (None, "numpy", "pallas"):
        raise ValueError(f"unknown ema backend {backend!r}")
    if len(power) == 0:
        return np.empty(0, np.float64)
    if backend == "pallas" or (backend is None and _on_tpu()):
        from repro.kernels.ops import ema_scan
        return np.asarray(ema_scan(power, alpha=alpha), np.float64)
    w = 1.0 - alpha
    out = alpha * power
    out[0] = power[0]
    shift, decay = 1, w
    while shift < len(out) and decay != 0.0:
        out[shift:] += decay * out[:-shift]
        shift *= 2
        decay *= decay
    return out


def _on_tpu() -> bool:
    import jax
    return jax.default_backend() == "tpu"


def trim_idle(power: np.ndarray, busy: np.ndarray) -> np.ndarray:
    """Keep samples between the first and last non-zero busy-counter reading."""
    nz = np.nonzero(busy > 0)[0]
    if len(nz) == 0:
        return power[:0]
    return power[nz[0]:nz[-1] + 1]


def num_bins(bin_size: float) -> int:
    return int(round((SPIKE_HI - SPIKE_LO) / bin_size))


def spike_vector(power: np.ndarray, tdp: float, bin_size: float = 0.1) -> np.ndarray:
    """Normalized spike-magnitude distribution vector v (paper §4.1.1)."""
    r = np.asarray(power, np.float64) / tdp
    r = r[r >= SPIKE_LO]
    n = num_bins(bin_size)
    if len(r) == 0:
        return np.zeros(n)
    idx = np.clip(((r - SPIKE_LO) / bin_size).astype(np.int64), 0, n - 1)
    v = np.bincount(idx, minlength=n).astype(np.float64)
    return v / v.sum()


def spike_cdf(power: np.ndarray, tdp: float, grid: np.ndarray | None = None):
    """Cumulative power distribution relative to TDP (paper Figs. 2/5/6)."""
    r = np.sort(np.asarray(power, np.float64) / tdp)
    if grid is None:
        grid = np.linspace(0.0, SPIKE_HI, 201)
    cdf = np.searchsorted(r, grid, side="right") / max(len(r), 1)
    return grid, cdf


def p_quantile(power: np.ndarray, tdp: float, q: float = 90.0) -> float:
    """q-th percentile of power relative to TDP (p90/p95/p99 in the paper)."""
    if len(power) == 0:
        return 0.0
    return float(np.percentile(np.asarray(power, np.float64), q) / tdp)


def mean_power_rel(power: np.ndarray, tdp: float) -> float:
    """Mean power relative to TDP (the Guerreiro et al. feature)."""
    if len(power) == 0:
        return 0.0
    return float(np.mean(power) / tdp)
