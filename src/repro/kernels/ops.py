"""jit'd public wrappers for the Pallas kernels.

``interpret`` defaults to True off-TPU (this container is CPU-only; the
kernels are validated in interpret mode against ref.py and lower natively
on TPU backends).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.ema_scan import ema_scan_pallas
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.spike_hist import (spike_hist_packed_pallas,
                                      spike_hist_pallas)
from repro.kernels.ssm_scan import ssm_scan_pallas


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


@partial(jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, block_q: int = 512,
                    block_k: int = 512,
                    interpret: bool | None = None) -> jax.Array:
    """q: (b, sq, H, dh); k/v: (b, skv, KV, dh) -> (b, sq, H, dh)."""
    interpret = _default_interpret() if interpret is None else interpret
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    ot = flash_attention_bhsd(qt, kt, vt, causal=causal, block_q=block_q,
                              block_k=block_k, interpret=interpret)
    return ot.transpose(0, 2, 1, 3)


@partial(jax.jit, static_argnames=("block_s", "block_d", "interpret"))
def ssm_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
             C: jax.Array, D: jax.Array, *, block_s: int = 64,
             block_d: int = 256, interpret: bool | None = None) -> jax.Array:
    interpret = _default_interpret() if interpret is None else interpret
    return ssm_scan_pallas(x, dt, A, B, C, D, block_s=block_s,
                           block_d=block_d, interpret=interpret)


@partial(jax.jit, static_argnames=("n_bins", "lo", "hi", "interpret"))
def spike_hist(power: jax.Array, tdp: float | jax.Array, n_bins: int = 15,
               lo: float = 0.5, hi: float = 2.0,
               interpret: bool | None = None) -> jax.Array:
    """Power samples (W) -> normalized spike vector (n_bins,)."""
    interpret = _default_interpret() if interpret is None else interpret
    rel = power.astype(jnp.float32) / tdp
    counts = spike_hist_pallas(rel, n_bins, lo=lo, hi=hi, interpret=interpret)
    total = jnp.sum(counts)
    return jnp.where(total > 0, counts / total, counts)


@partial(jax.jit, static_argnames=("fields", "interpret"))
def spike_hist_packed(packed: jax.Array, fields,
                      interpret: bool | None = None) -> jax.Array:
    """(rows, samples) packed int32 bin indices -> (rows, 128) int32 lane
    counts (layout: ``spike_hist.pack_fields``) — the fleet engine's one
    device call per tick."""
    interpret = _default_interpret() if interpret is None else interpret
    return spike_hist_packed_pallas(packed, fields, interpret=interpret)


@partial(jax.jit, static_argnames=("alpha", "interpret"))
def ema_scan(power: jax.Array, alpha: float = 0.5,
             interpret: bool | None = None) -> jax.Array:
    """Power samples (W) -> EMA-filtered samples (paper's alpha=0.5 filter)."""
    interpret = _default_interpret() if interpret is None else interpret
    return ema_scan_pallas(power.astype(jnp.float32), alpha=alpha,
                           interpret=interpret)


@partial(jax.jit, static_argnames=("eps", "interpret"))
def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-5,
            interpret: bool | None = None) -> jax.Array:
    interpret = _default_interpret() if interpret is None else interpret
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    return rmsnorm_pallas(x2, scale, eps=eps, interpret=interpret).reshape(shape)
