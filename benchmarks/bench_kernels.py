"""Kernel correctness/latency microbench: Pallas (interpret) vs jnp oracle.

On CPU the interpret-mode wall time is NOT a TPU performance proxy; the
benchmark reports correctness (max abs err) and the oracle's wall time as
the reference latency, plus the analytic FLOPs of each configuration.
"""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import RESULTS, emit
from repro.api import enable_compilation_cache
from repro.kernels import flash_attention, ref, rmsnorm, spike_hist, ssm_scan


def _time(fn, *args, reps=3):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps * 1e6


def run() -> dict:
    rows = []
    key = jax.random.key(0)
    # flash attention
    for (b, s, H, KV, dh) in [(1, 256, 8, 2, 64), (2, 512, 4, 4, 128)]:
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (b, s, H, dh), jnp.float32)
        k = jax.random.normal(ks[1], (b, s, KV, dh), jnp.float32)
        v = jax.random.normal(ks[2], (b, s, KV, dh), jnp.float32)
        out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
        want = ref.flash_attention_ref(q, k, v, causal=True)
        err = float(jnp.max(jnp.abs(out - want)))
        us = _time(lambda a, bb, c: ref.flash_attention_ref(a, bb, c, True), q, k, v)
        flops = 4.0 * b * s * s * H * dh / 2
        rows.append({"kernel": "flash_attention", "shape": f"b{b}s{s}H{H}kv{KV}d{dh}",
                     "max_abs_err": err, "ref_us": us, "flops": flops})
    # ssm scan
    for (b, s, di, ds) in [(2, 256, 256, 16)]:
        ks = jax.random.split(key, 6)
        x = jax.random.normal(ks[0], (b, s, di)) * 0.5
        dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, di)) * 0.2 - 1)
        A = -jnp.exp(jax.random.normal(ks[2], (di, ds)) * 0.3)
        B = jax.random.normal(ks[3], (b, s, ds)) * 0.5
        C = jax.random.normal(ks[4], (b, s, ds)) * 0.5
        D = jnp.ones((di,))
        y = ssm_scan(x, dt, A, B, C, D)
        want, _ = ref.ssm_scan_ref(x, dt, A, B, C, D)
        err = float(jnp.max(jnp.abs(y - want)))
        us = _time(lambda *a: ref.ssm_scan_ref(*a)[0], x, dt, A, B, C, D)
        rows.append({"kernel": "ssm_scan", "shape": f"b{b}s{s}di{di}ds{ds}",
                     "max_abs_err": err, "ref_us": us,
                     "flops": 9.0 * b * s * di * ds})
    # spike hist
    p = jax.random.uniform(jax.random.key(3), (100_000,), jnp.float32, 0, 2.2) * 200
    v1 = spike_hist(p, 200.0, n_bins=15)
    counts = ref.spike_hist_ref(p / 200.0, 15)
    err = float(jnp.max(jnp.abs(v1 - counts / jnp.sum(counts))))
    us = _time(lambda a: ref.spike_hist_ref(a, 15), p / 200.0)
    rows.append({"kernel": "spike_hist", "shape": "n100k", "max_abs_err": err,
                 "ref_us": us, "flops": 2.0 * len(p) * 15})
    # rmsnorm
    x = jax.random.normal(jax.random.key(4), (1024, 1024), jnp.bfloat16)
    sc = jnp.ones((1024,))
    err = float(jnp.max(jnp.abs(
        rmsnorm(x, sc).astype(jnp.float32) -
        ref.rmsnorm_ref(x, sc).astype(jnp.float32))))
    us = _time(lambda a, b: ref.rmsnorm_ref(a, b), x, sc)
    rows.append({"kernel": "rmsnorm", "shape": "1024x1024", "max_abs_err": err,
                 "ref_us": us, "flops": 4.0 * 1024 * 1024})

    with open(os.path.join(RESULTS, "kernels.json"), "w") as f:
        json.dump(rows, f, indent=1)
    worst = max(rows, key=lambda r: r["max_abs_err"])
    for r in rows:
        emit(f"kernel_{r['kernel']}_{r['shape']}", r["ref_us"],
             f"max_abs_err={r['max_abs_err']:.2e}")
    return {"rows": rows, "worst": worst}


if __name__ == "__main__":
    enable_compilation_cache()
    print(run()["worst"])
