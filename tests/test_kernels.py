"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention, ref, rmsnorm, spike_hist, ssm_scan
from repro.core import spikes as core_spikes


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("b,sq,skv,H,KV,dh,causal", [
    (1, 128, 128, 4, 4, 64, True),      # MHA causal
    (2, 128, 128, 8, 2, 64, True),      # GQA 4:1
    (2, 64, 256, 8, 8, 128, False),     # cross-ish, bidirectional
    (1, 256, 256, 16, 2, 128, True),    # MQA-ish wide
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(b, sq, skv, H, KV, dh, causal, dtype):
    k1, k2, k3 = jax.random.split(jax.random.key(b * sq + H), 3)
    q = jax.random.normal(k1, (b, sq, H, dh), jnp.float32).astype(dtype)
    k = jax.random.normal(k2, (b, skv, KV, dh), jnp.float32).astype(dtype)
    v = jax.random.normal(k3, (b, skv, KV, dh), jnp.float32).astype(dtype)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("b,s,di,ds,bs,bd", [
    (1, 64, 128, 8, 16, 128),
    (2, 128, 256, 16, 64, 128),
    (1, 96, 384, 16, 32, 384),          # non-pow2 seq blocks
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssm_scan_sweep(b, s, di, ds, bs, bd, dtype):
    keys = jax.random.split(jax.random.key(s + di), 6)
    x = (jax.random.normal(keys[0], (b, s, di)) * 0.5).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(keys[1], (b, s, di)) * 0.2 - 1).astype(dtype)
    A = -jnp.exp(jax.random.normal(keys[2], (di, ds)) * 0.3)
    B = (jax.random.normal(keys[3], (b, s, ds)) * 0.5).astype(dtype)
    C = (jax.random.normal(keys[4], (b, s, ds)) * 0.5).astype(dtype)
    D = jnp.ones((di,))
    y = ssm_scan(x, dt, A, B, C, D, block_s=bs, block_d=bd)
    want, _ = ref.ssm_scan_ref(x, dt, A, B, C, D)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(want, np.float32),
                               **(_tol(dtype) if dtype == jnp.bfloat16
                                  else dict(rtol=2e-4, atol=2e-4)))


@pytest.mark.parametrize("n,n_bins", [(100, 15), (5000, 15), (4096, 30),
                                      (777, 6)])
def test_spike_hist_sweep(n, n_bins):
    key = jax.random.key(n)
    p = jax.random.uniform(key, (n,), jnp.float32, 0.0, 2.3) * 200.0
    v = spike_hist(p, 200.0, n_bins=n_bins)
    counts = ref.spike_hist_ref(p / 200.0, n_bins)
    want = counts / jnp.maximum(jnp.sum(counts), 1)
    np.testing.assert_allclose(np.asarray(v), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    # cross-check against the numpy implementation Minos actually uses
    c = (2.0 - 0.5) / n_bins
    v_np = core_spikes.spike_vector(np.asarray(p), 200.0, bin_size=c)
    np.testing.assert_allclose(np.asarray(v), v_np, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("n,d", [(8, 128), (64, 512), (100, 384)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(n, d, dtype):
    k1, k2 = jax.random.split(jax.random.key(n + d))
    x = jax.random.normal(k1, (n, d), jnp.float32).astype(dtype)
    sc = jax.random.normal(k2, (d,), jnp.float32)
    y = rmsnorm(x, sc)
    want = ref.rmsnorm_ref(x, sc)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_flash_attention_matches_model_chunked_path():
    """Pallas kernel vs the model's jnp chunked attention (both vs exact)."""
    from repro.models.attention import chunked_attention
    from repro.models.common import SMOKE_TOPO
    b, s, H, KV, dh = 1, 128, 4, 2, 64
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (b, s, H, dh), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, KV, dh), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, KV, dh), jnp.float32)
    pos = jnp.arange(s, dtype=jnp.int32)
    o_model = chunked_attention(q * (dh ** 0.5) / (dh ** 0.5), k, v, causal=True,
                                q_positions=pos, kv_positions=pos,
                                topo=SMOKE_TOPO, heads_sharded=False)
    o_kernel = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(o_model), np.asarray(o_kernel),
                               rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("jobs,n,n_bins,c", [(1, 100, 15, 0.1),
                                             (13, 300, 10, 0.15),
                                             (9, 1000, 30, 0.05),
                                             (32, 257, 3, 0.5)])
def test_spike_hist_batch_sweep(jobs, n, n_bins, c):
    """Batched (jobs x samples) packed-index counting kernel == per-row
    float64 bincount of the host-computed bin indices; -1 padding/masking
    is never counted, and a second histogram packed into the same int32
    counts in its own lanes."""
    from repro.kernels.spike_hist import pack_fields, spike_hist_packed_pallas
    rng = np.random.default_rng(jobs * 1000 + n)
    r = rng.uniform(0.0, 2.5, size=(jobs, n))
    live = (rng.random((jobs, n)) < 0.8) & (r >= 0.5)
    fields = pack_fields([n_bins, 15])
    idx_a = np.minimum(((r - 0.5) / c).astype(np.int64), n_bins - 1)
    idx_b = np.minimum(((r - 0.5) / 0.1).astype(np.int64), 14)
    packed = np.where(live, (idx_a << fields[0][0]) | (idx_b << fields[1][0]),
                      -1)
    buf = np.full((-(-jobs // 8) * 8, -(-n // 128) * 128), -1, np.int32)
    buf[:jobs, :n] = packed
    got = np.asarray(spike_hist_packed_pallas(jnp.asarray(buf), fields,
                                              interpret=True))[:jobs]
    off = fields[1][2]
    for i in range(jobs):
        np.testing.assert_array_equal(
            got[i, :n_bins], np.bincount(idx_a[i][live[i]], minlength=n_bins))
        np.testing.assert_array_equal(
            got[i, off:off + 15], np.bincount(idx_b[i][live[i]], minlength=15))
    assert not got[:, off + 15:].any()
