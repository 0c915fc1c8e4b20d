"""The engine's device histogram path is exact: with the Pallas counting
kernel (interpreted on the CPU), every histogram count equals the NumPy
branch's, integer for integer — on rows built to sit one ulp either side of
every bin edge and just under ``SPIKE_LO``, and on a small fleet drive.

Binning float32 values on the device fails the edge rows: one row of
values at the 0.15 edges plus three just under 0.5 counted 33 samples
where the float64 reference counts 29."""
import numpy as np
import pytest

from repro.core import spikes
from repro.fleet import FleetTelemetryMux
from repro.pipeline.batch import (DEVICE_ROW_FLOOR, BatchProfileEngine,
                                  device_shape)
from repro.pipeline.builder import DEFAULT_BIN_SIZES
from repro.telemetry import TPUPowerModel, stream_telemetry
from repro.telemetry.kernel_stream import (micro_gemm, micro_idle_burst,
                                           micro_spmv_compute,
                                           micro_spmv_memory, micro_stencil)

MODEL = TPUPowerModel()
TDP = MODEL.spec.tdp_w


def _edge_values(c: float) -> np.ndarray:
    """Values one ulp either side of every edge of bin size ``c``, the
    edges themselves, and values just past the top of the range."""
    edges = spikes.SPIKE_LO + c * np.arange(spikes.num_bins(c) + 1)
    return np.concatenate([np.nextafter(edges, -np.inf), edges,
                           np.nextafter(edges, np.inf)])


def _under_lo() -> np.ndarray:
    lo = spikes.SPIKE_LO
    return np.array([np.nextafter(lo, -np.inf), lo - 1e-12, lo - 1e-9,
                     float(np.float32(lo)) - 2 ** -30, lo,
                     np.nextafter(lo, np.inf)])


def _engines():
    return (BatchProfileEngine(backend="numpy"),
            BatchProfileEngine(backend="pallas"))


def _scatter_both(r: np.ndarray, mask: np.ndarray):
    """Push one (k, F) committed block through both backends' scatter."""
    host, dev = _engines()
    for eng in (host, dev):
        meta = _meta()
        idx = np.array([eng.alloc(meta, TDP) for _ in range(len(r))])
        eng._scatter_hist(idx, r, mask)
        eng._flush_device()
    return host, dev


def _meta():
    meta, _ = stream_telemetry(micro_gemm(), 1.0, MODEL, seed=0,
                               target_duration=0.05, chunk_samples=64)
    return meta


def _assert_same_counts(host, dev):
    for c in host.bin_sizes:
        np.testing.assert_array_equal(dev._hist[c], host._hist[c])


@pytest.mark.parametrize("case", [*DEFAULT_BIN_SIZES, "under_lo"])
def test_device_counts_equal_numpy_on_edge_rows(case):
    vals = _under_lo() if case == "under_lo" else _edge_values(case)
    rng = np.random.default_rng(len(vals))
    r = np.stack([vals, rng.permutation(vals), vals[::-1]])
    mask = np.ones_like(r, bool)
    mask[2, ::3] = False                  # masked samples never count
    host, dev = _scatter_both(r, mask)
    _assert_same_counts(host, dev)
    assert dev.device_calls == 1
    if case != "under_lo":
        total = int(np.sum((r >= spikes.SPIKE_LO) & mask))
        for c in DEFAULT_BIN_SIZES:
            assert dev._hist[c].sum() == total


def test_device_counts_equal_numpy_on_the_reported_row():
    """0.15-bin edges one ulp either side, ten values near the top, and
    three just under 0.5: 29 spikes (binning in float32 counts 33)."""
    c = 0.15
    edges = spikes.SPIKE_LO + c * np.arange(1, spikes.num_bins(c))
    below = np.nextafter(np.float64(spikes.SPIKE_LO), -np.inf)
    row = np.concatenate([[spikes.SPIKE_LO], np.nextafter(edges, -np.inf),
                          np.nextafter(edges, np.inf),
                          np.nextafter(2.0, -np.inf) - np.arange(10) * 1e-3,
                          [below, below - 1e-12, below - 1e-10]])
    host, dev = _scatter_both(row[None, :], np.ones((1, len(row)), bool))
    _assert_same_counts(host, dev)
    assert dev._hist[c].sum() == np.sum(row >= spikes.SPIKE_LO) == 29


def _fleet_feed(n_jobs: int):
    mux = FleetTelemetryMux()
    streams = [micro_gemm, micro_spmv_memory, micro_spmv_compute,
               micro_idle_burst, micro_stencil]
    metas = {}
    for i in range(n_jobs):
        meta, chunks = stream_telemetry(
            streams[i % len(streams)](), 1.0, MODEL, seed=40 + i,
            target_duration=0.6, chunk_samples=256)
        metas[f"j{i}"] = meta
        mux.add_job(f"j{i}", meta, chunks)
    return mux, metas


def test_pallas_engine_is_bit_identical_to_numpy_engine_on_a_fleet_drive():
    mux, metas = _fleet_feed(7)
    host, dev = _engines()
    views = {e: {j: e.builder(m, TDP) for j, m in metas.items()}
             for e in (host, dev)}
    assert dev.warmup(len(metas)) == 1
    warmed = set(dev.device_shapes)
    for batch in mux.ticks():
        for e in (host, dev):
            e.ingest_batch([views[e][fc.job_id].slot for fc in batch],
                           [fc.chunk for fc in batch])
        _assert_same_counts(host, dev)
    assert dev.device_calls > 0 and host.device_calls == 0
    assert dev.device_shapes == warmed          # no compile inside the drive
    for j in metas:
        a, b = views[host][j].finalize(), views[dev][j].finalize()
        np.testing.assert_array_equal(a.power_trace, b.power_trace)
        for c in DEFAULT_BIN_SIZES:
            np.testing.assert_array_equal(a.spike_vec(c), b.spike_vec(c))


def test_device_shape_buckets_are_bounded():
    assert device_shape(1, 1) == (DEVICE_ROW_FLOOR, 256)
    assert device_shape(10_000, 256) == (16_384, 256)
    assert device_shape(257, 300) == (512, 512)
    # every row count up to a deployment's size lands on a few shapes
    shapes = {device_shape(k, 256) for k in range(1, 10_001)}
    assert len(shapes) == 7


def test_autodetect_takes_the_device_on_tpu(monkeypatch):
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert BatchProfileEngine()._resolve_backend() == "pallas"
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert BatchProfileEngine()._resolve_backend() == "numpy"


def test_bin_sizes_that_do_not_pack_are_refused_on_the_device_path():
    eng = BatchProfileEngine(bin_sizes=(0.01, 0.02), backend="pallas")
    with pytest.raises(ValueError, match="packed int32"):
        eng._resolve_backend()
