"""Golden-equivalence tests: the vectorized event-stream profiling engine
(PR 1) against the frozen seed implementations in ``repro.legacy``.

Every rewritten hot path — ``integrate_events``/``simulate``, ``ema_filter``,
the batched/cached classifier neighbors, ``linkage``, ``silhouette_score``,
kmeans++ seeding — must reproduce the seed semantics to 1e-9 on fixed-seed
inputs (the busy counter bit-exactly).  Plus behavior tests for the new API
surface: spike-matrix caching, ValueError on fully-excluded neighbor queries
and non-positive bin sizes, and backend autodetection of the Pallas kernels.
"""
import numpy as np
import pytest

from repro import legacy
from repro.core import spikes
from repro.core.algorithm1 import choose_bin_size, select_optimal_freq
from repro.core.classify import FreqPoint, MinosClassifier, WorkloadProfile
from repro.core.clustering import (cosine_distance_matrix,
                                   euclidean_distance_matrix, kmeanspp_init,
                                   linkage, silhouette_score)
from repro.telemetry import TPUPowerModel, simulate
from repro.telemetry.kernel_stream import micro_gemm, micro_idle_burst
from repro.telemetry.simulator import integrate_events

TDP = 200.0
FREQS = [0.6, 0.8, 1.0]


# ---------------------------------------------------------------------------
# telemetry: event integration + full simulate
# ---------------------------------------------------------------------------
def test_integrate_events_matches_dense():
    rng = np.random.default_rng(0)
    for n_events in (1, 7, 300):
        t0 = rng.uniform(0.0, 3.0, n_events)
        t1 = t0 + rng.uniform(1e-6, 0.5, n_events)
        pw = rng.uniform(-50.0, 400.0, n_events)
        edges = np.arange(0, 3500) * 1e-3
        got = integrate_events(t0, t1, pw, edges)
        want = legacy.integrate_events_dense(t0, t1, pw, edges)
        np.testing.assert_allclose(got, want, rtol=1e-9, atol=1e-9)


def test_integrate_events_empty_and_coincident():
    edges = np.linspace(0, 1, 11)
    assert np.all(integrate_events(np.array([]), np.array([]),
                                   np.array([]), edges) == 0)
    # two events sharing both endpoints (np.add.at must accumulate, not clobber)
    t0 = np.array([0.2, 0.2])
    t1 = np.array([0.6, 0.6])
    pw = np.array([10.0, 5.0])
    want = legacy.integrate_events_dense(t0, t1, pw, edges)
    np.testing.assert_allclose(integrate_events(t0, t1, pw, edges), want,
                               rtol=1e-12, atol=1e-12)


@pytest.mark.parametrize("stream_fn,freq", [(micro_gemm, 1.0),
                                            (micro_gemm, 0.6),
                                            (micro_idle_burst, 1.0)])
def test_simulate_matches_seed(stream_fn, freq):
    model = TPUPowerModel()
    a = simulate(stream_fn(), freq, model, seed=11, target_duration=1.0)
    b = legacy.simulate_dense(stream_fn(), freq, model, seed=11,
                              target_duration=1.0)
    np.testing.assert_allclose(a.power_raw, b.power_raw, rtol=1e-9, atol=1e-9)
    np.testing.assert_allclose(a.power_filtered, b.power_filtered,
                               rtol=1e-9, atol=1e-9)
    np.testing.assert_array_equal(a.busy, b.busy)
    assert a.exec_time == b.exec_time
    assert a.app_sm_util == b.app_sm_util


# ---------------------------------------------------------------------------
# spikes: EMA
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n", [1, 2, 100, 4099, 20000])
@pytest.mark.parametrize("alpha", [0.5, 0.1, 0.9])
def test_ema_vectorized_matches_loop(n, alpha):
    x = np.random.default_rng(n).uniform(40.0, 600.0, n)
    np.testing.assert_allclose(spikes.ema_filter(x, alpha),
                               legacy.ema_filter_loop(x, alpha),
                               rtol=1e-9, atol=1e-9)


def test_ema_pallas_backend_matches_loop():
    x = np.random.default_rng(3).uniform(40.0, 600.0, 3000)
    got = spikes.ema_filter(x, 0.5, backend="pallas")
    want = legacy.ema_filter_loop(x, 0.5)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-3)  # f32 kernel


def test_ema_edge_cases():
    assert spikes.ema_filter(np.array([]), 0.5).shape == (0,)
    with pytest.raises(ValueError, match="backend"):
        spikes.ema_filter(np.ones(4), 0.5, backend="cuda")


# ---------------------------------------------------------------------------
# classifier: cache + batched neighbors + error handling
# ---------------------------------------------------------------------------
def _profile(name, level, sm, dram):
    rng = np.random.default_rng(abs(hash(name)) % 2**31)
    trace = rng.normal(level * TDP, 9.0, 700)
    scaling = {f: FreqPoint(freq=f, p90=level * f, p95=level * f + 0.03,
                            p99=level * f + 0.07, mean_power=level * f - 0.1,
                            exec_time=1.0 / f) for f in FREQS}
    return WorkloadProfile(name=name, tdp=TDP, power_trace=trace,
                           sm_util=sm, dram_util=dram, exec_time=1.0,
                           scaling=scaling)


@pytest.fixture(scope="module")
def refs():
    return [_profile("gemm", 1.3, 0.95, 0.15),
            _profile("spmv", 0.7, 0.10, 0.90),
            _profile("hybrid", 1.05, 0.55, 0.50),
            _profile("stencil", 0.9, 0.40, 0.70),
            _profile("idle-burst", 1.5, 0.30, 0.20)]


def test_batched_power_neighbors_match_loop(refs):
    clf = MinosClassifier(refs)
    targets = [_profile("t-compute", 1.28, 0.9, 0.2),
               _profile("t-mem", 0.72, 0.15, 0.85)] + refs
    for c in (0.05, 0.1, 0.25):
        got = clf.power_neighbors(targets, bin_size=c)
        for t, (nn, d) in zip(targets, got):
            nn_ref, d_ref = legacy.power_neighbor_loop(refs, t, bin_size=c)
            assert nn.name == nn_ref.name
            assert d == pytest.approx(d_ref, abs=1e-9)


def test_batched_util_neighbors_match_loop(refs):
    clf = MinosClassifier(refs)
    targets = [_profile("t1", 1.0, 0.93, 0.18), _profile("t2", 1.0, 0.2, 0.8)] + refs
    for t, (nn, d) in zip(targets, clf.util_neighbors(targets)):
        nn_ref, d_ref = legacy.util_neighbor_loop(refs, t)
        assert nn.name == nn_ref.name
        assert d == pytest.approx(d_ref, abs=1e-9)


def test_neighbor_exclude_param(refs):
    clf = MinosClassifier(refs)
    target = _profile("t-compute", 1.28, 0.9, 0.2)
    nn_all, _ = clf.power_neighbor(target)
    nn_excl, _ = clf.power_neighbor(target, exclude=nn_all.name)
    assert nn_excl.name != nn_all.name
    want, _ = legacy.power_neighbor_loop(refs, target, 0.1, exclude=nn_all.name)
    assert nn_excl.name == want.name


def test_neighbor_raises_when_all_excluded(refs):
    single = MinosClassifier([refs[0]])
    with pytest.raises(ValueError, match="every reference"):
        single.power_neighbor(refs[0])        # self-match excludes the only ref
    with pytest.raises(ValueError, match="every reference"):
        single.util_neighbor(_profile("x", 1.0, 0.5, 0.5), exclude=refs[0].name)


def test_bad_bin_size_rejected(refs):
    clf = MinosClassifier(refs)
    t = _profile("t", 1.0, 0.5, 0.5)
    for bad in (0, 0.0, -0.1):
        with pytest.raises(ValueError, match="bin_size"):
            clf.power_neighbor(t, bin_size=bad)
        with pytest.raises(ValueError, match="bin_size"):
            clf.spike_matrix(bin_size=bad)
    with pytest.raises(ValueError, match="bin_size"):
        MinosClassifier(refs, bin_size=-1.0)
    with pytest.raises(ValueError, match="bin_size"):
        clf.power_neighbor(t, bin_size=True)   # bools are not bin sizes
    # numpy scalars are legitimate positive numbers
    nn_np, d_np = clf.power_neighbor(t, bin_size=np.float32(0.1))
    nn_py, d_py = clf.power_neighbor(t, bin_size=0.1)
    assert nn_np.name == nn_py.name


def test_spike_matrix_cached_per_bin_size(refs):
    clf = MinosClassifier(refs)
    m1 = clf.spike_matrix(0.1)
    m2 = clf.spike_matrix(0.1)
    assert m1 is m2                            # memoized, not recomputed
    m3 = clf.spike_matrix(0.25)
    assert m3 is not m1 and m3.shape != m1.shape
    np.testing.assert_allclose(
        m1, np.stack([r.spike_vec(0.1) for r in refs]), rtol=1e-12, atol=1e-12)


def test_choose_bin_size_matches_seed_loop(refs):
    clf = MinosClassifier(refs)
    for t in (_profile("t-compute", 1.28, 0.9, 0.2),
              _profile("t-mem", 0.72, 0.15, 0.85)):
        cands = (0.05, 0.1, 0.15, 0.25)
        assert choose_bin_size(t, clf, cands) == \
            legacy.choose_bin_size_loop(t, refs, cands)
        sel = select_optimal_freq(t, clf, cands)
        nn, _ = legacy.power_neighbor_loop(refs, t, bin_size=sel.bin_size)
        assert sel.power_neighbor == nn.name


# ---------------------------------------------------------------------------
# clustering
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("method", ["ward", "average", "complete", "single"])
@pytest.mark.parametrize("n", [2, 5, 18])
def test_linkage_matches_loop(method, n):
    X = np.abs(np.random.default_rng(n).normal(size=(n, 6))) + 0.05
    D = cosine_distance_matrix(X)
    np.testing.assert_allclose(linkage(D, method), legacy.linkage_loop(D, method),
                               rtol=1e-9, atol=1e-9)


def test_silhouette_matches_loop():
    rng = np.random.default_rng(5)
    for _ in range(20):
        n = int(rng.integers(3, 40))
        X = rng.normal(size=(n, 3))
        labels = rng.integers(0, 4, size=n) * 7 - 3   # non-contiguous labels
        assert silhouette_score(X, labels) == \
            pytest.approx(legacy.silhouette_loop(X, labels), abs=1e-9)
    # degenerate inputs take the same early exit
    assert silhouette_score(X[:2], np.array([0, 1])) == 0.0
    assert silhouette_score(X, np.zeros(n, np.int64)) == 0.0


def test_kmeanspp_init_matches_loop_rng_stream():
    rng = np.random.default_rng(9)
    for seed in range(10):
        n = int(rng.integers(4, 30))
        X = rng.normal(size=(n, 2))
        k = int(rng.integers(2, min(6, n + 1)))
        np.testing.assert_array_equal(
            kmeanspp_init(X, k, np.random.default_rng(seed)),
            legacy.kmeanspp_init_loop(X, k, np.random.default_rng(seed)))
    # identical points: the tot<=0 fallback draws the same stream too
    Z = np.ones((6, 2))
    np.testing.assert_array_equal(
        kmeanspp_init(Z, 3, np.random.default_rng(1)),
        legacy.kmeanspp_init_loop(Z, 3, np.random.default_rng(1)))


# ---------------------------------------------------------------------------
# kernels: backend autodetection
# ---------------------------------------------------------------------------
def test_spike_hist_interpret_autodetect():
    import jax
    from repro.kernels.spike_hist import spike_hist_pallas

    p = jax.random.uniform(jax.random.key(0), (777,), minval=0.0, maxval=2.3)
    got = np.asarray(spike_hist_pallas(p, 15))             # interpret=None
    want = np.asarray(spike_hist_pallas(p, 15, interpret=True))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    r = np.asarray(p, np.float64)
    counts, _ = np.histogram(r[(r >= 0.5) & (r < 2.0)],
                             bins=15, range=(0.5, 2.0))
    hi = np.sum(r >= 2.0)                                   # top bin clips
    counts[-1] += hi
    np.testing.assert_allclose(got, counts.astype(np.float64), atol=1e-6)


@pytest.mark.parametrize("n", [96 * 128, 1280, 130, 125 * 128, 250 * 128])
def test_spike_hist_partial_block_rows(n):
    """Row counts that don't divide the requested block (the seed shrank the
    block with a decrement search; the engine pads rows instead) still count
    every sample exactly once."""
    import jax
    from repro.kernels.spike_hist import spike_hist_pallas

    p = jax.random.uniform(jax.random.key(n), (n,), minval=0.4, maxval=2.2)
    got = np.asarray(spike_hist_pallas(p, 15, interpret=True))
    assert got.sum() == pytest.approx(float(np.sum(np.asarray(p) >= 0.5)))


# ---------------------------------------------------------------------------
# engine: the p90 an emitted profile carries from its slot
# ---------------------------------------------------------------------------
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.pipeline.batch import BatchProfileEngine  # noqa: E402
from repro.telemetry.simulator import TelemetryChunk, TraceMeta  # noqa: E402

DT = 0.5     # integer watts times DT stay exact through the counters


def _stream(rng, n, shape, name):
    """Counter chunks of an ``n``-sample trace: uniform power, a step down
    from above TDP to well below it, or a few integer levels (ties)."""
    if shape == "uniform":
        power = rng.uniform(0.0, 1.3 * TDP, n)
    elif shape == "step":
        k = int(rng.integers(0, n + 1))
        power = np.concatenate([rng.uniform(0.9 * TDP, 1.3 * TDP, k),
                                rng.uniform(0.05 * TDP, 0.45 * TDP, n - k)])
    else:
        power = rng.integers(0, 7, n) * 40.0
    busy = (rng.random(n) < 0.85).astype(float)
    e = np.concatenate([[0.0], np.cumsum(power * DT)])
    b = np.concatenate([[0.0], np.cumsum(busy * DT)])
    meta = TraceMeta(name=name, domain="test", sample_dt=DT, n_samples=n,
                     exec_time=1.0, app_sm_util=0.5, app_dram_util=0.5,
                     kernel_rows=[])
    bounds, i = [0], 0
    while i < n:
        i = min(n, i + int(rng.integers(1, 600)))
        bounds.append(i)
    chunks = [TelemetryChunk(energy_j=e[i + 1:j + 1], busy_s=b[i + 1:j + 1],
                             sample_dt=DT, start_index=i)
              for i, j in zip(bounds[:-1], bounds[1:])]
    return meta, chunks


def _assert_p90_exact(prof):
    """The engine filled the memo itself, with the float the reference path
    (``np.percentile`` over the whole trace, over TDP) computes."""
    got = prof.__dict__["_pq_memo"][90.0]
    want = spikes.p_quantile(prof.power_trace, prof.tdp, 90.0)
    assert got.hex() == want.hex(), (len(prof.power_trace), got, want)


@given(st.integers(min_value=0, max_value=10_000))
@settings(max_examples=20, deadline=None)
def test_slot_p90_is_np_percentile_bit_for_bit(scenario_seed):
    """Random chunkings, every tick's snapshots (with their pending
    extras), mid-stream retires with slot reuse, and both finalize paths:
    every emitted profile's p90 equals ``np.percentile`` bit for bit."""
    rng = np.random.default_rng(scenario_seed)
    eng = BatchProfileEngine(capacity=2)         # force slot-array growth
    shapes = ("uniform", "step", "ties")

    def new_job(name):
        n = int(rng.integers(1, 3000))
        meta, chunks = _stream(rng, n, shapes[int(rng.integers(3))], name)
        return dict(slot=eng.alloc(meta, TDP), chunks=chunks, pos=0)

    live = {f"j{k}": new_job(f"j{k}") for k in range(int(rng.integers(2, 5)))}
    admits_left, next_id = 3, 100
    while live:
        ids = sorted(live)
        tick = [j for j in ids if live[j]["pos"] < len(live[j]["chunks"])
                and rng.random() < 0.7]
        if tick:
            eng.ingest_batch([live[j]["slot"] for j in tick],
                             [live[j]["chunks"][live[j]["pos"]]
                              for j in tick])
            for j in tick:
                live[j]["pos"] += 1
        for prof in eng.snapshot_batch([live[j]["slot"] for j in ids]):
            _assert_p90_exact(prof)
        _assert_p90_exact(eng.snapshot(live[ids[0]]["slot"]))
        if rng.random() < 0.1:                   # retire mid-stream
            eng.free(live.pop(ids[int(rng.integers(len(ids)))])["slot"])
            if admits_left:                      # the freed slot is reused
                admits_left -= 1
                live[f"n{next_id}"] = new_job(f"n{next_id}")
                next_id += 1
        done = [j for j in sorted(live)
                if live[j]["pos"] >= len(live[j]["chunks"])]
        if not done:
            continue
        slots = [live.pop(j)["slot"] for j in done]
        if rng.random() < 0.5:
            profs = eng.finalize_batch(slots)
        else:
            profs = [eng.finalize(s) for s in slots]
        for prof, s in zip(profs, slots):
            assert prof.complete
            _assert_p90_exact(prof)
            eng.free(s)


def _step_down_slot(eng, n=12000, high=1500):
    rng = np.random.default_rng(5)
    power = np.concatenate([rng.uniform(1.0 * TDP, 1.3 * TDP, high),
                            rng.uniform(0.1 * TDP, 0.3 * TDP, n - high)])
    e = np.concatenate([[0.0], np.cumsum(power * DT)])
    b = np.arange(n + 1) * DT
    meta = TraceMeta(name="step", domain="test", sample_dt=DT, n_samples=n,
                     exec_time=1.0, app_sm_util=0.5, app_dram_util=0.5,
                     kernel_rows=[])
    slot = eng.alloc(meta, TDP)
    chunks = [TelemetryChunk(energy_j=e[i + 1:i + 301],
                             busy_s=b[i + 1:i + 301], sample_dt=DT,
                             start_index=i) for i in range(0, n, 300)]
    return slot, chunks


def test_slot_p90_after_a_step_down_rebuilds_the_window():
    """Power that drops from above TDP to a fifth of it pushes the p90's
    ranks below the window's threshold: the window is rebuilt from the
    whole trace, and every snapshot's p90 stays exact."""
    import repro.obs as obs

    eng = BatchProfileEngine()
    slot, chunks = _step_down_slot(eng)
    obs.reset()
    obs.enable()
    try:
        for chunk in chunks:
            eng.ingest_batch([slot], [chunk])
            _assert_p90_exact(eng.snapshot_batch([slot])[0])
        _assert_p90_exact(eng.finalize(slot))
        assert obs.report()["counters"]["snapshot.pq_rebuilds"] >= 1
    finally:
        obs.disable()
        obs.reset()


@pytest.mark.parametrize("n", [1, 2])
@pytest.mark.parametrize("busy", [True, False])
def test_slot_p90_of_one_and_two_samples(n, busy):
    eng = BatchProfileEngine()
    power = np.array([150.0, 230.0])[:n]
    e = np.concatenate([[0.0], np.cumsum(power * DT)])
    b = np.concatenate([[0.0], np.cumsum(np.full(n, DT if busy else 0.0))])
    meta = TraceMeta(name="tiny", domain="test", sample_dt=DT, n_samples=n,
                     exec_time=1.0, app_sm_util=0.5, app_dram_util=0.5,
                     kernel_rows=[])
    a, c = eng.alloc(meta, TDP), eng.alloc(meta, TDP)
    chunk = TelemetryChunk(energy_j=e[1:], busy_s=b[1:], sample_dt=DT,
                           start_index=0)
    eng.ingest_batch([a, c], [chunk, chunk])
    snap = eng.snapshot(a)
    assert len(snap.power_trace) == (n if busy else 0)
    _assert_p90_exact(snap)
    _assert_p90_exact(eng.snapshot_batch([a])[0])
    _assert_p90_exact(eng.finalize(a))
    _assert_p90_exact(eng.finalize_batch([c])[0])


def test_rank_window_rounds_like_numpy_lerp():
    """Traces whose two order statistics ``a < b`` make numpy's two lerp
    forms round apart (it takes ``b - diff * (1 - gamma)`` from gamma 0.5
    up): the window, fed in random pieces with uncommitted extras, gives
    ``np.percentile``'s float in every case."""
    from repro.pipeline.batch import _RankWindow

    rng = np.random.default_rng(11)
    cases = 0
    while cases < 60:
        n = int(rng.integers(2, 4000))
        vi = (n - 1) * 0.9
        lo = int(np.floor(vi))
        gamma = vi - lo
        a, b = np.sort(rng.uniform(0.0, 300.0, 2)).tolist()
        if a + (b - a) * gamma == b - (b - a) * (1.0 - gamma):
            continue
        cases += 1
        x = np.concatenate([np.full(lo + 1, a), np.full(n - lo - 1, b)])
        x += rng.uniform(-1.0, 0.0, n) * (x == a) * (np.arange(n) < lo)
        rng.shuffle(x)
        m = int(rng.integers(0, min(n, 256)))
        cuts = np.sort(rng.integers(0, n - m + 1, 3))
        pieces = [p for p in np.split(x[:n - m], cuts) if len(p)]
        window = _RankWindow()
        for k in range(1, len(pieces)):
            window.absorb(pieces[:k])
        got = window.percentile(pieces, x[n - m:])
        assert got.hex() == float(np.percentile(x, 90.0)).hex()
