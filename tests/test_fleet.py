"""Fleet layer tests: inventory determinism, per-device power models, the
telemetry mux, device-portable classification, and the pinned invariance —
on a homogeneous zero-variability fleet, ``FleetCapController`` decisions
are byte-identical to the single-job ``OnlineCapController`` path."""
import dataclasses
from contextlib import nullcontext

import numpy as np
import pytest

from repro.analysis.hardware import CHIP_MODELS, V5E
from repro.core.algorithm1 import select_optimal_freq
from repro.fleet import (DEGRADED, FAILED, HEALTHY, DeviceInstance,
                         DeviceInventory, FleetCapController, FleetChunk,
                         FleetTelemetryMux, VariabilityModel)
from repro.ft import FleetStragglerAdapter, StragglerMonitor
from repro.pipeline import (OnlineCapController, ReferenceLibrary,
                            stream_profile_once, stream_profile_workload)
from repro.sched import PowerAwareScheduler
from repro.store import Encoded
from repro.telemetry import TPUPowerModel, simulate, stream_telemetry
from repro.telemetry.kernel_stream import (micro_gemm, micro_idle_burst,
                                           micro_spmv_compute,
                                           micro_spmv_memory, micro_stencil)

MODEL = TPUPowerModel()
TDP = MODEL.spec.tdp_w
FREQS = (0.6, 0.8, 1.0)
GATES = dict(min_confidence=0.2, min_fraction=0.1, min_spike_samples=50)


@pytest.fixture(scope="module")
def micro_library():
    return ReferenceLibrary(
        (stream_profile_workload(s, MODEL, FREQS, TDP, seed=i,
                                 target_duration=0.5)
         for i, s in enumerate([micro_gemm(), micro_idle_burst(),
                                micro_spmv_memory(), micro_stencil()])),
        built_on="tpu-v5e")


# ---------------------------------------------------------------------------
# inventory
# ---------------------------------------------------------------------------
def test_inventory_generation_is_deterministic():
    a = DeviceInventory.generate({"tpu-v5e": 2, "tpu-v5p": 1},
                                 VariabilityModel(), seed=9)
    b = DeviceInventory.generate({"tpu-v5e": 2, "tpu-v5p": 1},
                                 VariabilityModel(), seed=9)
    assert [d.spec for d in a] == [d.spec for d in b]
    assert [d.device_id for d in a] == [d.device_id for d in b]
    c = DeviceInventory.generate({"tpu-v5e": 2, "tpu-v5p": 1},
                                 VariabilityModel(), seed=10)
    assert [d.spec for d in a] != [d.spec for d in c]


def test_zero_variability_is_exactly_nominal():
    inv = DeviceInventory.generate(3, VariabilityModel.none(), seed=4)
    assert inv.homogeneous
    for d in inv:
        assert d.spec.perf_scale == 1.0 and d.spec.power_scale == 1.0
        assert d.effective_tdp_w == V5E.tdp_w
        # everything but the variability fields matches the registry spec
        assert dataclasses.replace(d.spec) == dataclasses.replace(
            CHIP_MODELS[d.model], perf_scale=1.0, power_scale=1.0)


def test_variability_perturbs_each_device_differently():
    inv = DeviceInventory.generate(4, VariabilityModel(), seed=0)
    scales = {(d.spec.perf_scale, d.spec.power_scale) for d in inv}
    assert len(scales) == 4
    assert not inv.homogeneous
    for d in inv:
        assert 1 - 3 * 0.05 <= d.spec.perf_scale <= 1 + 3 * 0.05
        assert 1 - 3 * 0.08 <= d.spec.power_scale <= 1 + 3 * 0.08


def test_inventory_lookup_and_validation():
    inv = DeviceInventory.generate({"tpu-v5e": 1, "tpu-v6e": 1}, seed=0)
    assert len(inv) == 2 and inv.models == ["tpu-v5e", "tpu-v6e"]
    assert inv.get("tpu-v6e/000").model == "tpu-v6e"
    assert inv.nameplate_w == V5E.tdp_w + CHIP_MODELS["tpu-v6e"].tdp_w
    with pytest.raises(KeyError):
        inv.get("nope")
    with pytest.raises(KeyError):
        DeviceInventory.generate({"tpu-v9x": 1})
    dup = inv[0]
    with pytest.raises(ValueError, match="duplicate device_id"):
        DeviceInventory([dup, dup])


@pytest.mark.parametrize("n", [1, 3, 2000])
def test_inventory_index_answers_as_iteration(n):
    """Lookups by id answer exactly what iterating the device list does, at
    any size; health moves keep the views in inventory order, and an
    unknown id raises ``KeyError`` naming it."""
    inv = DeviceInventory.generate(n, VariabilityModel(), seed=7)
    assert len(inv) == n
    health = inv.device_health
    for i, d in enumerate(inv):
        assert inv.get(d.device_id) is d is inv[i]
        assert d.device_id in inv and d in inv
        assert inv.health(d.device_id) == health[d.device_id] == HEALTHY
        assert inv.is_healthy(d.device_id)
    ids = [d.device_id for d in inv]
    down = ids[::2]
    for did in reversed(down):
        inv.mark_failed(did)
    assert inv.failed_ids == down
    assert [d.device_id for d in inv.healthy] == ids[1::2]
    for did in down:
        assert inv.health(did) == FAILED and not inv.is_healthy(did)
    inv.restore(down[-1])
    assert inv.failed_ids == down[:-1]
    assert [d.device_id for d in inv.healthy] \
        == [i for i in ids if i not in down[:-1]]
    assert list(inv.device_health) == ids
    assert inv.device_health == {
        i: FAILED if i in down[:-1] else HEALTHY for i in ids}
    missing = "tpu-v5e/nope"
    for op in (inv.get, inv.health, inv.is_healthy, inv.mark_failed,
               inv.restore):
        with pytest.raises(KeyError, match=missing):
            op(missing)
    assert missing not in inv
    assert list(inv.device_health) == ids


# ---------------------------------------------------------------------------
# per-device power model
# ---------------------------------------------------------------------------
def test_nominal_device_trace_is_byte_identical_to_prefleet():
    dev = DeviceInventory.generate(1, seed=0)[0]
    base = simulate(micro_gemm(), 1.0, MODEL, target_duration=0.5, seed=3)
    got = simulate(micro_gemm(), 1.0, dev.power_model(),
                   target_duration=0.5, seed=3)
    np.testing.assert_array_equal(got.power_filtered, base.power_filtered)
    np.testing.assert_array_equal(got.power_raw, base.power_raw)


def test_power_scale_scales_drawn_power():
    hot = dataclasses.replace(V5E, power_scale=1.1)
    cool = dataclasses.replace(V5E, power_scale=0.9)
    m_hot, m_cool = TPUPowerModel(hot), TPUPowerModel(cool)
    assert m_hot.idle_w > MODEL.idle_w > m_cool.idle_w
    p = [m.steady_power(0.9, 0.2, 1.0) for m in (m_hot, MODEL, m_cool)]
    assert p[0] > p[1] > p[2]
    assert p[0] == pytest.approx(1.1 * p[1] / 1.0)


def test_perf_scale_scales_kernel_duration():
    fast = dataclasses.replace(V5E, perf_scale=1.1)
    slow = dataclasses.replace(V5E, perf_scale=0.9)
    k = micro_gemm().kernels[0]
    d = [TPUPowerModel(s).exec_kernel(k, 1.0).duration
         for s in (fast, V5E, slow)]
    assert d[0] < d[1] < d[2]


def test_device_portable_classification(micro_library):
    """A profile captured on a perturbed chip, normalized by the device's
    effective TDP, classifies to the same neighbor as the nominal chip."""
    clf = micro_library.classifier()
    nominal = stream_profile_once(micro_spmv_compute(), MODEL, TDP, seed=21)
    sel_nom = select_optimal_freq(nominal, clf)
    dev = DeviceInventory.generate(
        1, VariabilityModel(sigma_perf=0.0, sigma_power=0.08), seed=2)[0]
    assert dev.spec.power_scale != 1.0
    raw = stream_profile_once(micro_spmv_compute(), dev.power_model(),
                       dev.effective_tdp_w, seed=21)
    sel_dev = select_optimal_freq(raw, clf)
    assert sel_dev.power_neighbor == sel_nom.power_neighbor
    assert sel_dev.f_pwr == sel_nom.f_pwr
    # normalize_profile reframes an existing nameplate-relative profile
    nameplate_frame = stream_profile_once(micro_spmv_compute(), dev.power_model(),
                                   dev.nameplate_w, seed=21)
    renormed = dev.normalize_profile(nameplate_frame)
    assert renormed.tdp == dev.effective_tdp_w
    np.testing.assert_array_equal(renormed.power_trace,
                                  nameplate_frame.power_trace)


# ---------------------------------------------------------------------------
# telemetry mux
# ---------------------------------------------------------------------------
def _job_stream(stream_fn, seed, device_id=""):
    return stream_telemetry(stream_fn(), 1.0, MODEL, seed=seed,
                            target_duration=0.5, chunk_samples=100,
                            device_id=device_id)


def test_mux_preserves_per_job_order_and_merges_by_time():
    mux = FleetTelemetryMux()
    metas = {}
    for i, fn in enumerate([micro_gemm, micro_idle_burst]):
        meta, chunks = _job_stream(fn, seed=i, device_id=f"dev/{i}")
        metas[f"job{i}"] = meta
        mux.add_job(f"job{i}", meta, chunks)
    seen = {}
    last_t = -1.0
    for fc in mux:
        assert fc.t_end >= last_t            # global time order
        last_t = fc.t_end
        assert fc.device_id == f"dev/{fc.job_id[-1]}"
        seen.setdefault(fc.job_id, []).append(fc.chunk)
    for job_id, chunks in seen.items():
        idx = [c.start_index for c in chunks]
        assert idx == sorted(idx)            # per-job order intact
        assert idx[0] == 0
        n = idx[-1] + len(chunks[-1].energy_j)
        assert n == metas[job_id].n_samples  # nothing dropped
    assert set(seen) == {"job0", "job1"}


def test_mux_rejects_duplicate_job_and_honors_t_start():
    mux = FleetTelemetryMux()
    meta, chunks = _job_stream(micro_gemm, seed=0)
    mux.add_job("a", meta, chunks)
    with pytest.raises(ValueError, match="duplicate job_id"):
        mux.add_job("a", meta, iter(()))
    # a job arriving much later drains strictly after an early one
    meta_b, chunks_b = _job_stream(micro_gemm, seed=0)
    mux.add_job("b", meta_b, chunks_b, t_start=1e6)
    order = [fc.job_id for fc in mux]
    assert order == ["a"] * order.count("a") + ["b"] * order.count("b")


# ---------------------------------------------------------------------------
# FleetCapController: the pinned homogeneous-fleet invariance
# ---------------------------------------------------------------------------
def test_homogeneous_fleet_is_byte_identical_to_single_job_path(
        micro_library):
    """ISSUE 3 acceptance: variability disabled + one device type ->
    every fleet decision (neighbor, bin size, cap, confidence, fraction)
    is byte-identical to the PR 2 per-job ``OnlineCapController.run``."""
    inv = DeviceInventory.generate(3, VariabilityModel.none(), seed=0)
    jobs = [(micro_gemm, 0), (micro_spmv_memory, 1), (micro_spmv_compute, 2)]

    fleet = FleetCapController(micro_library, budget_w=1e9, **GATES)
    mux = FleetTelemetryMux()
    ids = []
    for (fn, seed), dev in zip(jobs, inv):
        meta, chunks = _job_stream(fn, seed=seed, device_id=dev.device_id)
        ids.append(fleet.admit(dev, meta, chips=4))
        mux.add_job(ids[-1], meta, chunks)
    result = fleet.run(mux)

    for (fn, seed), dev, job_id in zip(jobs, inv, ids):
        single = OnlineCapController(micro_library, actuator=None,
                                     **GATES)
        meta, chunks = _job_stream(fn, seed=seed)
        expect = single.run(meta, chunks, V5E.tdp_w)
        got = result.decisions[job_id]
        assert got.selection == expect.selection      # neighbor + bin size
        assert got.cap == expect.cap
        assert got.confidence == expect.confidence
        assert got.fraction == expect.fraction
        assert got.n_samples == expect.n_samples
        assert got.early == expect.early
        assert got.device_id == dev.device_id
    # the fleet plan never exceeds its budget, at any repack
    for res in fleet.repacks:
        assert res.planned_power_w <= res.budget_w


def test_fleet_controller_gates_budget_and_early_stop(micro_library):
    inv = DeviceInventory.generate(2, VariabilityModel(), seed=1)
    fleet = FleetCapController(micro_library, budget_w=1.0, **GATES)
    mux = FleetTelemetryMux()
    for i, (fn, dev) in enumerate(zip([micro_gemm, micro_spmv_memory], inv)):
        meta, chunks = _job_stream(fn, seed=i, device_id=dev.device_id)
        mux.add_job(fleet.admit(dev, meta, chips=8), meta, chunks)
    with pytest.raises(ValueError, match="duplicate job_id"):
        fleet.admit(inv[0], meta, job_id=list(fleet.jobs)[0])
    result = fleet.run(mux)
    assert len(result.decisions) == 2
    # a 1 W budget can place nothing, but decisions still happen
    assert result.schedule.placed == []
    assert len(result.schedule.deferred) == 2
    assert result.schedule.planned_power_w == 0.0
    if result.early_decisions:
        assert result.chunks_dropped > 0
    # per-job actuators were driven on the jobs' own devices
    for job in fleet.jobs.values():
        assert job.actuator.device_id == job.device.device_id
        assert job.actuator.get_cap() == result.decisions[job.job_id].cap


# ---------------------------------------------------------------------------
# batched engine: bit-for-bit identity with per-job ProfileBuilders
# ---------------------------------------------------------------------------
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.pipeline import BatchProfileEngine, ProfileBuilder  # noqa: E402
from repro.telemetry.simulator import TelemetryChunk, TraceMeta  # noqa: E402


def _synthetic_counters(seed, n, name="synthetic"):
    rng = np.random.default_rng(seed)
    power = rng.uniform(0.0, 1.3 * TDP, size=n)
    busy = (rng.random(n) < 0.8).astype(float)
    energy_ctr = np.concatenate([[0.0], np.cumsum(power * 1e-3)])
    busy_ctr = np.concatenate([[0.0], np.cumsum(busy * 1e-3)])
    meta = TraceMeta(name=name, domain="test", sample_dt=1e-3, n_samples=n,
                     exec_time=1.0, app_sm_util=0.5, app_dram_util=0.5,
                     kernel_rows=[])
    return meta, energy_ctr, busy_ctr


def _assert_builder_match(ref, sb):
    assert ref.n_ingested == sb.n_ingested
    assert ref.n_committed == sb.n_committed
    assert ref.fraction == sb.fraction
    assert ref.spike_count() == sb.spike_count()
    for c in ref.bin_sizes:
        np.testing.assert_array_equal(ref.spike_vector(c),
                                      sb.spike_vector(c))
    a, b = ref.snapshot(), sb.snapshot()
    np.testing.assert_array_equal(a.power_trace, b.power_trace)
    assert a.fraction == b.fraction and a.n_samples == b.n_samples


@given(st.integers(min_value=0, max_value=10_000))
@settings(max_examples=20, deadline=None)
def test_batched_engine_is_bit_identical_to_perjob_builders(scenario_seed):
    """ISSUE 7 pin: under arbitrary job interleavings, chunk splits, and
    mid-stream retire/admit (with slot reuse), the columnar engine's state
    is bit-for-bit identical to one ``ProfileBuilder`` per job — spike
    histograms, committed traces, snapshots, and finalized profiles."""
    rng = np.random.default_rng(scenario_seed)
    eng = BatchProfileEngine(capacity=2)       # force slot-array growth

    def new_job(name):
        n = int(rng.integers(1, 1200))
        meta, e, b = _synthetic_counters(int(rng.integers(0, 10 ** 6)), n,
                                         name)
        cuts = sorted({int(c) for c in
                       rng.integers(1, max(n, 2),
                                    size=int(rng.integers(0, 6)))
                       if 0 < c < n})
        bounds = [0] + cuts + [n]
        chunks = [TelemetryChunk(energy_j=e[i + 1:j + 1],
                                 busy_s=b[i + 1:j + 1],
                                 sample_dt=meta.sample_dt, start_index=i)
                  for i, j in zip(bounds[:-1], bounds[1:])]
        return dict(ref=ProfileBuilder(meta, TDP),
                    sb=eng.builder(meta, TDP), chunks=chunks, pos=0)

    live = {f"j{k}": new_job(f"j{k}")
            for k in range(int(rng.integers(2, 5)))}
    admits_left, next_id = 3, 100
    while live:
        remaining = [j for j in sorted(live)
                     if live[j]["pos"] < len(live[j]["chunks"])]
        if remaining:
            # random tick: a random subset of unfinished jobs polls at once
            tick = [j for j in remaining if rng.random() < 0.7] \
                or [remaining[0]]
            slots, chunks = [], []
            for jid in tick:
                job = live[jid]
                ck = job["chunks"][job["pos"]]
                job["pos"] += 1
                job["ref"].ingest(ck)
                slots.append(job["sb"].slot)
                chunks.append(ck)
            eng.ingest_batch(slots, chunks)
            _assert_builder_match(live[tick[0]]["ref"], live[tick[0]]["sb"])
        # mid-stream retire (slot goes back to the free list mid-run)
        if rng.random() < 0.15:
            jid = sorted(live)[int(rng.integers(len(live)))]
            job = live.pop(jid)
            _assert_builder_match(job["ref"], job["sb"])
            job["sb"].release()
            if admits_left and rng.random() < 0.5:   # slot reuse
                admits_left -= 1
                live[f"n{next_id}"] = new_job(f"n{next_id}")
                next_id += 1
        # fully-fed jobs: finalize must match bit-for-bit, then free
        for jid in [j for j in sorted(live)
                    if live[j]["pos"] >= len(live[j]["chunks"])]:
            job = live.pop(jid)
            _assert_builder_match(job["ref"], job["sb"])
            a, b = job["ref"].finalize(), job["sb"].finalize()
            np.testing.assert_array_equal(a.power_trace, b.power_trace)
            assert a.fraction == b.fraction and a.n_samples == b.n_samples
            assert a.complete and b.complete
            job["sb"].release()


def test_batched_engine_poisoned_tick_is_all_or_nothing():
    """A poisoned chunk raises the per-job builder's message and leaves
    every slot in the tick untouched (no partial mutation)."""
    eng = BatchProfileEngine()
    meta_a, ea, ba = _synthetic_counters(1, 300, "a")
    meta_b, eb, bb = _synthetic_counters(2, 300, "b")
    sa, sb_ = eng.builder(meta_a, TDP), eng.builder(meta_b, TDP)
    bad = eb[1:301].copy()
    bad[50] = np.nan
    with pytest.raises(ValueError, match="NaN/non-finite energy_j"):
        eng.ingest_batch(
            (sa.slot, sb_.slot),
            (TelemetryChunk(energy_j=ea[1:301], busy_s=ba[1:301],
                            sample_dt=1e-3, start_index=0),
             TelemetryChunk(energy_j=bad, busy_s=bb[1:301],
                            sample_dt=1e-3, start_index=0)))
    assert sa.n_ingested == 0 and sb_.n_ingested == 0


def test_mux_ticks_batches_equal_timestamps_in_chunk_order():
    """ISSUE 7 satellite: ``ticks()`` yields all equal-``t_end`` chunks as
    one batch, and concatenating the batches reproduces ``__iter__``'s
    exact chunk sequence."""
    def build():
        mux = FleetTelemetryMux()
        for i, fn in enumerate([micro_gemm, micro_idle_burst,
                                micro_spmv_memory]):
            meta, chunks = _job_stream(fn, seed=i, device_id=f"dev/{i}")
            mux.add_job(f"job{i}", meta, chunks)
        return mux
    flat = [(fc.job_id, fc.t_end, fc.chunk.start_index)
            for fc in build()]
    ticked = []
    n_batches = 0
    for batch in build().ticks():
        n_batches += 1
        assert len({fc.t_end for fc in batch}) == 1   # one poll instant
        ticked.extend((fc.job_id, fc.t_end, fc.chunk.start_index)
                      for fc in batch)
    assert ticked == flat
    assert n_batches < len(flat)     # equal timestamps really coalesced


def test_fleet_batched_engine_matches_perjob_engine(micro_library):
    """Fleet-level pin: the controller's tick path lands the decisions, the
    final packing and the dropped-chunk count of the plain per-job
    references — ``OnlineCapController.run`` per job (one standalone
    ``ProfileBuilder`` each) at its device's effective TDP, then one
    ``PowerAwareScheduler.pack`` over the plans."""
    jobs = [(micro_gemm, 0), (micro_spmv_memory, 1), (micro_spmv_compute, 2),
            (micro_idle_burst, 3)]
    budget = 5000.0
    inv = DeviceInventory.generate(4, VariabilityModel(), seed=7)
    fleet = FleetCapController(micro_library, budget_w=budget, **GATES)
    mux = FleetTelemetryMux()
    streams = []
    for (fn, seed), dev in zip(jobs, inv):
        meta, chunks = _job_stream(fn, seed=seed, device_id=dev.device_id)
        chunks = list(chunks)
        job_id = fleet.admit(dev, meta, chips=4)
        mux.add_job(job_id, meta, chunks)
        streams.append((job_id, dev, meta, chunks))
    got = fleet.run(mux)

    clf = micro_library.classifier()
    sched = PowerAwareScheduler(clf, tdp_w=0.0, quantile="p99")
    decisions, plans, dropped = {}, [], 0
    for job_id, dev, meta, chunks in streams:
        ctl = OnlineCapController(clf, device_id=dev.device_id, **GATES)
        feed = iter(chunks)
        d = decisions[job_id] = ctl.run(meta, feed, dev.effective_tdp_w)
        dropped += sum(1 for _ in feed)     # left unread by the decision
        plans.append(sched.plan_from_selection(d.selection, 4, dev,
                                               job_id=job_id))
    assert list(got.decisions) == list(decisions)
    for job_id, expect in decisions.items():
        assert got.decisions[job_id] == expect
    assert got.early_decisions > 0 and dropped > 0
    assert got.schedule == sched.pack(plans, budget)
    assert got.chunks_dropped == dropped


class _RecordingJournal:
    """Stands in for a session store: keeps every record, in order."""

    def __init__(self):
        self.records = []

    def record(self, kind, **data):
        self.records.append((kind, {k: v.value if isinstance(v, Encoded)
                                    else v for k, v in data.items()}))

    def batch(self):
        return nullcontext()

    def flush_snapshot(self):
        pass


def _tick_vs_per_chunk(library, admit_and_feed, fleet_kw):
    """Drive the same fleet twice: every chunk of ``feed`` in one
    ``ingest_tick``, then one ``ingest`` per chunk.  Returns both
    ``(fleet, tick decisions, result, journal records, engine calls)``."""
    out = []
    for tick in (True, False):
        journal = _RecordingJournal()
        fleet = FleetCapController(library, journal=journal,
                                   **{**GATES, **fleet_kw()})
        feed = admit_and_feed(fleet)
        calls = []
        ingest_batch = fleet.engine.ingest_batch

        def counted(slots, chunks):
            calls.append(len(slots))
            return ingest_batch(slots, chunks)
        fleet.engine.ingest_batch = counted
        if tick:
            decisions = fleet.ingest_tick(feed)
        else:
            decisions = [d for d in map(fleet.ingest, feed) if d is not None]
        mid = (list(fleet.events),
               [(r.planned_power_w, r.nameplate_power_w, r.budget_w)
                for r in fleet.repacks], fleet.repacks[-1])
        out.append((fleet, decisions, mid, fleet.finalize(),
                    journal.records, calls))
    return out


def _assert_same_outcome(tick, per_chunk):
    fleet_t, dec_t, mid_t, res_t, rec_t, _ = tick
    fleet_c, dec_c, mid_c, res_c, rec_c, _ = per_chunk
    assert dec_t == dec_c
    assert mid_t == mid_c                  # events, every re-pack, the last
    assert res_t.decisions == res_c.decisions
    assert list(res_t.decisions) == list(res_c.decisions)
    assert res_t.schedule == res_c.schedule
    assert res_t.repacks == res_c.repacks
    assert res_t.chunks_dropped == res_c.chunks_dropped
    assert res_t.events == res_c.events
    assert rec_t == rec_c                  # journal records, in order
    for job_id, job in fleet_t.jobs.items():
        assert job.builder.n_ingested \
            == fleet_c.jobs[job_id].builder.n_ingested


@pytest.mark.parametrize("chunk_samples", [25, 60])
def test_straggler_tick_equals_per_chunk_feed(micro_library, chunk_samples):
    """One ``ingest_tick`` over a whole interleaved feed, with a straggler
    adapter whose degrade fires mid-batch, lands what feeding the chunks
    one at a time through ``ingest`` lands — and still advances the engine
    many rows per call, not one chunk at a time.  With 60-sample chunks
    the slow device's job decides in the run that the degrade cuts short,
    so the degrade drains it only if that run lands first; with 25-sample
    chunks the degrade comes first and the job migrates the moment it
    decides."""
    def inventory():
        return DeviceInventory.generate(3, VariabilityModel.none(), seed=0)

    def admit_and_feed(fleet):
        inv = fleet.inventory
        streams = []
        for i, fn in enumerate([micro_gemm, micro_spmv_memory,
                                micro_stencil]):
            meta, chunks = stream_telemetry(
                fn(), 1.0, inv[i].power_model(), seed=i,
                target_duration=0.5, chunk_samples=chunk_samples,
                device_id=inv[i].device_id)
            streams.append((fleet.admit(inv[i], meta, chips=2),
                            list(chunks)))
        # device 2 polls at a tenth of the others' cadence
        rounds = min(len(c) for _, c in streams)
        return [FleetChunk(job_id, inv[i].device_id,
                           r * (0.5 if i == 2 else 0.05), chunks[r])
                for r in range(rounds)
                for i, (job_id, chunks) in enumerate(streams)]

    def fleet_kw():
        return dict(budget_w=1e9, inventory=inventory(),
                    straggler_adapter=FleetStragglerAdapter(
                        StragglerMonitor(min_samples=4, k=4.0)),
                    min_confidence=0.0, min_spike_samples=10)

    tick, per_chunk = _tick_vs_per_chunk(micro_library, admit_and_feed,
                                         fleet_kw)
    _assert_same_outcome(tick, per_chunk)
    fleet, decisions, (events, _, _), _, _, calls = tick
    slow = fleet.inventory[2].device_id
    assert [(e.kind, e.device_id) for e in events] == [
        ("degrade", slow), ("migrate", slow)]
    assert len(decisions) == 3
    assert fleet.inventory.health(slow) == DEGRADED
    assert max(calls) > 1 and len(calls) < len(per_chunk[-1])


def test_tick_with_repeated_jobs_equals_per_chunk_feed(micro_library):
    """A batch that holds several chunks of the same jobs (here a whole
    feed, the last of whose jobs profiles to completion) cuts into runs of
    distinct jobs and lands what the per-chunk feed lands.  The first job
    decides early, so its next chunk is the one that cuts the run in
    which it decides: that chunk is dropped only if the run lands before
    the chunk is filtered."""
    def admit_and_feed(fleet):
        inv = DeviceInventory.generate(3, VariabilityModel(), seed=3)
        mux = FleetTelemetryMux()
        for i, (fn, dev) in enumerate(zip(
                [micro_gemm, micro_spmv_memory, micro_idle_burst], inv)):
            meta, chunks = _job_stream(fn, seed=i, device_id=dev.device_id)
            mux.add_job(fleet.admit(dev, meta, chips=4,
                                    profile_to_completion=i == 2),
                        meta, chunks)
        return list(mux)

    tick, per_chunk = _tick_vs_per_chunk(
        micro_library, admit_and_feed, lambda: dict(budget_w=5000.0))
    _assert_same_outcome(tick, per_chunk)
    fleet, decisions, _, result, _, calls = tick
    assert decisions and result.chunks_dropped > 0
    assert max(calls) > 1 and len(calls) < len(per_chunk[-1])
    ptc = list(fleet.jobs.values())[-1]
    assert ptc.builder.n_ingested == ptc.builder.meta.n_samples


@pytest.mark.parametrize("engine", ["batched", "perjob"])
def test_engine_profiles_carry_their_p90(micro_library, monkeypatch, engine):
    """An engine-minted profile's p90 comes from its slot's order
    statistics: the sweep (``observe_fleet``), ``finalize_job`` and
    ``finalize_fleet`` take no percentile over its trace.  The same streams
    through standalone ``ProfileBuilder``s (``OnlineCapController.run``)
    still take ``np.percentile``."""
    from repro.core import spikes
    from repro.pipeline import online

    for ref in micro_library:          # the references' memo, filled once
        ref.p_quantile(90.0)
    calls = []
    p_quantile, percentile = spikes.p_quantile, np.percentile

    def spy_p_quantile(power, tdp, q=90.0):
        calls.append("p_quantile")
        return p_quantile(power, tdp, q)

    def spy_percentile(a, q, *args, **kwargs):
        calls.append("percentile")
        return percentile(a, q, *args, **kwargs)

    monkeypatch.setattr(spikes, "p_quantile", spy_p_quantile)
    monkeypatch.setattr(online.np, "percentile", spy_percentile)
    inv = DeviceInventory.generate(4, VariabilityModel(), seed=7)
    # no early decision clears the gate: every tick sweeps every job, and
    # every decision comes from a stream end
    gates = {**GATES, "min_confidence": 1.01}
    streams = [(dev, *_job_stream(fn, seed=i, device_id=dev.device_id))
               for i, (fn, dev) in enumerate(zip(
                   (micro_gemm, micro_spmv_memory, micro_spmv_compute,
                    micro_idle_burst), inv))]
    if engine == "perjob":
        for dev, meta, chunks in streams:
            d = OnlineCapController(micro_library, **gates).run(
                meta, chunks, dev.effective_tdp_w)
            assert not d.early
        assert "percentile" in calls
        return
    fleet = FleetCapController(micro_library, budget_w=5000.0, **gates)
    mux = FleetTelemetryMux()
    jobs = []
    for dev, meta, chunks in streams:
        jobs.append(fleet.admit(dev, meta, chips=4))
        mux.add_job(jobs[-1], meta, chunks)
    swept = 0
    for k, batch in enumerate(mux.ticks()):
        fleet.ingest_tick(batch)
        swept += 1
        if k == 2:
            fleet.finalize_job(jobs[0])          # one stream end alone
    result = fleet.finalize()                    # the rest in one sweep
    assert swept > 3 and len(result.decisions) == len(jobs)
    assert calls == []
