"""Fault-tolerance tests (ISSUE 5 acceptance): device health state, failure/
migration/restore paths with the classifier call-count pinned at ZERO across
migrations, elastic shrink of multi-chip jobs, straggler-driven proactive
drain, the no-failure byte-identity pin (an FT-wired fleet that never fails
equals the plain path), the session surface + JSON codec for fleet events,
and a hypothesis property: the packed budget is never exceeded under ANY
failure schedule.  Plus the satellite pins: ``ElasticPlan`` loss accounting,
``rescale_batch``'s per-device-batch contract, ``StragglerMonitor`` aging,
and the ``core.baselines`` all-excluded contract."""
import pytest
from hypothesis import example, given, settings, strategies as st

from repro.api import (MinosSession, OnlineCapController, ReferenceLibrary,
                       SessionReport, TPUPowerModel,
                       count_classifier_calls as _count_classifier_calls,
                       from_json, stream_profile_once,
                       stream_profile_workload, stream_telemetry, to_json)
from repro.configs.base import MeshConfig
from repro.core.baselines import mean_power_neighbor, util_only_neighbor
from repro.fleet import (DEGRADED, FAILED, HEALTHY, DeviceInventory,
                         FleetCapController, FleetChunk, FleetTelemetryMux,
                         VariabilityModel)
from repro.ft import (FleetStragglerAdapter, StragglerMonitor, plan_new_mesh,
                      rescale_batch)
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


def _job_stream(stream_fn, device, seed):
    return stream_telemetry(stream_fn(), 1.0, device.power_model(),
                            seed=seed, target_duration=0.5,
                            chunk_samples=100, device_id=device.device_id)


# ---------------------------------------------------------------------------
# inventory health state
# ---------------------------------------------------------------------------
def test_inventory_health_lifecycle():
    inv = DeviceInventory.generate(3, VariabilityModel.none(), seed=0)
    ids = [d.device_id for d in inv]
    assert inv.device_health == {i: HEALTHY for i in ids}
    assert [d.device_id for d in inv.healthy] == ids
    inv.mark_failed(ids[0])
    inv.mark_degraded(ids[1])
    assert inv.health(ids[0]) == FAILED and not inv.is_healthy(ids[0])
    assert inv.health(ids[1]) == DEGRADED
    assert [d.device_id for d in inv.healthy] == [ids[2]]
    assert inv.failed_ids == [ids[0]]
    assert inv.healthy_nameplate_w == pytest.approx(
        inv.nameplate_w - inv.get(ids[0]).nameplate_w)
    inv.restore(ids[0])
    inv.restore(ids[1])
    assert inv.device_health == {i: HEALTHY for i in ids}
    with pytest.raises(KeyError):
        inv.mark_failed("tpu-v9x/000")
    with pytest.raises(KeyError):
        inv.health("nope")


# ---------------------------------------------------------------------------
# failure -> migration (the zero-classification pin)
# ---------------------------------------------------------------------------
def _decided_fleet(micro_library, n_devices=3, seed=0):
    """A fleet with every job decided (streams fully pumped)."""
    inv = DeviceInventory.generate(n_devices, VariabilityModel(), seed=seed)
    fleet = FleetCapController(micro_library, budget_w=1e9, inventory=inv,
                               **GATES)
    mux = FleetTelemetryMux()
    for i, fn in enumerate([micro_gemm, micro_spmv_memory]):
        meta, chunks = _job_stream(fn, inv[i], seed=i)
        mux.add_job(fleet.admit(inv[i], meta, chips=4), meta, chunks)
    fleet.run(mux)
    return inv, fleet


def test_fail_device_migrates_decided_jobs_without_classifying(micro_library):
    inv, fleet = _decided_fleet(micro_library)
    job = next(iter(fleet.jobs.values()))
    old_device, old_plan = job.device, job.plan
    assert old_plan is not None
    calls = _count_classifier_calls(fleet.clf)
    repacks_before = len(fleet.repacks)

    events = fleet.fail_device(old_device.device_id)

    assert calls["n"] == 0                     # the acceptance pin
    assert [e.kind for e in events] == ["fail", "migrate"]
    assert events[1].job_id == job.job_id
    assert events[1].to_device_id == job.device.device_id
    assert job.device.device_id != old_device.device_id
    assert inv.health(old_device.device_id) == FAILED
    # the plan was re-costed on the new device's effective TDP: same cap,
    # same selection, new watts frame
    assert job.plan.cap == old_plan.cap
    assert job.plan.selection == old_plan.selection
    assert job.plan.device_id == job.device.device_id
    rel = old_plan.predicted_p90_w / old_device.effective_tdp_w
    assert job.plan.predicted_p90_w == pytest.approx(
        rel * job.device.effective_tdp_w, rel=1e-12)
    # the cap was re-asserted on the new device's actuator
    assert job.actuator.device_id == job.device.device_id
    assert job.actuator.get_cap() == job.decision.cap
    # the failure ended in exactly one repack, still inside the budget
    assert len(fleet.repacks) == repacks_before + 1
    assert fleet.repacks[-1].planned_power_w <= fleet.budget_w
    # the failed device hosts nothing
    assert all(j.device.device_id != old_device.device_id
               for j in fleet.jobs.values())


def test_fail_device_requires_inventory(micro_library):
    fleet = FleetCapController(micro_library, budget_w=1e9, **GATES)
    with pytest.raises(ValueError, match="inventory"):
        fleet.fail_device("tpu-v5e/000")
    session = MinosSession(micro_library, **GATES)     # no inventory
    with pytest.raises(ValueError, match="inventory"):
        session.fail_device("tpu-v5e/000")


def test_fail_device_mid_profile_restarts_on_new_device(micro_library):
    inv = DeviceInventory.generate(2, VariabilityModel(), seed=3)
    fleet = FleetCapController(micro_library, budget_w=1e9, inventory=inv,
                               **GATES)
    meta, chunks = _job_stream(micro_gemm, inv[0], seed=5)
    job_id = fleet.admit(inv[0], meta, chips=2)
    chunks = list(chunks)
    fleet.ingest_chunk(job_id, chunks[0])      # some partial trace
    job = fleet.jobs[job_id]
    assert job.decision is None and job.builder.n_ingested > 0

    events = fleet.fail_device(inv[0].device_id)
    assert [e.kind for e in events] == ["fail", "migrate"]
    assert events[1].detail == "reprofile"
    assert job.device is inv[1]
    # the partial trace died with the device; the builder restarted in the
    # new device's normalization frame
    assert job.builder.n_ingested == 0
    assert job.builder.tdp == inv[1].effective_tdp_w
    # stale chunks from the dead device are discarded on the mux path
    stale = FleetChunk(job_id, inv[0].device_id, 1.0, chunks[1])
    assert fleet.ingest(stale) is None
    assert job.builder.n_ingested == 0
    # the un-tagged feed path can't tell stale from re-run: it demands an
    # explicit restart instead of mixing frames
    with pytest.raises(ValueError, match="restart"):
        fleet.ingest_chunk(job_id, chunks[1])
    # a re-run on the new device decides normally
    meta2, chunks2 = _job_stream(micro_gemm, inv[1], seed=6)
    fleet.restart_profile(job_id, meta2)
    for chunk in chunks2:
        if fleet.ingest_chunk(job_id, chunk) is not None:
            break
    decision = fleet.finalize_job(job_id)
    assert decision.device_id == inv[1].device_id


def test_fail_device_strands_jobs_when_no_healthy_device(micro_library):
    inv, fleet = _decided_fleet(micro_library, n_devices=2)
    calls = _count_classifier_calls(fleet.clf)
    fleet.fail_device(inv[1].device_id)        # second job moves to inv[0]
    events = fleet.fail_device(inv[0].device_id)
    assert {e.kind for e in events} == {"fail", "strand"}
    assert all(j.plan is None for j in fleet.jobs.values())
    assert fleet.repacks[-1].placed == []      # stranded jobs draw nothing
    assert calls["n"] == 0
    # decisions survive stranding: capacity can come back later
    assert all(j.decision is not None for j in fleet.jobs.values())

    # ...and when it does, restore re-places the strandees — still without
    # a single classification
    events = fleet.restore_device(inv[1].device_id)
    assert [e.kind for e in events] == ["restore", "migrate", "migrate"]
    assert all(j.plan is not None for j in fleet.jobs.values())
    assert all(j.device is inv[1] for j in fleet.jobs.values())
    assert len(fleet.repacks[-1].placed) == 2
    assert calls["n"] == 0


def test_restore_replaces_jobs_stranded_by_a_degrade_drain(micro_library):
    """A degrade drain with nowhere to go strands the job on the straggler;
    restoring capacity elsewhere must re-place it (zero classifier calls)."""
    inv, fleet = _decided_fleet(micro_library, n_devices=2)
    calls = _count_classifier_calls(fleet.clf)
    fleet.fail_device(inv[1].device_id)        # everyone ends up on inv[0]
    events = fleet.degrade_device(inv[0].device_id)
    assert {e.kind for e in events} == {"degrade", "strand"}
    assert all(j.plan is None for j in fleet.jobs.values())

    events = fleet.restore_device(inv[1].device_id)
    assert [e.kind for e in events] == ["restore", "migrate", "migrate"]
    assert all(j.plan is not None for j in fleet.jobs.values())
    assert all(j.device is inv[1] for j in fleet.jobs.values())
    assert len(fleet.repacks[-1].placed) == 2
    assert calls["n"] == 0


def test_span_job_deciding_on_degraded_device_drains_immediately(
        micro_library):
    """degrade_device's deferred contract must hold for multi-chip spans
    too: a span job that decides while a member is degraded shrinks the bad
    member away at decision time."""
    inv = DeviceInventory.generate(3, VariabilityModel(), seed=9)
    fleet = FleetCapController(micro_library, budget_w=1e9, inventory=inv,
                               **GATES)
    meta, chunks = _job_stream(micro_gemm, inv[1], seed=4)
    job_id = fleet.admit(inv[1], meta, chips=4, devices=(inv[0], inv[1]))
    chunks = list(chunks)
    fleet.ingest_chunk(job_id, chunks[0])
    fleet.degrade_device(inv[0].device_id)     # undecided span: no-op now
    job = fleet.jobs[job_id]
    assert job.decision is None and inv[0] in job.devices

    for chunk in chunks[1:]:
        if fleet.ingest_chunk(job_id, chunk) is not None:
            break
    fleet.finalize_job(job_id)
    assert any(e.kind == "shrink" and e.job_id == job_id
               for e in fleet.events)
    assert inv[0] not in job.devices
    assert job.chips == 2 and job.plan.chips == 2
    assert job.plan.device_id == inv[1].device_id


def test_restore_device_rejoins_placement_pool(micro_library):
    inv, fleet = _decided_fleet(micro_library)
    failed_id = inv[0].device_id
    fleet.fail_device(failed_id)
    meta, _ = _job_stream(micro_gemm, inv[0], seed=9)
    with pytest.raises(ValueError, match="device is failed"):
        fleet.admit(inv[0], meta, job_id="late-arrival")
    events = fleet.restore_device(failed_id)
    assert events[0].kind == "restore" and "failed" in events[0].detail
    assert inv.health(failed_id) == HEALTHY
    fleet.admit(inv[0], meta, job_id="late-arrival")   # admissible again


# ---------------------------------------------------------------------------
# multi-chip jobs: elastic shrink on partial span loss
# ---------------------------------------------------------------------------
def test_partial_span_loss_shrinks_through_elastic_remesh(micro_library):
    inv = DeviceInventory.generate(4, VariabilityModel(), seed=1)
    fleet = FleetCapController(micro_library, budget_w=1e9, inventory=inv,
                               **GATES)
    span = (inv[0], inv[1], inv[2])
    meta, chunks = _job_stream(micro_gemm, inv[0], seed=2)
    job_id = fleet.admit(inv[0], meta, chips=12, devices=span,
                         global_batch=96)
    for chunk in chunks:
        if fleet.ingest_chunk(job_id, chunk) is not None:
            break
    fleet.finalize_job(job_id)
    job = fleet.jobs[job_id]
    assert job.plan.chips == 12
    calls = _count_classifier_calls(fleet.clf)

    events = fleet.fail_device(inv[1].device_id)
    assert calls["n"] == 0
    assert [e.kind for e in events] == ["fail", "shrink"]
    # 12 chips over 3 devices -> lose 4, survivors hold 8 = a power of two
    assert job.chips == 8
    assert job.plan.chips == 8
    assert {d.device_id for d in job.devices} == \
        {inv[0].device_id, inv[2].device_id}
    # per-device batch constant: 96/12 = 8 per chip -> 64 on 8 chips
    assert job.global_batch == 64
    assert "chips 12->8" in events[1].detail

    # losing another span member drops to the largest power of two (4)
    events = fleet.fail_device(inv[2].device_id)
    assert events[1].kind == "shrink"
    assert job.chips == 4 and job.global_batch == 32
    assert job.device is inv[0]
    assert calls["n"] == 0


def test_partial_span_loss_of_primary_restarts_profiling(micro_library):
    inv = DeviceInventory.generate(3, VariabilityModel(), seed=6)
    fleet = FleetCapController(micro_library, budget_w=1e9, inventory=inv,
                               **GATES)
    meta, chunks = _job_stream(micro_gemm, inv[0], seed=7)
    job_id = fleet.admit(inv[0], meta, chips=4, devices=(inv[0], inv[1]))
    fleet.ingest_chunk(job_id, next(iter(chunks)))
    job = fleet.jobs[job_id]

    events = fleet.fail_device(inv[0].device_id)   # the profiling frame
    assert events[1].kind == "shrink"
    assert job.chips == 2 and job.device is inv[1]
    # the partial trace was captured on the lost primary: restart there too
    assert job.builder.n_ingested == 0
    assert job.builder.tdp == inv[1].effective_tdp_w
    with pytest.raises(ValueError, match="restart"):
        fleet.ingest_chunk(job_id, next(iter(chunks)))
    meta2, chunks2 = _job_stream(micro_gemm, inv[1], seed=8)
    fleet.restart_profile(job_id, meta2)
    fleet.ingest_chunk(job_id, next(iter(chunks2)))   # feeds again


def test_admit_validates_span(micro_library):
    inv = DeviceInventory.generate(3, VariabilityModel.none(), seed=0)
    fleet = FleetCapController(micro_library, budget_w=1e9, inventory=inv,
                               **GATES)
    meta, _ = _job_stream(micro_gemm, inv[0], seed=0)
    with pytest.raises(ValueError, match="part of the span"):
        fleet.admit(inv[0], meta, chips=4, devices=(inv[1], inv[2]))
    with pytest.raises(ValueError, match="divide evenly"):
        fleet.admit(inv[0], meta, chips=5, devices=(inv[0], inv[1]))
    with pytest.raises(ValueError, match="duplicate device"):
        fleet.admit(inv[0], meta, chips=4, devices=(inv[0], inv[0]))


# ---------------------------------------------------------------------------
# straggler-driven proactive drain
# ---------------------------------------------------------------------------
def test_straggler_adapter_flags_slow_device():
    adapter = FleetStragglerAdapter(StragglerMonitor(min_samples=5, k=4.0))

    class _FC:                                  # minimal FleetChunk stand-in
        def __init__(self, device_id, t_end):
            self.device_id, self.t_end = device_id, t_end

    for i in range(8):
        for d, cadence in (("dev/0", 0.05), ("dev/1", 0.05), ("dev/2", 0.5)):
            adapter.observe(_FC(d, i * cadence))
    assert adapter.degraded() == ["dev/2"]
    assert adapter.devices() == ["dev/0", "dev/1", "dev/2"]
    assert adapter.dead() == []


def test_degrade_drains_decided_jobs_and_migrates_on_decide(micro_library):
    inv = DeviceInventory.generate(3, VariabilityModel(), seed=4)
    fleet = FleetCapController(micro_library, budget_w=1e9, inventory=inv,
                               **GATES)
    # job A decides on inv[0]; job B stays mid-profile on inv[0]
    meta_a, chunks_a = _job_stream(micro_gemm, inv[0], seed=1)
    job_a = fleet.admit(inv[0], meta_a, chips=2, job_id="a")
    for chunk in chunks_a:
        if fleet.ingest_chunk(job_a, chunk) is not None:
            break
    fleet.finalize_job(job_a)
    meta_b, chunks_b = _job_stream(micro_spmv_memory, inv[0], seed=2)
    chunks_b = list(chunks_b)
    job_b = fleet.admit(inv[0], meta_b, chips=2, job_id="b")
    fleet.ingest_chunk(job_b, chunks_b[0])
    calls = _count_classifier_calls(fleet.clf)

    events = fleet.degrade_device(inv[0].device_id)
    assert calls["n"] == 0                      # drain never classifies
    assert [e.kind for e in events] == ["degrade", "migrate"]
    assert events[1].job_id == "a"              # only the decided job moved
    assert fleet.jobs["a"].device.device_id != inv[0].device_id
    assert fleet.jobs["b"].device is inv[0]     # still profiling in place
    assert fleet.degrade_device(inv[0].device_id) == []   # idempotent

    # job B keeps its partial trace (a slow chip's power frame is valid)
    # and migrates the moment it decides
    assert fleet.jobs["b"].builder.n_ingested > 0
    for chunk in chunks_b[1:]:
        if fleet.ingest_chunk(job_b, chunk) is not None:
            break
    fleet.finalize_job(job_b)
    assert fleet.jobs["b"].device.device_id != inv[0].device_id
    assert any(e.kind == "migrate" and e.job_id == "b" for e in fleet.events)


def test_auto_degrade_from_straggler_adapter(micro_library):
    inv = DeviceInventory.generate(3, VariabilityModel.none(), seed=0)
    adapter = FleetStragglerAdapter(StragglerMonitor(min_samples=5, k=4.0))
    fleet = FleetCapController(micro_library, budget_w=1e9, inventory=inv,
                               straggler_adapter=adapter, **GATES)
    streams = {}
    for i, fn in enumerate([micro_gemm, micro_spmv_memory, micro_stencil]):
        meta, chunks = stream_telemetry(
            fn(), 1.0, inv[i].power_model(), seed=i, target_duration=0.5,
            chunk_samples=50, device_id=inv[i].device_id)
        streams[fleet.admit(inv[i], meta, chips=2)] = (meta, list(chunks))
    # interleave with synthetic arrival times: device 2's cadence is 10x
    rounds = min(len(c) for _, c in streams.values())
    for r in range(rounds):
        for i, (job_id, (meta, chunks)) in enumerate(streams.items()):
            cadence = 0.5 if i == 2 else 0.05
            fleet.ingest(FleetChunk(job_id, inv[i].device_id,
                                    r * cadence, chunks[r]))
    assert inv.health(inv[2].device_id) == DEGRADED
    assert any(e.kind == "degrade" for e in fleet.events)


# ---------------------------------------------------------------------------
# the byte-identity pin: FT wiring that never fires changes nothing
# ---------------------------------------------------------------------------
def test_no_failure_fleet_byte_identical_to_no_ft_path(micro_library):
    inv = DeviceInventory.generate(3, VariabilityModel(), seed=7)
    jobs = [(micro_gemm, 0), (micro_spmv_memory, 1), (micro_spmv_compute, 2)]

    def run_fleet(**ft_kw):
        fleet = FleetCapController(micro_library, budget_w=2e4, **GATES,
                                   **ft_kw)
        mux = FleetTelemetryMux()
        for (fn, seed), dev in zip(jobs, inv):
            meta, chunks = _job_stream(fn, dev, seed=seed)
            mux.add_job(fleet.admit(dev, meta, chips=4), meta, chunks)
        return fleet.run(mux)

    plain = run_fleet()
    wired = run_fleet(inventory=inv,
                      straggler_adapter=FleetStragglerAdapter())
    assert wired.decisions == plain.decisions          # full dataclass eq
    assert list(wired.decisions) == list(plain.decisions)
    assert wired.schedule.placed == plain.schedule.placed
    assert wired.schedule.deferred == plain.schedule.deferred
    assert wired.repacks == plain.repacks
    assert wired.chunks_dropped == plain.chunks_dropped
    assert wired.events == [] and wired.migrations == 0


# ---------------------------------------------------------------------------
# session surface + codec
# ---------------------------------------------------------------------------
def test_session_fail_restore_surface_and_report(micro_library):
    inv = DeviceInventory.generate({"tpu-v5e": 2, "tpu-v5p": 1},
                                   VariabilityModel(), seed=5)
    session = MinosSession(micro_library, inventory=inv, budget_w=1e9,
                           **GATES)
    handles = []
    for i, fn in enumerate([micro_gemm, micro_spmv_memory]):
        h = session.submit(_job_stream(fn, inv[i], seed=i), device=inv[i],
                           chips=4)
        h.run()
        handles.append(h)
    calls = _count_classifier_calls(session.classifier)

    events = session.fail_device(inv[0].device_id)
    assert calls["n"] == 0
    assert session.device_health[inv[0].device_id] == FAILED
    assert handles[0].device.device_id != inv[0].device_id
    assert handles[0].plan().device_id == handles[0].device.device_id

    report = session.run()
    assert report.failures == 1 and report.migrations == 1
    assert report.events == session._fleet.events
    assert report.device_health == session.device_health
    # new submits round-robin over healthy devices only
    got = {session.submit(_job_stream(micro_stencil, inv[1], seed=9))
           .device.device_id for _ in range(4)}
    assert inv[0].device_id not in got

    session.restore_device(inv[0].device_id)
    assert session.device_health[inv[0].device_id] == HEALTHY
    report = session.report()
    assert [e.kind for e in report.events] == ["fail", "migrate", "restore"]
    # the whole FT trail round-trips through the JSON codec
    back = SessionReport.from_json(report.to_json())
    assert back == report
    assert [e.kind for e in back.events] == ["fail", "migrate", "restore"]
    assert back.device_health == report.device_health
    event = report.events[1]
    assert from_json(to_json(event)) == event


def test_session_reprofile_after_mid_profile_failure(micro_library):
    inv = DeviceInventory.generate(2, VariabilityModel(), seed=8)
    session = MinosSession(micro_library, inventory=inv, budget_w=1e9,
                           **GATES)
    meta, chunks = _job_stream(micro_gemm, inv[0], seed=3)
    handle = session.submit(meta, device=inv[0], chips=2)
    handle.feed(next(iter(chunks)))                    # one chunk only
    session.fail_device(inv[0].device_id)
    assert not handle.decided and handle.fraction == 0.0
    handle.reprofile(micro_gemm(), seed=4, target_duration=0.5,
                     chunk_samples=100)
    decision = handle.run()
    assert decision.device_id == inv[1].device_id
    with pytest.raises(ValueError, match="already decided"):
        handle.reprofile(micro_gemm(), seed=4, target_duration=0.5)
    with pytest.raises(TypeError, match="KernelStream"):
        handle.reprofile(42)


def test_from_config_stragglers(micro_library):
    cfg = {"devices": 2, "stragglers": {"window": 10, "k": 4.0}}
    session = MinosSession.from_config(cfg, references=micro_library)
    adapter = session._fleet.straggler_adapter
    assert isinstance(adapter, FleetStragglerAdapter)
    assert adapter.monitor.window == 10 and adapter.monitor.k == 4.0
    with pytest.raises(ValueError, match="unknown straggler keys"):
        MinosSession.from_config({"devices": 2, "stragglers": {"win": 1}},
                                 references=micro_library)
    with pytest.raises(ValueError, match="stragglers"):
        MinosSession.from_config({"devices": 2, "stragglers": 7},
                                 references=micro_library)
    assert MinosSession.from_config(
        {"devices": 2}, references=micro_library)._fleet.straggler_adapter \
        is None


# ---------------------------------------------------------------------------
# property: the packed budget survives ANY failure schedule
# ---------------------------------------------------------------------------
@settings(max_examples=8, deadline=None)
@given(st.lists(st.integers(min_value=0, max_value=3 * 3 * 12 - 1),
                min_size=0, max_size=6))
# fail then restore one device at the same chunk, with that device's chunk
# still in flight for a job that migrated mid-profile
@example(encoded=[91, 97])
def test_budget_never_exceeded_across_any_failure_schedule(encoded):
    """Each encoded int unpacks to (chunk index 0..11, action 0..2,
    device 0..2); whatever the churn, every repack stays inside the
    budget and chaos handling never classifies."""
    lib = _PROPERTY_LIB[0]
    inv = DeviceInventory.generate(3, VariabilityModel(), seed=2)
    jobs = [(micro_gemm, 0), (micro_spmv_memory, 1), (micro_stencil, 2)]
    budget = 0.75 * sum(4 * d.nameplate_w for d in inv)
    fleet = FleetCapController(lib, budget_w=budget, inventory=inv, **GATES)
    mux = FleetTelemetryMux()
    for (fn, seed), dev in zip(jobs, inv):
        meta, chunks = _job_stream(fn, dev, seed=seed)
        mux.add_job(fleet.admit(dev, meta, chips=4), meta, chunks)

    schedule = sorted(((e // 9) % 12, (e // 3) % 3, e % 3) for e in encoded)
    calls = _count_classifier_calls(fleet.clf)

    def apply_due(n):
        while schedule and n >= schedule[0][0]:
            _, action, dev_idx = schedule.pop(0)
            device_id = inv[dev_idx].device_id
            before = calls["n"]
            if action == 0:
                fleet.fail_device(device_id)
                mux.drop_device(device_id)
            elif action == 1:
                fleet.degrade_device(device_id)
            else:
                fleet.restore_device(device_id)
            assert calls["n"] == before        # chaos handling: 0 calls

    n = 0
    for fchunk in mux:
        apply_due(n)
        fleet.ingest(fchunk)                   # deciding MAY classify
        n += 1
    apply_due(12)
    for res in fleet.repacks:
        assert res.planned_power_w <= res.budget_w + 1e-9


_PROPERTY_LIB = []


@pytest.fixture(autouse=True)
def _seed_property_lib(micro_library):
    _PROPERTY_LIB[:] = [micro_library]


# ---------------------------------------------------------------------------
# satellites: elastic loss accounting + rescale contract
# ---------------------------------------------------------------------------
def test_elastic_plan_reports_actual_losses_and_idles():
    mesh = MeshConfig((16, 16), ("data", "model"))
    plan = plan_new_mesh(mesh, surviving_devices=208)
    # 256 -> 208 survivors: 48 actually lost; data 13 rounds down to 8,
    # idling 208 - 128 = 80 healthy devices (NOT "lost")
    assert plan.lost_devices == 48
    assert plan.idle_devices == 80
    assert plan.new.num_devices == 128
    assert plan.surviving_devices == 208
    # no loss, no rounding: nothing lost, nothing idle
    full = plan_new_mesh(mesh, surviving_devices=256)
    assert full.lost_devices == 0 and full.idle_devices == 0
    assert full.new.num_devices == 256


def test_rescale_batch_keeps_integer_per_device_batch():
    mesh = MeshConfig((16, 16), ("data", "model"))
    plan = plan_new_mesh(mesh, surviving_devices=144)   # data 16 -> 8
    assert rescale_batch(256, plan) == 128              # 16 per slice, kept
    # a non-divisible global batch keeps the floored per-device batch
    # instead of truncating the float ratio (250*8/16 = 125 would change
    # the per-device batch from 15 to 15.625)
    assert rescale_batch(250, plan) == 15 * 8
    assert rescale_batch(3, plan) == 8                  # min 1 per device


# ---------------------------------------------------------------------------
# satellites: straggler aging + baselines all-excluded contract
# ---------------------------------------------------------------------------
def test_straggler_monitor_ages_out_silent_hosts():
    mon = StragglerMonitor(window=10, min_samples=3, k=4.0)
    for step in range(5):
        mon.record(9, step, 5.0)               # host 9 then goes silent
    for host in range(3):
        for step in range(30):
            mon.record(host, step, 1.0)
    # host 9's stale window is evicted: it is dead, not a straggler, and
    # healthy_hosts no longer vouches for it
    assert mon.dead_hosts() == [9]
    assert 9 not in mon.stragglers()
    assert mon.healthy_hosts([0, 1, 2, 9]) == [0, 1, 2]
    # a host that reports again comes back from the dead
    mon.record(9, 31, 1.0)
    assert mon.dead_hosts() == []
    assert 9 in mon.healthy_hosts([0, 1, 2, 9])


def test_baselines_raise_on_all_excluded(micro_library):
    target = stream_profile_once(micro_gemm(), MODEL, TDP, seed=1,
                                 target_duration=0.5)
    refs = [r for r in micro_library.profiles if r.name == target.name]
    assert refs                                 # only the self-match left
    with pytest.raises(ValueError, match="every reference is excluded"):
        mean_power_neighbor(target, refs)
    with pytest.raises(ValueError, match="every reference is excluded"):
        util_only_neighbor(target, refs)
    only = [r for r in micro_library.profiles if r.name != target.name][0]
    with pytest.raises(ValueError, match="every reference is excluded"):
        mean_power_neighbor(target, [only], exclude=only.name)
