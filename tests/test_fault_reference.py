"""Device faults against a plain reference of their semantics.  After every
``fail_device`` and ``restore_device`` on a small fleet, through failures
that strand jobs and restores that bring them back:

  * every job keeps the decision it had (the same object);
  * a job the reference moves lands on the healthy device it names: the
    least planned watts, ties by device id, the moved plans counted as
    they land; a decided job whose own device is back stays there;
  * a moved plan is the old one re-costed on the new device's effective
    TDP (same cap and selection);
  * the packing equals ``PowerAwareScheduler.pack`` over the same plans;
  * no call classifies (``count_classifier_calls``)."""
import pytest

from repro.api import (ReferenceLibrary, TPUPowerModel,
                       count_classifier_calls, micro_gemm, micro_idle_burst,
                       micro_spmv_compute, micro_spmv_memory, micro_stencil,
                       stream_profile_workload, stream_telemetry)
from repro.fleet import (FAILED, HEALTHY, DeviceInventory, FleetCapController,
                         VariabilityModel)

MODEL = TPUPowerModel()
GATES = dict(min_confidence=0.2, min_fraction=0.1, min_spike_samples=50)
STREAMS = (micro_gemm, micro_spmv_memory, micro_spmv_compute, micro_stencil,
           micro_idle_burst)


@pytest.fixture(scope="module")
def library():
    return ReferenceLibrary(
        (stream_profile_workload(s(), MODEL, (0.6, 0.8, 1.0),
                                 MODEL.spec.tdp_w, seed=i,
                                 target_duration=0.5)
         for i, s in enumerate([micro_gemm, micro_idle_burst,
                                micro_spmv_memory, micro_stencil])),
        built_on="tpu-v5e")


def _fleet(library):
    """Eight jobs on the first two of three devices: six decided, two
    mid-profile (one chunk in), under a budget that defers some plans."""
    inv = DeviceInventory.generate(3, VariabilityModel(), seed=3)
    fleet = FleetCapController(library, budget_w=1e9, inventory=inv,
                               **GATES)
    for i in range(8):
        dev = inv[i % 2]
        meta, chunks = stream_telemetry(
            STREAMS[i % len(STREAMS)](), 1.0, dev.power_model(), seed=50 + i,
            target_duration=0.5, chunk_samples=128,
            device_id=dev.device_id)
        jid = fleet.admit(dev, meta, chips=1 + i % 3, job_id=f"j{i}")
        for k, chunk in enumerate(chunks):
            if i >= 6 and k == 1:
                break
            fleet.ingest_chunk(jid, chunk)
        if i < 6:
            fleet.finalize_job(jid)
    assert [j.decision is None for j in fleet.jobs.values()] \
        == [False] * 6 + [True] * 2
    planned = sum(j.plan.predicted_p90_w * j.plan.chips
                  for j in fleet.jobs.values() if j.plan is not None)
    fleet.set_budget(0.6 * planned)
    return inv, fleet


class Reference:
    """Each job's device and plan from the fault rules alone: ``jobs``
    maps job id to ``[device, plan or None]``, ``last`` keeps a decided
    job's last plan while it is stranded."""

    def __init__(self, inv, fleet):
        self.inv = inv
        self.tdp = {d.device_id: d.effective_tdp_w for d in inv}
        self.down: set[str] = set()
        self.jobs = {jid: [j.device, j.plan] for jid, j in fleet.jobs.items()}
        self.last = {jid: j.plan for jid, j in fleet.jobs.items()
                     if j.plan is not None}

    def _recost(self, jid: str, device) -> None:
        plan = self.last[jid]
        self.jobs[jid][1] = self.last[jid] = Plan(
            plan, plan.predicted_p90_w / self.tdp[plan.device_id]
            * self.tdp[device.device_id], device.device_id)

    def _move(self, jid: str, from_id: str) -> None:
        load: dict[str, float] = {}
        for dev, plan in self.jobs.values():
            if plan is not None:
                load[dev.device_id] = load.get(dev.device_id, 0.0) \
                    + plan.predicted_p90_w * plan.chips
        healthy = [d for d in self.inv if d.device_id not in self.down
                   and d.device_id != from_id]
        if not healthy:                         # stranded where it is
            self.jobs[jid][1] = None
            return
        target = min(healthy, key=lambda d: (load.get(d.device_id, 0.0),
                                             d.device_id))
        if jid in self.last:
            self._recost(jid, target)
        self.jobs[jid][0] = target

    def fail(self, device_id: str) -> None:
        self.down.add(device_id)
        for jid, (dev, _) in list(self.jobs.items()):
            if dev.device_id == device_id:
                self._move(jid, device_id)

    def restore(self, device_id: str) -> None:
        self.down.discard(device_id)
        for jid, (dev, plan) in list(self.jobs.items()):
            own_down = dev.device_id in self.down
            if jid in self.last and plan is None:
                if own_down:
                    self._move(jid, dev.device_id)
                else:                          # its own device is back
                    self._recost(jid, dev)
            elif jid not in self.last and own_down:
                self._move(jid, dev.device_id)


class Plan:
    """The reference's re-costed plan: the old one's selection, cap and
    chips at new watts on a new device."""

    def __init__(self, plan, watts: float, device_id: str):
        self.selection, self.cap, self.chips = \
            plan.selection, plan.cap, plan.chips
        self.predicted_p90_w, self.device_id = watts, device_id


def _check(ref: Reference, fleet, decisions: dict) -> None:
    for jid, job in fleet.jobs.items():
        assert job.decision is decisions[jid], jid
        dev, plan = ref.jobs[jid]
        assert job.device.device_id == dev.device_id, jid
        if plan is None:
            assert job.plan is None, jid
            continue
        assert fleet.inventory.health(job.device.device_id) == HEALTHY
        assert job.plan.device_id == plan.device_id == dev.device_id
        assert job.plan.selection == plan.selection
        assert job.plan.cap == plan.cap and job.plan.chips == plan.chips
        assert job.plan.predicted_p90_w == pytest.approx(
            plan.predicted_p90_w, rel=1e-12), jid
        ref.jobs[jid][1] = ref.last[jid] = job.plan   # exact watts on
    plans = [j.plan for j in fleet.jobs.values() if j.plan is not None]
    want = fleet.scheduler.pack(plans, budget_w=fleet.budget_w)
    got = fleet.repacks[-1]
    assert [p.job_id for p in got.placed] == [p.job_id for p in want.placed]
    assert got.deferred == want.deferred


def test_fail_and_restore_follow_the_reference(library):
    inv, fleet = _fleet(library)
    ids = [d.device_id for d in inv]
    decisions = {jid: j.decision for jid, j in fleet.jobs.items()}
    ref = Reference(inv, fleet)
    calls = count_classifier_calls(fleet.clf)
    assert fleet.repacks[-1].deferred          # the budget binds
    # down one by one until every job is stranded, then back, then a
    # failure with capacity to spare
    schedule = [("fail", ids[0]), ("fail", ids[1]), ("fail", ids[2]),
                ("restore", ids[1]), ("restore", ids[0]), ("fail", ids[1]),
                ("restore", ids[2])]
    stranded = 0
    for kind, dev in schedule:
        if kind == "fail":
            events = fleet.fail_device(dev)
            ref.fail(dev)
        else:
            events = fleet.restore_device(dev)
            ref.restore(dev)
        stranded += sum(e.kind == "strand" for e in events)
        assert {d for d, h in fleet.device_health().items()
                if h == FAILED} == ref.down
        _check(ref, fleet, decisions)
    assert stranded > 0
    assert all(j.plan is not None for jid, j in fleet.jobs.items()
               if decisions[jid] is not None)
    assert calls["n"] == 0
