"""A durable, failure-heavy run with the timed path broken underneath
reports ``correct`` false through the check that guards the broken
guarantee; unbroken, the same entry is correct."""
import pytest

import bench_rehearsal as R

WORKLOAD = "hpc.durable"


def whole_journal(workload: str) -> dict:
    """The durable entry with no cadence snapshot, so that the resume
    replays the whole journal and a record lost from it cannot hide
    behind a snapshot that captured its effect."""
    spec = R.small_spec(workload)
    spec["config"]["store"]["snapshot_every"] = 10**9
    return spec


def drain_migrates_nothing(monkeypatch):
    """A failed device's jobs stay bound to it and keep their plans."""
    from repro.fleet.controller import FleetCapController
    monkeypatch.setattr(FleetCapController, "_drain_device",
                        lambda self, device_id, cause, decided_only=False:
                        [cause])


def last_decision_unwritten(monkeypatch):
    """The newest decision made with a quarter of its trace or more still
    to stream waits in memory, and is journaled only when the next such
    decision, or its own job's retire, is: one decision record, of a job
    still streaming, never reaches the journal, though the program acted
    on it."""
    from repro.store import SessionStore
    real = SessionStore.record
    held = []

    def record(self, kind, **data):
        young = kind == "decision" and data["decision"].fraction <= 0.75
        if held and (young or (kind == "retire"
                               and data["job_id"] == held[0]["job_id"])):
            real(self, "decision", **held.pop())
        if young:
            held.append(data)
            return self.journal.last_seq
        return real(self, kind, **data)

    monkeypatch.setattr(SessionStore, "record", record)


def migration_classifies(monkeypatch):
    """Re-costing a migrated plan queries the classifier."""
    from repro.sched.power_sched import PowerAwareScheduler
    real = PowerAwareScheduler.migrate_plan

    def migrate_plan(self, plan, device, chips=None):
        self.clf.power_neighbors([self.clf.references[0]])
        return real(self, plan, device, chips)

    monkeypatch.setattr(PowerAwareScheduler, "migrate_plan", migrate_plan)


def restart_never_taken(monkeypatch):
    """``restart_profile`` leaves the job awaiting its re-run, so the
    re-run's chunks are dropped as a dead run's."""
    from repro.fleet.controller import FleetCapController
    monkeypatch.setattr(FleetCapController, "restart_profile",
                        lambda self, job_id, meta=None: None)


@pytest.mark.parametrize("fault, check, spec_fn", [
    (drain_migrates_nothing, "failed_placements", R.small_spec),
    (last_decision_unwritten, "resume_gap", whole_journal),
    (migration_classifies, "migration_classify_calls", R.small_spec),
    (restart_never_taken, "lost_runs", R.small_spec)])
def test_fault_fails_its_check(fault, check, spec_fn, monkeypatch, capsys,
                               tmp_path):
    R.pretend_chip(monkeypatch, spec_fn, tmp_path)
    fault(monkeypatch)
    out = R.result(capsys, R.args(WORKLOAD, seed=2**31 + 9, seconds=3.0))
    assert out["correct"] is False
    c = out["checks"][check]
    assert c["value"] > c["limit"], out["checks"]


def test_whole_journal_entry_is_correct(monkeypatch, capsys, tmp_path):
    R.pretend_chip(monkeypatch, whole_journal, tmp_path)
    out = R.result(capsys, R.args(WORKLOAD, seed=2**31 + 9, seconds=3.0))
    assert out["correct"] is True, out["checks"]
    assert out["checks"]["resume_gap"]["value"] == 0
