"""A durable, failure-heavy deployment through the harness on the CPU at a
tiny size: ``helios`` with a ``store`` and a ``faults`` section (the
rehearsal's entries), the checks those sections add, the fault process,
and ``helios`` without them built and driven as before."""
import numpy as np
import pytest

import bench_rehearsal as R
from bench import harness
from bench.faults import FaultProcess


@pytest.mark.parametrize("workload", sorted(R.DURABLE))
def test_durable_entry_runs_and_is_correct(workload, monkeypatch, capsys,
                                           tmp_path):
    # the open loop at 10 arrivals a second, over 6 s: its jobs stream in
    # real time, and the store's fsync and snapshots keep a loaded CPU
    # from the default 40 and from deciding enough of them in 3 s
    R.pretend_chip(monkeypatch, lambda w: R.small_spec(w, rate=10.0),
                   tmp_path)
    seconds = 3.0 if R.DURABLE[workload] == "replay" else 6.0
    out = R.result(capsys, R.args(workload, seed=2**31 + 7,
                                  seconds=seconds))
    checks = out["checks"]
    assert out["correct"] is True, {
        k: c for k, c in checks.items()
        if (c["value"] < c["limit"] if c.get("at_least")
            else c["value"] > c["limit"])}
    assert checks["failures"]["value"] >= 1
    assert checks["restarts"]["value"] >= 1
    for name in ("failed_placements", "migration_classify_calls",
                 "lost_runs", "resume_gap", "decision_gap", "hist_gap"):
        assert checks[name]["value"] == 0, name
    assert list(out)[-1] == "checks"
    assert "setup_s" in out["metrics"] and len(out["metrics"]) >= 2


def _drive(cell, ticks: int) -> list:
    """Exactly ``ticks`` ticks of the closed loop (the window's clock counts
    ticks); every decision made, in order."""
    made, done = [], [0]
    fleet = cell.fleet
    tick, final = fleet.ingest_tick, fleet.finalize_job

    def ingest_tick(batch):
        made.extend(tick(batch))
        done[0] += 1

    def finalize_job(jid):
        made.append(final(jid))

    fleet.ingest_tick, fleet.finalize_job = ingest_tick, finalize_job
    cell.win = lambda: float(done[0])
    assert cell.run(float(ticks)).ticks == ticks
    return [(d.n_samples, d.early, float(d.cap), float(d.confidence),
             d.selection.power_neighbor, d.selection.util_neighbor)
            for d in made]


def test_helios_builds_the_parent_controller_and_decides_alike(
        monkeypatch):
    import repro.api
    real = repro.api.FleetCapController
    seen = []

    class Spy(real):
        def __init__(self, *args, **kwargs):
            seen.append((args, kwargs))
            super().__init__(*args, **kwargs)

    monkeypatch.setattr(repro.api, "FleetCapController", Spy)
    spec = R.small_spec("hpc.replay")
    assert "store" not in spec["config"] and "faults" not in spec["config"]
    cell = harness.Cell(spec, 11)
    cell.build()
    assert cell.system is cell.fleet and cell.faults is None
    (args, kwargs), = seen
    gates = spec["config"]["gates"]
    # the parent's call, keyword for keyword
    assert args == (cell.lib,)
    assert kwargs == dict(
        budget_w=cell.budget_w, objective=spec["config"]["objective"],
        provision_quantile=spec["config"]["provision_quantile"],
        min_confidence=float(gates["min_confidence"]),
        min_fraction=float(gates["min_fraction"]),
        min_spike_samples=int(gates["min_spike_samples"]),
        inventory=cell.inv)

    def parent_system(self):
        return real(self.lib, budget_w=self.budget_w,
                    objective=self.cfg["objective"],
                    provision_quantile=self.cfg["provision_quantile"],
                    min_confidence=float(gates["min_confidence"]),
                    min_fraction=float(gates["min_fraction"]),
                    min_spike_samples=int(gates["min_spike_samples"]),
                    inventory=self.inv)

    twin = harness.Cell(R.small_spec("hpc.replay"), 11)
    monkeypatch.setattr(twin, "make_system",
                        parent_system.__get__(twin))
    twin.build()
    got, want = _drive(cell, 24), _drive(twin, 24)
    assert got == want and len(got) > 10


def test_fault_schedule_is_a_fixed_set_the_seed_orders():
    spec = {"failures_per_device_s": 0.01, "repair_s": 5.0}
    ids = [f"d{i:03d}" for i in range(100)]

    def run(seed):
        p = FaultProcess(spec, ids, np.random.default_rng(seed))
        return p, list(p.due(500.0))

    (p1, a), (p2, b) = run(1), run(2)
    # steady state from the start: rate x devices x repair_s down
    assert len(p1.initial) == len(p2.initial) == 5
    assert {k for _, k, _ in a} == {"fail", "restore"}
    fails = [t for t, k, _ in a if k == "fail"]
    # 1 failure a second over 500 s, from a fixed set of gaps
    assert abs(len(fails) - 500) <= 2
    gaps_a = np.diff([0.0] + fails)[:64]
    gaps_b = np.diff([0.0] + [t for t, k, _ in b if k == "fail"])[:64]
    assert sorted(gaps_a) == pytest.approx(sorted(gaps_b))
    assert list(gaps_a) != pytest.approx(list(gaps_b))
    assert run(1)[1] == a
    # every device fails while healthy and is restored repair_s later
    down = set(p1.initial)
    failed_at = {}
    for t, kind, dev in a:
        if kind == "fail":
            assert dev not in down
            down.add(dev)
            failed_at[dev] = t
        else:
            assert dev in down
            down.discard(dev)
            if dev in failed_at:
                assert t == pytest.approx(failed_at.pop(dev) + 5.0)
    assert down == p1.failed and 1 <= len(down) <= 12


def test_fault_process_refuses_a_cluster_it_would_take_down():
    with pytest.raises(SystemExit):
        FaultProcess({"failures_per_device_s": 1.0, "repair_s": 2.0},
                     ["a", "b"], np.random.default_rng(0))


def test_a_run_cut_twice_at_once_streams_one_new_run(monkeypatch):
    """A job that two failures due together migrate twice is restarted
    after each, and handed back once: its new run streams from chunk 0 one
    time, not twice."""
    from types import SimpleNamespace
    cell = harness.Cell(R.small_spec("hpc.replay"), 5)
    job = harness.Job("j0", 0, None, 1, False)
    cell.jobs = {"j0": job}
    cell.faults = SimpleNamespace(
        due=lambda now: [(0.0, "fail", "d0"), (0.0, "fail", "d1")])
    moved = SimpleNamespace(kind="migrate", detail="reprofile", job_id="j0")
    monkeypatch.setattr(cell, "fault", lambda dev, kind: [moved])
    cut = []
    monkeypatch.setattr(cell, "restart", lambda j, rec: cut.append(j.jid))
    rec = harness.Record()
    assert cell.inject(0.0, rec) == [job]
    assert cut == ["j0", "j0"] and rec.failures == 2 and rec.events == 2
