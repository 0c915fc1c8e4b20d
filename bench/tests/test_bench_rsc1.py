"""``rsc1.durable`` (Meta's RSC-1 on the session store, failure-heavy) end
to end on the CPU at a tiny size, the sizing rules of its configuration,
and the readers of its per-layer metrics."""
import numpy as np
import pytest

import bench_rehearsal as R
from bench import harness, run
from bench.faults import BLOCK
from bench.traffic import generator as gen

WORKLOAD = "rsc1.durable"
READERS = ("journal_ms_per_job.durable", "journal_bytes_per_job.durable",
           "fsyncs_per_job.durable", "fault_ms_per_failure.durable")


def small(workload: str) -> dict:
    """The cell on the rehearsal's 36-node cluster.  The cell's rate per
    device would give 36 nodes 0.36 failures a telemetry second, whose
    largest fixed gap (53 ticks) a loaded CPU's window may not reach; the
    rehearsal's durable rate keeps that gap near 5 ticks, as the cell's
    sizing keeps it under one at 2,000 nodes."""
    spec = R.small_spec(workload)
    spec["config"]["faults"] = dict(R.FAULTS)
    return spec


@pytest.mark.parametrize("seed", [2142767591, 7])
def test_rsc1_durable_runs_and_is_correct(seed, monkeypatch, capsys,
                                          tmp_path):
    R.pretend_chip(monkeypatch, small, tmp_path)
    out = R.result(capsys, R.args(WORKLOAD, seed=seed, seconds=3.0))
    checks = out["checks"]
    assert out["correct"] is True, checks
    assert checks["failures"]["value"] >= 1
    assert checks["restarts"]["value"] >= 1
    for name in ("failed_placements", "migration_classify_calls",
                 "lost_runs", "resume_gap"):
        assert checks[name]["value"] == 0, name
    assert set(out["metrics"]) == {"decisions_per_s", "setup_s"}


def test_traced_run_reads_the_durable_metrics(monkeypatch, capsys,
                                              tmp_path):
    R.pretend_chip(monkeypatch, small, tmp_path)
    out = R.result(capsys, R.args(WORKLOAD, seed=2**31 + 3, seconds=2.0,
                                  trace=1))
    assert out["correct"] is True, out["checks"]
    assert set(READERS) <= set(out["metrics"])
    # every record is fsynced: at least one record and one fsync a decision
    assert out["metrics"]["fsyncs_per_job.durable"]["value"] >= 1
    assert out["metrics"]["journal_bytes_per_job.durable"]["value"] > 0
    assert out["metrics"]["fault_ms_per_failure.durable"]["value"] > 0


def test_config_sizing_holds_on_every_seed():
    """At the committed size the largest of the fault process's fixed gaps
    is under two ticks of the telemetry clock, a failure comes by the
    third tick on every ordering, and about 1% of nodes are down."""
    spec = harness.load_cell(WORKLOAD)
    cfg = spec["config"]
    assert spec["cell"]["chips"] == 1 and spec["mix"]["loop"] == "closed"
    nodes = cfg["cluster"]["nodes"]
    assert cfg["devices"] == {"tpu-v5e": nodes} and nodes == 2000
    rate = cfg["faults"]["failures_per_device_s"] * nodes
    tick_s = cfg["telemetry"]["chunk_samples"] * \
        cfg["telemetry"]["sample_dt_s"]
    gaps = gen.exponential_set(BLOCK, 1.0 / rate, np.random.default_rng(0))
    assert gaps.max() <= 2 * tick_s
    down = round(rate * cfg["faults"]["repair_s"])
    assert down / nodes == pytest.approx(0.01)
    assert cfg["store"]["fsync"] is True
    # no cadence snapshot in set-up or the window: far above the records
    # of both at 2,802 live jobs
    assert cfg["store"]["snapshot_every"] >= 100 * harness.Cell(
        spec, 1).live_jobs()
    for key in ("devices", "arrivals", "durations", "failure_clock"):
        assert key in cfg["reduced"] and key in cfg
    entry = {c["name"]: c for c in harness.load_json(
        R.ROOT, "BENCHMARK.json")["configs"]}["rsc1"]
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]


LAYER = dict(window_s=10.0, decisions=400, events=90,
             program={"spans": {"journal.append": {"calls": 2000,
                                                   "total_s": 1.6,
                                                   "self_s": 1.6},
                                "fault.fail": {"calls": 50, "total_s": 0.5,
                                               "self_s": 0.2},
                                "fault.restore": {"calls": 40,
                                                  "total_s": 0.25,
                                                  "self_s": 0.25}},
                      "counters": {"journal.records": 2000,
                                   "journal.bytes": 8_000_000,
                                   "journal.fsyncs": 2000}})


def test_durable_readers():
    read = run.read_layer
    assert read("journal_ms_per_job.durable", LAYER) == pytest.approx(4.0)
    assert read("journal_bytes_per_job.durable", LAYER) == 20_000
    assert read("fsyncs_per_job.durable", LAYER) == 5
    assert read("fault_ms_per_failure.durable", LAYER) == pytest.approx(15)


@pytest.mark.parametrize("program", [
    None,                                     # a checkout with no recorder
    {"spans": {}, "counters": {}},            # a program without the spans
])
def test_durable_readers_say_nothing_without_the_spans(program):
    for name in READERS:
        assert run.read_layer(name, dict(LAYER, program=program)) is None
    assert run.read_layer("journal_ms_per_job.durable",
                          dict(LAYER, decisions=0)) is None
