"""The metric arithmetic: a tail over every sample, a rate over the whole
window, and per-layer readers that say nothing when there is nothing."""
import statistics

import pytest

import bench_rehearsal as R
from bench import harness, run


def test_p95_is_the_tail_of_all_values():
    values = list(range(1, 101))
    assert run.p95(values) == statistics.quantiles(values, n=20)[-1]
    assert run.p95(values[:19]) is None
    spiky = [1.0] * 90 + [100.0] * 10
    assert run.p95(spiky) == pytest.approx(100.0)


def test_rate_is_over_the_whole_window():
    rec = harness.Record(window_s=20.0, decisions=5000, ticks=7,
                         decision_ms=[1.0] * 30, event_ms=[2.0] * 30)
    e2e = run.end_to_end(rec, setup_s=12.5)
    assert e2e["decisions_per_s"] == 250.0
    assert e2e["setup_s"] == 12.5
    assert e2e["decision_p95_ms"] == 1.0 and e2e["event_p95_ms"] == 2.0


LAYER = dict(window_s=10.0, decisions=1000, events=400,
             spans={"engine": 2.0, "classify": 1.0, "tick": 4.5},
             pack_in_tick_s=0.5, repack_s=1.2,
             wire_late_ms=[float(i) for i in range(100)],
             hist_calls=[(1000, 10), (3000, 20)],
             trace=dict(busy_s=0.25, window_s=10.0, devices=1,
                        op_s={"spike_hist_packed": 0.001, "copy": 0.002},
                        ops=[], gaps=[]),
             peaks=R.PEAKS)


def test_per_layer_readers():
    read = run.read_layer
    assert read("engine_ms_per_job.replay", LAYER) == 2.0
    assert read("classify_ms_per_job.replay", LAYER) == 1.0
    assert read("pack_ms_per_job.replay", LAYER) == pytest.approx(1.2)
    assert read("tick_self_ms_per_job.replay", LAYER) == pytest.approx(1.0)
    assert read("device_idle.replay", LAYER) == pytest.approx(97.5)
    assert read("pack_ms_per_event.steady", LAYER) == pytest.approx(3.0)
    assert read("wire_late_p95_ms.steady", LAYER) == \
        statistics.quantiles(range(100), n=20)[-1]
    moved = 4 * 4000 + 4 * 128 * 30
    assert read("hist_roofline.replay", LAYER) == pytest.approx(
        100.0 * moved / 819e9 / 0.001)


def test_readers_return_nothing_without_input():
    empty = dict(LAYER, decisions=0, events=0, spans={}, wire_late_ms=[],
                 hist_calls=[], trace=None)
    for name in ("engine_ms_per_job.replay", "classify_ms_per_job.replay",
                 "pack_ms_per_job.replay", "tick_self_ms_per_job.replay",
                 "hist_roofline.replay", "device_idle.replay",
                 "wire_late_p95_ms.steady", "pack_ms_per_event.steady"):
        assert run.read_layer(name, empty) is None, name
    no_kernel = dict(LAYER, trace=dict(LAYER["trace"], op_s={"copy": 1.0}))
    assert run.read_layer("hist_roofline.replay", no_kernel) is None


def test_every_listed_metric_has_a_reader():
    import json
    import os
    spec = json.load(open(os.path.join(R.ROOT, "BENCHMARK.json")))
    for m in spec["per_layer"]:
        assert os.path.isfile(os.path.join(R.BENCH, "metrics",
                                           m["name"] + ".py"))
    for c in spec["configs"]:
        assert os.path.isfile(os.path.join(R.ROOT, c["file"]))
    for w in spec["workloads"]:
        assert os.path.isfile(os.path.join(R.BENCH, "checks",
                                           w["name"] + ".json"))
        assert os.path.isfile(os.path.join(R.BENCH, "traffic", "mixes",
                                           w["traffic"] + ".json"))
