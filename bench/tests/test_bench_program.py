"""The program's own spans in a traced run: the recorder switched on and
read around the window, device-idle time cut by the innermost ``minos.*``
span over it, and the readers of the metrics built on them."""
import pytest
from jax.profiler import ProfileData

import bench_rehearsal as R
from bench import program_trace, run, tracing

# times in ps from each line's base (ns): a 10 ms window, the device busy
# over [1,4] and [7,8] ms, so idle over [0,1], [4,7] and [8,10] ms; the
# host in benchmark spans and, inside them, program spans
TRACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 1000000000 duration_ps: 3000000000 }
    events { metadata_id: 2 offset_ps: 7000000000 duration_ps: 1000000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.7" } }
  event_metadata { key: 2 value { id: 2 name: "spike_hist_packed" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000000 }
    events { metadata_id: 2 offset_ps: 500000000 duration_ps: 5500000000 }
    events { metadata_id: 3 offset_ps: 2000000000 duration_ps: 3000000000 }
    events { metadata_id: 4 offset_ps: 5000000000 duration_ps: 500000000 }
    events { metadata_id: 5 offset_ps: 8000000000 duration_ps: 2000000000 }
    events { metadata_id: 6 offset_ps: 8500000000 duration_ps: 1000000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "minos.tick" } }
  event_metadata { key: 3 value { id: 3 name: "minos.engine" } }
  event_metadata { key: 4 value { id: 4 name: "minos.classify" } }
  event_metadata { key: 5 value { id: 5 name: "bench.retire" } }
  event_metadata { key: 6 value { id: 6 name: "minos.retire" } }
}
"""


def test_idle_time_goes_to_the_innermost_program_span_by_overlap():
    out = program_trace.attribute(ProfileData.from_text_proto(TRACE))
    # [0,1]: tick from 0.5; [4,7]: engine to 5, classify 5-5.5, tick
    # 5.5-6, then nothing; [8,10]: retire 8.5-9.5 inside the bench span
    assert out["spans"] == {"tick": pytest.approx(1e-3),
                            "engine": pytest.approx(1e-3),
                            "retire": pytest.approx(1e-3),
                            "classify": pytest.approx(0.5e-3)}
    assert out["unspanned_s"] == pytest.approx(2.5e-3)
    assert out["devices"] == 1
    red = tracing.reduce(ProfileData.from_text_proto(TRACE))
    idle = red["window_s"] - red["busy_s"]
    assert sum(out["spans"].values()) + out["unspanned_s"] \
        == pytest.approx(idle)


def test_reduce_names_gaps_by_benchmark_spans_alone():
    red = tracing.reduce(ProfileData.from_text_proto(TRACE))
    assert red["busy_s"] == pytest.approx(4e-3)
    # program spans do not name gaps there: the midpoint of [4,7] ms falls
    # in no benchmark span
    assert red["gaps"] == [["none", pytest.approx(3e-3)],
                           ["retire", pytest.approx(2e-3)],
                           ["none", pytest.approx(1e-3)]]


def test_attribution_without_the_window_is_refused():
    bad = TRACE.replace('"bench.window"', '"other"')
    with pytest.raises(RuntimeError, match="bench.window"):
        program_trace.attribute(ProfileData.from_text_proto(bad))


def test_nested_spans_split_into_innermost_pieces():
    pieces = program_trace._pieces([(0, 10, "tick"), (2, 4, "engine"),
                                    (3, 4, "engine.device"),
                                    (6, 8, "classify"), (12, 13, "retire")])
    assert pieces == [(0, 2, "tick"), (2, 3, "engine"),
                      (3, 4, "engine.device"), (4, 6, "tick"),
                      (6, 8, "classify"), (8, 10, "tick"),
                      (12, 13, "retire")]


def test_start_and_finish_switch_the_recorder():
    import repro.obs as obs
    rec = program_trace.start()
    assert rec is obs
    with obs.span("tick"):
        obs.count("classify.swept", 4)
    report = program_trace.finish(rec)
    assert report["spans"]["tick"]["calls"] == 1
    assert report["counters"] == {"classify.swept": 4}
    assert obs.span("tick") is obs.span("engine")        # off again
    obs.reset()


def test_a_program_without_a_recorder_gives_nothing(monkeypatch):
    import sys
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    rec = program_trace.start()
    assert rec is None and program_trace.finish(rec) is None
    layer = dict(LAYER, program=None, idle_by_span=None)
    for name in NEW:
        assert run.read_layer(name, layer) is None, name


NEW = ("snapshot_ms_per_job.replay", "snapshot_samples_per_job.replay",
       "classifier_ms_per_job.replay", "classify_yield.replay",
       "finalize_ms_per_job.replay", "lifecycle_ms_per_job.replay",
       "device_call_ms_per_job.replay", "unspanned_ms_per_job.replay",
       "pq_rebuild_share.replay")


def _span(total):
    return {"calls": 3, "total_s": total, "self_s": total}


LAYER = dict(
    window_s=10.0, decisions=1000, events=400, spans={}, pack_in_tick_s=0.0,
    repack_s=1.2, wire_late_ms=[], hist_calls=[], trace=None, peaks=R.PEAKS,
    program={"spans": {"classify.snapshot": _span(0.6),
                       "classify.sweep": _span(0.3),
                       "finalize_job": _span(0.25),
                       "admit": _span(0.1), "retire": _span(0.15),
                       "engine.device": _span(0.05)},
             "counters": {"snapshot.samples": 2_500_000,
                          "classify.swept": 4000,
                          "classify.decided": 800,
                          "snapshot.pq_prefilled": 4000,
                          "snapshot.pq_rebuilds": 20}},
    idle_by_span={"spans": {"classify.sweep": 0.3}, "unspanned_s": 0.7,
                  "devices": 1})


@pytest.mark.parametrize("name, value", [
    ("snapshot_ms_per_job.replay", 0.6),
    ("snapshot_samples_per_job.replay", 2500.0),
    ("classifier_ms_per_job.replay", 0.3),
    ("classify_yield.replay", 20.0),
    ("finalize_ms_per_job.replay", 0.25),
    ("lifecycle_ms_per_job.replay", 0.25),
    ("device_call_ms_per_job.replay", 0.05),
    ("unspanned_ms_per_job.replay", 0.7),
    ("pq_rebuild_share.replay", 0.5)])
def test_program_readers(name, value):
    assert run.read_layer(name, LAYER) == pytest.approx(value)


def test_program_readers_return_nothing_without_input():
    empty = dict(LAYER, decisions=0)
    for name in NEW:
        if name not in ("classify_yield.replay",    # not per job
                        "pq_rebuild_share.replay"):
            assert run.read_layer(name, empty) is None, name
    bare = dict(LAYER, program={"spans": {}, "counters": {}},
                idle_by_span=dict(LAYER["idle_by_span"], devices=0))
    for name in NEW:
        assert run.read_layer(name, bare) is None, name


def test_traced_rehearsal_reports_the_program_metrics(monkeypatch, capsys):
    """``bench/run.py`` itself switches the recorder on around a traced
    window and reports every program metric that ``BENCHMARK.json`` lists
    for the cell."""
    R.pretend_chip(monkeypatch)
    out = R.result(capsys, R.args("hpc.replay", seed=2**31 + 5, trace=1))
    assert out["correct"] is True
    got = out["metrics"]
    # the CPU has no device plane, so no device-idle time to attribute
    for name in NEW:
        if name != "unspanned_ms_per_job.replay":
            assert got[name]["value"] is not None, name
    assert "unspanned_ms_per_job.replay" not in got
    assert 0.0 < got["classify_yield.replay"]["value"] <= 100.0
    assert 0.0 <= got["pq_rebuild_share.replay"]["value"] <= 100.0
    # the existing per-layer metrics read as before
    assert {"engine_ms_per_job.replay", "classify_ms_per_job.replay",
            "pack_ms_per_job.replay", "tick_self_ms_per_job.replay"} <= set(got)
