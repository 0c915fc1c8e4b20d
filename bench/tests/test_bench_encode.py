"""The reader of ``encode_ms_per_job.durable``: the program's
``store.encode`` span per decision, in ``rsc1.durable`` at the
rehearsal's size and on a hand-made layer record."""
import pytest

import bench_rehearsal as R
from bench import run
from test_bench_rsc1 import LAYER, WORKLOAD, small

NAME = "encode_ms_per_job.durable"
ENCODE = {"calls": 2000, "total_s": 0.3, "self_s": 0.3}


def test_traced_run_reads_the_encoding(monkeypatch, capsys, tmp_path):
    import repro.obs as obs
    R.pretend_chip(monkeypatch, small, tmp_path)
    out = R.result(capsys, R.args(WORKLOAD, seed=2**31 + 11, seconds=2.0,
                                  trace=1))
    assert out["correct"] is True, out["checks"]
    assert out["metrics"][NAME]["value"] > 0
    assert out["metrics"][NAME]["unit"] == "ms/job"
    # the window's report stays with the recorder once it is off: every
    # admit meta took the flat path
    assert obs.report()["counters"]["journal.encode_fallbacks"] == 0


def test_encode_reader():
    program = dict(LAYER["program"],
                   spans=dict(LAYER["program"]["spans"],
                              **{"store.encode": ENCODE}))
    layer = dict(LAYER, program=program)
    assert run.read_layer(NAME, layer) == pytest.approx(0.75)
    assert run.read_layer(NAME, dict(layer, decisions=0)) is None


@pytest.mark.parametrize("program", [
    None,                                     # a checkout with no recorder
    {"spans": {}, "counters": {}},            # a program without the span
    LAYER["program"],                         # the parent's durable spans
])
def test_encode_reader_says_nothing_without_the_span(program):
    assert run.read_layer(NAME, dict(LAYER, program=program)) is None
