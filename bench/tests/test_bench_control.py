"""The control: the plain reference in float32, put in the program's place,
has to make ``correct`` come out false where the program's run is
correct."""
import bench_rehearsal as R
from bench import control, harness


def test_float32_control_is_not_correct_where_the_program_is(monkeypatch):
    R.pretend_chip(monkeypatch)
    spec = R.small_spec("hpc.replay", nodes=48)
    spec["check"]["sample"] = 40
    cell = harness.Cell(spec, seed=21)
    cell.build()
    rec = cell.run(2.0)
    got = control.readings(cell, rec)
    prog, ctrl = got["program"], got["control"]
    assert prog["correct"] is True, prog
    assert prog["placement_gap"] == 0 and prog["violations"] == 0
    assert ctrl["correct"] is False, ctrl
    limits = spec["check"]["limits"]
    assert any(ctrl[name] > limits[name]
               for name in ("hist_gap", "decision_gap", "confidence_gap"))
