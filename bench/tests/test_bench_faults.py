"""A run with the timed path broken underneath reports ``correct`` false,
once for each fault a cell of this benchmark can have.  (One chip: there
is no exchange between chips to leave out.)"""
import numpy as np
import pytest

import bench_rehearsal as R


def state_unchanged(monkeypatch):
    """The engine's ingest step returns and leaves every slot as it was."""
    from repro.pipeline.batch import BatchProfileEngine
    monkeypatch.setattr(BatchProfileEngine, "ingest_batch",
                        lambda self, slots, chunks: None)


def half_the_batch(monkeypatch):
    """The device histogram counts only the first half of the tick's rows."""
    import repro.kernels.ops as ops
    real = ops.spike_hist_packed

    def half(packed, fields, *a, **k):
        packed = np.array(packed)
        rows = np.nonzero((packed >= 0).any(axis=1))[0]
        packed[rows[len(rows) // 2:]] = -1
        return real(packed, fields, *a, **k)

    monkeypatch.setattr(ops, "spike_hist_packed", half)


def altered_answer(monkeypatch):
    """Each issued cap is altered where the decision is made."""
    from repro.pipeline.online import OnlineCapController
    real = OnlineCapController._record

    def record(self, profile, builder, sel, confidence, early):
        d = real(self, profile, builder, sel, confidence, early)
        d.cap = round(d.cap - 0.05, 2)
        return d

    monkeypatch.setattr(OnlineCapController, "_record", record)


@pytest.mark.parametrize("workload", R.CELLS)
@pytest.mark.parametrize("fault", [state_unchanged, half_the_batch,
                                   altered_answer])
def test_fault_makes_the_run_incorrect(fault, workload, monkeypatch, capsys):
    R.pretend_chip(monkeypatch)
    fault(monkeypatch)
    seconds = 1.5 if workload.endswith("replay") else 3.0
    out = R.result(capsys, R.args(workload, seed=9, seconds=seconds))
    assert out["correct"] is False
    failing = [k for k, c in out["checks"].items()
               if (c["value"] < c["limit"] if c.get("at_least")
                   else c["value"] > c["limit"])]
    assert failing, out["checks"]
