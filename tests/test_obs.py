"""The control plane's span and counter recorder (``repro.obs``): off it
reads no clock and hands out one shared null context; on it keeps calls,
total and self time per span and the counters; and it never changes what
the fleet decides, places or journals."""
import hashlib
import os
import time
import types

import pytest

import repro.obs as obs
from repro.api import (DeviceInventory, MinosSession, ReferenceLibrary,
                       TPUPowerModel, VariabilityModel,
                       stream_profile_workload, stream_telemetry)
from repro.pipeline.batch import BatchProfileEngine
from repro.store import journal as journal_mod
from repro.store.journal import JOURNAL_FILE
from repro.telemetry.kernel_stream import (micro_gemm, micro_idle_burst,
                                           micro_spmv_compute,
                                           micro_spmv_memory, micro_stencil)

MODEL = TPUPowerModel()
GATES = dict(min_confidence=0.2, min_fraction=0.1, min_spike_samples=50)
STREAMS = (micro_gemm, micro_spmv_memory, micro_spmv_compute,
           micro_idle_burst, micro_stencil)
# every span the served path opens on the NumPy engine; the device
# histogram's own span only where the engine counts on the device
SERVED = {"tick", "engine", "engine.validate", "engine.advance", "classify",
          "classify.snapshot", "classify.sweep", "finalize_job", "admit",
          "retire"}


@pytest.fixture(autouse=True)
def recorder_off():
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


@pytest.fixture(scope="module")
def library():
    return ReferenceLibrary(
        (stream_profile_workload(s(), MODEL, (0.6, 0.8, 1.0),
                                 MODEL.spec.tdp_w, seed=i,
                                 target_duration=0.5)
         for i, s in enumerate([micro_gemm, micro_idle_burst,
                                micro_spmv_memory, micro_stencil])),
        built_on="tpu-v5e")


def _drive(library, store=None) -> dict:
    """A small fleet through every spanned call: one bulk admission, the
    mux ticks, ``finalize_job`` for the streams that end undecided, and a
    retire.  Returns what the fleet decided and placed."""
    inventory = DeviceInventory.generate({"tpu-v5e": 3, "tpu-v5p": 2},
                                         VariabilityModel(), seed=7)
    session = MinosSession(library, inventory=inventory, budget_w=20000.0,
                           store=store, **GATES)
    sources = [stream_telemetry(s(), 1.0, MODEL, seed=30 + i,
                                target_duration=0.5, chunk_samples=128)
               for i, s in enumerate(STREAMS * 2)]
    handles = session.submit_many(sources, chips=4)
    session.run(finalize=False)
    fleet = session._fleet
    for h in handles:
        fleet.finalize_job(h.job_id)
    session.retire(handles[0].job_id)
    out = dict(
        decisions={j: repr(job.decision) for j, job in fleet.jobs.items()},
        plans={j: repr(job.plan) for j, job in fleet.jobs.items()},
        schedule=repr(fleet.repacks[-1]))
    session.close()
    return out


def test_off_path_reads_no_clock(library, monkeypatch):
    def no_clock():
        raise AssertionError("the recorder read the clock while off")

    monkeypatch.setattr(time, "perf_counter_ns", no_clock)
    assert obs.span("tick") is obs.span("engine")
    obs.count("classify.swept", 3)
    _drive(library)
    assert obs.report() == {"spans": {}, "counters": {}}


def test_self_time_is_total_less_children(monkeypatch):
    ticks = iter([0, 10, 40, 50, 60, 100, 200, 230])
    monkeypatch.setattr(obs, "time", types.SimpleNamespace(
        perf_counter_ns=lambda: next(ticks)))
    opened = []

    class Annotation:
        def __init__(self, name, **kw):
            opened.append((name, kw))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    obs.enable()
    monkeypatch.setattr(obs, "_annotation", Annotation)
    with obs.span("tick"):               # 0 .. 100
        with obs.span("engine"):         # 10 .. 40
            pass
        with obs.span("classify"):       # 50 .. 60
            pass
    with obs.span("retire"):             # 200 .. 230
        pass
    spans = obs.report()["spans"]
    assert spans["tick"] == {"calls": 1, "total_s": pytest.approx(100e-9),
                             "self_s": pytest.approx(60e-9)}
    assert spans["engine"]["self_s"] == spans["engine"]["total_s"] \
        == pytest.approx(30e-9)
    assert spans["classify"]["total_s"] == pytest.approx(10e-9)
    assert spans["retire"] == {"calls": 1, "total_s": pytest.approx(30e-9),
                               "self_s": pytest.approx(30e-9)}
    # a span carries the number of the last tick opened
    assert opened == [("minos.tick", {"tick": 1}),
                      ("minos.engine", {"tick": 1}),
                      ("minos.classify", {"tick": 1}),
                      ("minos.retire", {"tick": 1})]


def test_counters_and_reset():
    obs.enable()
    obs.count("classify.swept", 5)
    obs.count("classify.swept")
    obs.count("classify.decided", 2)
    with obs.span("tick"):
        pass
    rep = obs.report()
    assert rep["counters"] == {"classify.swept": 6, "classify.decided": 2}
    assert rep["spans"]["tick"]["calls"] == 1
    obs.disable()
    obs.count("classify.swept", 100)       # off: not counted
    assert obs.report()["counters"]["classify.swept"] == 6
    obs.reset()
    assert obs.report() == {"spans": {}, "counters": {}}


def test_a_fleet_run_records_every_served_span(library):
    obs.enable()
    _drive(library)
    rep = obs.report()
    assert SERVED <= set(rep["spans"])
    assert "engine.device" not in rep["spans"]          # NumPy engine
    for name in SERVED:
        s = rep["spans"][name]
        assert s["calls"] >= 1 and 0 <= s["self_s"] <= s["total_s"], name
    assert rep["spans"]["admit"]["calls"] == 1
    assert rep["spans"]["retire"]["calls"] == 1
    c = rep["counters"]
    assert c["snapshot.samples"] > 0
    assert 0 < c["classify.decided"] <= c["classify.swept"]
    engine = rep["spans"]["engine"]
    parts = sum(rep["spans"][n]["total_s"]
                for n in ("engine.validate", "engine.advance"))
    assert engine["self_s"] == pytest.approx(engine["total_s"] - parts)


def _journal_digest(path: str) -> str:
    with open(os.path.join(path, JOURNAL_FILE), "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


@pytest.mark.parametrize("backend", ["numpy", "pallas"])
def test_outputs_are_byte_identical_with_tracing_on_and_off(
        library, tmp_path, monkeypatch, backend):
    init = BatchProfileEngine.__init__

    def with_backend(self, *args, **kwargs):
        init(self, *args, **{**kwargs, "backend": backend})

    monkeypatch.setattr(BatchProfileEngine, "__init__", with_backend)
    # the journal's wall-clock stamp is informational; pin it so that the
    # two runs' journals can be compared byte for byte
    monkeypatch.setattr(journal_mod, "time",
                        types.SimpleNamespace(time=lambda: 0.0))
    off = _drive(library, store=str(tmp_path / "off"))
    obs.enable()
    on = _drive(library, store=str(tmp_path / "on"))
    spans = obs.report()["spans"]
    assert ("engine.device" in spans) == (backend == "pallas")
    assert on == off
    assert _journal_digest(str(tmp_path / "on")) \
        == _journal_digest(str(tmp_path / "off"))
