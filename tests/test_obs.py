"""The control plane's span and counter recorder (``repro.obs``): off it
reads no clock and hands out one shared null context; on it keeps calls,
total and self time per span and the counters; and it never changes what
the fleet decides, places or journals."""
import hashlib
import os
import time
import types

import numpy as np
import pytest

import repro.obs as obs
from repro.api import (DeviceInventory, MinosSession, ReferenceLibrary,
                       TPUPowerModel, VariabilityModel,
                       stream_profile_workload, stream_telemetry)
from repro.pipeline.batch import BatchProfileEngine
from repro.store import journal as journal_mod
from repro.store.journal import JOURNAL_FILE
from repro.telemetry.simulator import TelemetryChunk, TraceMeta
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


def test_pq_counters_count_the_engine_profiles(library, monkeypatch):
    """``snapshot.pq_prefilled`` counts every profile the engine emits (each
    carries its p90 from the slot); nothing is recorded while off."""
    emitted = []
    profile = BatchProfileEngine._profile

    def counted(self, *args, **kwargs):
        emitted.append(1)
        return profile(self, *args, **kwargs)

    monkeypatch.setattr(BatchProfileEngine, "_profile", counted)
    _drive(library)
    assert emitted and obs.report() == {"spans": {}, "counters": {}}
    emitted.clear()
    obs.enable()
    _drive(library)
    c = obs.report()["counters"]
    assert c["snapshot.pq_prefilled"] == len(emitted)


def _slot_stream(eng, power):
    n = len(power)
    e = np.concatenate([[0.0], np.cumsum(power * 1e-3)])
    b = np.arange(n + 1) * 1e-3
    meta = TraceMeta(name="slot", domain="test", sample_dt=1e-3,
                     n_samples=n, exec_time=1.0, app_sm_util=0.5,
                     app_dram_util=0.5, kernel_rows=[])
    slot = eng.alloc(meta, 200.0)
    for i in range(0, n, 256):
        eng.ingest_batch([slot], [TelemetryChunk(
            energy_j=e[i + 1:i + 257], busy_s=b[i + 1:i + 257],
            sample_dt=1e-3, start_index=i)])
        eng.snapshot_batch([slot])
    eng.finalize(slot)


@pytest.mark.parametrize("shape", ["stationary", "step_down"])
def test_pq_rebuilds_count_windows_rebuilt(shape):
    """A stationary trace never rebuilds the rank window; power that steps
    down from above TDP to a fifth of it does."""
    rng = np.random.default_rng(3)
    n, high = 12_000, 1_500
    if shape == "stationary":
        power = rng.uniform(100.0, 260.0, n)
    else:
        power = np.concatenate([rng.uniform(200.0, 260.0, high),
                                rng.uniform(20.0, 60.0, n - high)])
    obs.enable()
    _slot_stream(BatchProfileEngine(), power)
    c = obs.report()["counters"]
    assert c["snapshot.pq_prefilled"] == -(-n // 256) + 1
    if shape == "stationary":
        assert "snapshot.pq_rebuilds" not in c
    else:
        assert c["snapshot.pq_rebuilds"] >= 1


# spans and counters of the durable and fault paths
DURABLE_SPANS = {"journal.append", "store.encode", "store.snapshot",
                 "fault.fail", "fault.restore", "fault.pick_target"}
DURABLE_COUNTERS = {"journal.records", "journal.bytes", "journal.fsyncs",
                    "journal.encode_fallbacks", "snapshot.bytes",
                    "fault.migrated", "fault.reprofiled"}


def _store_files(path: str) -> dict:
    """Every file of a store directory by name, as bytes."""
    out = {}
    for name in sorted(os.listdir(path)):
        with open(os.path.join(path, name), "rb") as f:
            out[name] = f.read()
    return out


def _durable_drive(library, path: str, mark=lambda tag: None) -> dict:
    """A small durable session, every record fsynced, through a failure
    and a restore: two jobs decide on device 0, two more are mid-profile
    there when it fails, so the failure migrates four jobs and cuts two
    runs.  The store is left as a crash leaves it (neither closed nor
    flushed), and ``MinosSession.resume`` rebuilds the session from it.
    ``mark(tag)`` is called after each step; returns what the fleet and
    the resumed session decided and placed."""
    from repro.api import SessionStore
    inventory = DeviceInventory.generate({"tpu-v5e": 3}, VariabilityModel(),
                                         seed=7)
    store = SessionStore.create(path, fsync=True, snapshot_every=6)
    session = MinosSession(library, inventory=inventory, budget_w=20000.0,
                           store=store, **GATES)
    dev = inventory[0]
    streams = [stream_telemetry(s(), 1.0, dev.power_model(), seed=40 + i,
                                target_duration=0.5, chunk_samples=128,
                                device_id=dev.device_id)
               for i, s in enumerate(STREAMS[:4])]
    handles = session.submit_many([(meta, None) for meta, _ in streams],
                                  device=dev, chips=2)
    chunks = [list(c) for _, c in streams]
    for h, c in zip(handles[:2], chunks[:2]):
        h.feed(c)
        h.decision()
    for h, c in zip(handles[2:], chunks[2:]):
        h.feed(c[:1])
    mark("streamed")
    fleet = session.fleet
    events = fleet.fail_device(dev.device_id)
    assert sum(e.kind == "migrate" for e in events) == 4
    assert sum(e.detail == "reprofile" for e in events) == 2
    mark("failed")
    fleet.restore_device(dev.device_id)
    mark("restored")
    live = dict(
        decisions={j: repr(job.decision) for j, job in fleet.jobs.items()},
        plans={j: repr(job.plan) for j, job in fleet.jobs.items()},
        schedule=repr(fleet.repacks[-1]))
    resumed = MinosSession.resume(path, references=library)
    back = resumed.fleet
    live["resumed"] = dict(
        decisions={j: repr(job.decision) for j, job in back.jobs.items()},
        devices={j: job.device.device_id for j, job in back.jobs.items()})
    resumed.store.close()
    mark("resumed")
    return live


def test_durable_spans_and_counters_fire(library, tmp_path):
    path = str(tmp_path / "store")
    seen = {}

    def mark(tag):
        seen[tag] = obs.report()

    obs.enable()
    _durable_drive(library, path, mark)
    rep = seen["resumed"]
    assert DURABLE_SPANS <= set(rep["spans"])
    assert DURABLE_COUNTERS <= set(rep["counters"])
    # before the resume: the failure migrated four jobs and cut two runs,
    # the restore found nothing stranded
    c = seen["restored"]["counters"]
    assert c["fault.migrated"] == 4 and c["fault.reprofiled"] == 2
    assert seen["failed"]["spans"]["fault.fail"]["calls"] == 1
    assert "fault.restore" not in seen["failed"]["spans"]
    assert seen["restored"]["spans"]["fault.restore"]["calls"] == 1
    assert seen["failed"]["spans"]["fault.pick_target"]["calls"] == 4
    # every record counted, its bytes and its fsync: the journal file
    # holds exactly what the counters say
    journal = os.path.join(path, JOURNAL_FILE)
    records = c["journal.records"]
    assert rep["spans"]["journal.append"]["calls"] >= records > 0
    assert c["journal.fsyncs"] == records
    # each record's payload encoded once, every admit meta on the flat path
    assert rep["spans"]["store.encode"]["calls"] >= records
    assert c["journal.encode_fallbacks"] == 0
    with open(journal, "rb") as f:
        lines = f.read().splitlines(keepends=True)
    assert c["journal.bytes"] == sum(map(len, lines[:records]))
    # the cadence snapshot and the resume's closing one, with their bytes
    snaps = [n for n in os.listdir(path) if n.startswith("snapshot-")]
    assert rep["spans"]["store.snapshot"]["calls"] >= 2
    assert rep["counters"]["snapshot.bytes"] >= sum(
        os.path.getsize(os.path.join(path, n)) for n in snaps) > 0
    for name in DURABLE_SPANS:
        s = rep["spans"][name]
        assert 0 <= s["self_s"] <= s["total_s"], name


def test_durable_outputs_are_byte_identical_with_tracing_on_and_off(
        library, tmp_path, monkeypatch):
    from repro.store import snapshots as snapshots_mod
    # the wall-clock stamps of records and snapshots are informational;
    # pin them so that the two stores can be compared byte for byte
    pinned = types.SimpleNamespace(time=lambda: 0.0)
    monkeypatch.setattr(journal_mod, "time", pinned)
    monkeypatch.setattr(snapshots_mod, "time", pinned)
    off = _durable_drive(library, str(tmp_path / "off"))
    obs.enable()
    on = _durable_drive(library, str(tmp_path / "on"))
    assert obs.report()["counters"]["journal.fsyncs"] > 0
    assert on == off
    assert on["resumed"]["decisions"] == on["decisions"]
    assert _store_files(str(tmp_path / "on")) \
        == _store_files(str(tmp_path / "off"))


def test_durable_off_path_reads_no_clock(library, tmp_path, monkeypatch):
    def no_clock():
        raise AssertionError("the recorder read the clock while off")

    monkeypatch.setattr(time, "perf_counter_ns", no_clock)
    _durable_drive(library, str(tmp_path / "store"))
    assert obs.report() == {"spans": {}, "counters": {}}


def test_store_encoding_span_and_fallback_counter(tmp_path, monkeypatch):
    """On, every record opens ``store.encode`` and counts the pre-encoded
    values that took the generic walk; off, the same records create
    neither and allocate no span."""
    from repro.api import SessionStore, to_dict
    from repro.store import Encoded

    def records(path):
        store = SessionStore.create(path, encode=to_dict)
        store.record("budget", budget_w=float("inf"))
        store.record("reprofile", meta=Encoded({"rows": [(0.5, 1.0)]}))
        store.record("reprofile", meta=Encoded({"rows": [(float("nan"),)]}))
        store.close()

    obs.enable()
    records(str(tmp_path / "on"))
    rep = obs.report()
    assert rep["spans"]["store.encode"]["calls"] == 4     # one re-walk
    assert rep["counters"]["journal.encode_fallbacks"] == 1
    assert rep["counters"]["journal.records"] == 3
    obs.disable()
    obs.reset()

    def no_span(name):
        raise AssertionError(f"span {name!r} allocated while off")

    monkeypatch.setattr(obs, "_Span", no_span)
    records(str(tmp_path / "off"))
    assert obs.report() == {"spans": {}, "counters": {}}
