"""Durable-session acceptance (PR 6): write-ahead journal + snapshot store.

The contracts pinned here:

  * **crash-at-any-point equivalence** — truncate the journal after ANY
    record, resume, and the reconstructed decisions / plans / device
    health / events are byte-identical to the live session at that point,
    with **zero classifier calls** (the `count_classifier_calls` spy);
  * **torn tails and corrupt snapshots never crash recovery** — damaged
    journal tails are truncated with a warning, a corrupt latest snapshot
    falls back to its predecessor (N-1 retention);
  * **store-inert-by-default** — a session without a ``store`` key takes
    exactly the pre-store code paths and produces identical outcomes;
  * the satellite hardening: poisoned telemetry cannot corrupt a later
    snapshot, and a corrupt spike cache degrades to a cold rebuild.
"""
import dataclasses
import glob
import json
import math
import os
import shutil
import warnings

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.api import (DeviceInventory, EventJournal, MinosSession,
                       NoStoreError, ProfileBuilder, ReferenceLibrary,
                       SessionStore, SnapshotStore, StoreError,
                       TPUPowerModel, TraceMeta, VariabilityModel,
                       count_classifier_calls, micro_gemm, micro_idle_burst,
                       micro_spmv_memory, micro_stencil, store_report,
                       stream_profile_workload, stream_telemetry, to_dict,
                       windowed_report)
import repro.obs as obs
from repro.configs.base import MeshConfig
from repro.fleet.records import meta_record
from repro.store import Encoded
from repro.store import journal as journal_mod
from repro.store.journal import JOURNAL_FILE
from repro.telemetry.simulator import TelemetryChunk

MODEL = TPUPowerModel()
TDP = MODEL.spec.tdp_w
FREQS = (0.6, 0.8, 1.0)
GATES = dict(min_confidence=0.2, min_fraction=0.1, min_spike_samples=50)


@pytest.fixture(scope="module")
def micro_library():
    return ReferenceLibrary(
        (stream_profile_workload(s, MODEL, FREQS, TDP, seed=i,
                                 target_duration=0.5)
         for i, s in enumerate([micro_gemm(), micro_idle_burst(),
                                micro_spmv_memory(), micro_stencil()])),
        built_on="tpu-v5e")


def _inventory():
    return DeviceInventory.generate({"tpu-v5e": 3, "tpu-v5p": 2},
                                    VariabilityModel(), seed=7)


def _telemetry(stream, seed):
    return stream_telemetry(stream, 1.0, MODEL, seed=seed,
                            target_duration=0.5)


def _state(session) -> dict:
    """JSON-comparable view of everything resume must reproduce."""
    fleet = session._fleet
    return {
        "job_ids": sorted(fleet.jobs),
        "decisions": {jid: to_dict(j.decision) for jid, j in
                      fleet.jobs.items() if j.decision is not None},
        "plans": {jid: to_dict(j.plan) for jid, j in fleet.jobs.items()
                  if j.plan is not None},
        "health": fleet.device_health(),
        "events": [to_dict(e) for e in fleet.events],
        "retired": {jid: to_dict(d) if d is not None else None
                    for jid, d in session._retired.items()},
        "budget": to_dict(fleet.budget_w),
        "failed": sorted(fleet._failed_devices),
        "rr": session._rr,
    }


def _drive_scripted(session, record_boundary=None):
    """The chaos script every store test replays: submits, an early
    decision, a failure, a budget squeeze, a degrade, a retire, and a
    restore — every journaled mutation kind appears at least once.
    ``record_boundary(tag)`` is called after each step."""
    mark = record_boundary or (lambda tag: None)
    mark("open")
    a = session.submit(_telemetry(micro_gemm(), 100), chips=4)
    mark("submit-a")
    a.run()
    mark("decide-a")
    b = session.submit(_telemetry(micro_spmv_memory(), 101), chips=2)
    mark("submit-b")
    session.fail_device(a.device.device_id)
    mark("fail")
    session.set_budget(5000.0)
    mark("budget")
    c = session.submit(_telemetry(micro_stencil(), 102), chips=1)
    mark("submit-c")
    session.run()
    mark("run")
    session.degrade_device(c.device.device_id)
    mark("degrade")
    session.retire(a.job_id)
    mark("retire")
    session.restore_device(sorted(session._fleet._failed_devices)[0])
    mark("restore")
    return session


@pytest.fixture(scope="module")
def scripted_store(micro_library, tmp_path_factory):
    """One scripted durable run: returns (store_path, boundaries) where
    boundaries maps journal seq -> the live session state at that point."""
    path = str(tmp_path_factory.mktemp("store") / "session")
    session = MinosSession(micro_library, inventory=_inventory(),
                           budget_w=20000.0, store=path, **GATES)
    boundaries = {}

    def mark(tag):
        boundaries[session.store.journal.last_seq] = (tag, _state(session))

    _drive_scripted(session, mark)
    session.close()
    return path, boundaries


def _truncate_journal(src: str, dst: str, keep_records: int) -> None:
    """Copy a store, keeping only the first ``keep_records`` journal
    records — the on-disk picture of a crash right after that append."""
    shutil.rmtree(dst, ignore_errors=True)
    shutil.copytree(src, dst)
    jp = os.path.join(dst, JOURNAL_FILE)
    with open(jp, "rb") as f:
        lines = f.read().splitlines(keepends=True)
    with open(jp, "wb") as f:
        f.writelines(lines[:keep_records])


def _resume_spied(path, micro_library):
    """Resume with the classifier spied from before construction; returns
    (session, calls)."""
    clf = micro_library.classifier()
    calls = count_classifier_calls(clf)
    session = MinosSession.resume(path, references=clf)
    return session, calls


# ---------------------------------------------------------------------------
# tentpole: crash-at-any-point equivalence, zero classifier calls
# ---------------------------------------------------------------------------
def test_resume_at_every_boundary_is_byte_identical(scripted_store,
                                                    micro_library, tmp_path):
    path, boundaries = scripted_store
    for seq, (tag, expected) in boundaries.items():
        crash = str(tmp_path / f"crash-{seq}")
        _truncate_journal(path, crash, seq)
        session, calls = _resume_spied(crash, micro_library)
        assert calls["n"] == 0, \
            f"resume at {tag!r} (seq {seq}) re-classified {calls['n']}x"
        got = _state(session)
        assert got == expected, f"state diverged at boundary {tag!r}"
        # jobs that were still profiling lost their in-flight telemetry:
        # they must come back flagged for an explicit re-run
        for job in session._fleet.jobs.values():
            if job.decision is None:
                assert job.needs_reprofile


def test_resume_after_any_single_record_never_crashes(scripted_store,
                                                      micro_library,
                                                      tmp_path):
    """Crash points BETWEEN session-level operations (mid-drain, between a
    cause record and its consequence events) must still resume cleanly —
    write-ahead redo semantics — with zero classifier calls throughout."""
    path, _ = scripted_store
    with open(os.path.join(path, JOURNAL_FILE), "rb") as f:
        total = len(f.read().splitlines())
    clf = micro_library.classifier()
    calls = count_classifier_calls(clf)
    for keep in range(1, total + 1):
        crash = str(tmp_path / "crash")
        _truncate_journal(path, crash, keep)
        session = MinosSession.resume(crash, references=clf)
        assert session.report() is not None
    assert calls["n"] == 0


def test_resume_with_torn_journal_tail(scripted_store, micro_library,
                                       tmp_path):
    """A partially flushed last record (no newline / garbage bytes) is
    truncated with a warning; the session recovers to the last intact
    record's state."""
    path, boundaries = scripted_store
    last_seq = max(boundaries)
    crash = str(tmp_path / "torn")
    _truncate_journal(path, crash, last_seq)
    with open(os.path.join(crash, JOURNAL_FILE), "ab") as f:
        f.write(b'{"seq": 999, "ts": 0.0, "kind": "bud')   # torn mid-write
    with pytest.warns(RuntimeWarning, match="torn record"):
        session, calls = _resume_spied(crash, micro_library)
    assert calls["n"] == 0
    assert _state(session) == boundaries[last_seq][1]


def test_resume_with_corrupt_middle_record_truncates_tail(scripted_store,
                                                          micro_library,
                                                          tmp_path):
    """A checksum-corrupt record invalidates everything after it (those
    records describe state that may never have been reached): recovery
    keeps the clean prefix and warns."""
    path, _ = scripted_store
    crash = str(tmp_path / "corrupt")
    shutil.rmtree(crash, ignore_errors=True)
    shutil.copytree(path, crash)
    for snap in glob.glob(os.path.join(crash, "snapshot-*.json")):
        os.remove(snap)                   # force pure journal replay
    jp = os.path.join(crash, JOURNAL_FILE)
    with open(jp, "rb") as f:
        lines = f.read().splitlines(keepends=True)
    victim = len(lines) // 2
    lines[victim] = lines[victim].replace(b'"kind"', b'"kinX"', 1)
    with open(jp, "wb") as f:
        f.writelines(lines)
    with pytest.warns(RuntimeWarning):
        session, calls = _resume_spied(crash, micro_library)
    assert calls["n"] == 0
    assert session.store.journal.last_seq >= victim


def test_resume_with_corrupt_latest_snapshot_falls_back(scripted_store,
                                                        micro_library,
                                                        tmp_path):
    """N-1 rollback: flipping bytes in the newest snapshot forces the
    previous snapshot (or full replay) — same reconstructed state."""
    path, boundaries = scripted_store
    crash = str(tmp_path / "badsnap")
    shutil.rmtree(crash, ignore_errors=True)
    shutil.copytree(path, crash)
    snaps = sorted(glob.glob(os.path.join(crash, "snapshot-*.json")))
    assert snaps, "scripted run should have written snapshots"
    with open(snaps[-1], "r+b") as f:
        f.seek(20)
        f.write(b"XXXXXX")
    with pytest.warns(RuntimeWarning, match="corrupt"):
        session, calls = _resume_spied(crash, micro_library)
    assert calls["n"] == 0
    assert _state(session) == boundaries[max(boundaries)][1]


def test_reprofile_after_resume_reproduces_decision(scripted_store,
                                                    micro_library, tmp_path):
    """A mid-profile job resumes via needs_reprofile: feeding it raises
    until JobHandle.reprofile, and re-running the SAME stream/seed yields
    the byte-identical decision the uninterrupted session reached."""
    path, boundaries = scripted_store
    submit_b = next(seq for seq, (tag, _) in boundaries.items()
                    if tag == "submit-b")
    final_states = boundaries[max(boundaries)][1]
    crash = str(tmp_path / "reprofile")
    _truncate_journal(path, crash, submit_b)
    session, calls = _resume_spied(crash, micro_library)
    # at this boundary A is decided and B is the lone mid-profile job
    b_id = next(jid for jid, j in session._fleet.jobs.items()
                if j.decision is None)
    handle = session.jobs[b_id]
    _, probe = _telemetry(micro_spmv_memory(), 101)
    with pytest.raises(ValueError, match="restart"):
        handle.feed(next(iter(probe)))
    assert calls["n"] == 0                 # resume itself never classified
    handle.reprofile(_telemetry(micro_spmv_memory(), 101))
    handle.run()
    got = to_dict(handle.decision())
    expect = final_states["decisions"][b_id]
    # same stream, same seed, same device frame -> byte-identical decision
    # (the device tag survives, too: the job was re-admitted on its device)
    assert got == expect


# ---------------------------------------------------------------------------
# store-inert-by-default + transparent journaling
# ---------------------------------------------------------------------------
def test_store_inert_by_default(micro_library):
    session = MinosSession(micro_library, inventory=_inventory(),
                           budget_w=20000.0, **GATES)
    assert session.store is None and session._fleet.journal is None
    _drive_scripted(session)
    session.close()                        # no-op without a store
    assert session.report() is not None


def test_stored_session_behaves_identically(micro_library, tmp_path):
    """Attaching a store must not perturb a single decision, plan, event,
    or placement — durability is observation, not interference."""
    plain = MinosSession(micro_library, inventory=_inventory(),
                         budget_w=20000.0, **GATES)
    stored = MinosSession(micro_library, inventory=_inventory(),
                          budget_w=20000.0, store=str(tmp_path / "s"),
                          **GATES)
    assert _state(_drive_scripted(plain)) \
        == _state(_drive_scripted(stored))
    stored.close()


def test_from_config_store_key(micro_library, tmp_path):
    path = str(tmp_path / "cfg-store")
    session = MinosSession.from_config(
        {"devices": {"tpu-v5e": 2}, "budget_w": 1500.0, "store": path},
        references=micro_library)
    assert session.store is not None
    assert os.path.exists(os.path.join(path, JOURNAL_FILE))
    session.submit(_telemetry(micro_gemm(), 5)).run()
    session.close()
    resumed = MinosSession.resume(path, references=micro_library)
    assert len(resumed._fleet.jobs) == 1
    resumed.close()


def test_fleet_property_is_the_journaled_controller(micro_library,
                                                   tmp_path):
    """``MinosSession.fleet`` is the session's own controller, read-only,
    and a mutation made through it is journaled and resumed."""
    path = str(tmp_path / "fleet")
    session = MinosSession(micro_library, inventory=_inventory(),
                           budget_w=20000.0, store=path, **GATES)
    fleet = session.fleet
    assert fleet is session._fleet and fleet.journal is session.store
    with pytest.raises(AttributeError):
        session.fleet = None
    handle = session.submit(_telemetry(micro_gemm(), 7), chips=2)
    handle.run()
    seq, dev = session.store.journal.last_seq, handle.device.device_id
    fleet.fail_device(dev)
    records, _ = EventJournal.recover(os.path.join(path, JOURNAL_FILE))
    assert records[seq].kind == "fail" and records[seq].data == {
        "device": dev}
    assert handle.device.device_id != dev
    resumed = MinosSession.resume(path, references=micro_library)
    assert resumed.device_health == session.device_health
    assert resumed.jobs[handle.job_id].device.device_id \
        == handle.device.device_id
    resumed.close()


def test_fresh_store_refuses_existing_journal(micro_library, tmp_path):
    path = str(tmp_path / "reused")
    MinosSession(micro_library, store=path, **GATES).close()
    with pytest.raises(ValueError, match="already holds a session journal"):
        MinosSession(micro_library, store=path, **GATES)


# ---------------------------------------------------------------------------
# satellite: actionable resume errors (no store vs corrupt store)
# ---------------------------------------------------------------------------
def test_resume_errors_distinguish_missing_from_corrupt(micro_library,
                                                        tmp_path):
    with pytest.raises(NoStoreError, match="no session store"):
        MinosSession.resume(str(tmp_path / "nowhere"),
                            references=micro_library)
    empty = tmp_path / "empty"
    empty.mkdir()
    with pytest.raises(NoStoreError, match="no session store"):
        MinosSession.resume(str(empty), references=micro_library)
    corrupt = tmp_path / "corrupt"
    corrupt.mkdir()
    (corrupt / JOURNAL_FILE).write_text("this is not a journal\n")
    with pytest.raises(StoreError, match="corrupt"), \
            warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        MinosSession.resume(str(corrupt), references=micro_library)
    assert issubclass(NoStoreError, StoreError)   # one except catches both


def test_from_config_unknown_key_suggests(micro_library):
    with pytest.raises(ValueError, match="did you mean 'budget_w'"):
        MinosSession.from_config({"budgett_w": 1.0},
                                 references=micro_library)
    with pytest.raises(ValueError, match="recognized"):
        MinosSession.from_config({"zzz": 1}, references=micro_library)


# ---------------------------------------------------------------------------
# journal / snapshot unit behavior
# ---------------------------------------------------------------------------
def test_journal_roundtrip_and_torn_tail(tmp_path):
    jp = str(tmp_path / "j" / JOURNAL_FILE)
    journal = EventJournal(jp)
    for i in range(5):
        assert journal.append("tick", {"i": i}) == i + 1
    journal.close()
    records, good = EventJournal.recover(jp)
    assert [r.data["i"] for r in records] == list(range(5))
    assert good == os.path.getsize(jp)
    with open(jp, "ab") as f:
        f.write(b'{"seq": 6, "ts": 1.0, "ki')
    with pytest.warns(RuntimeWarning, match="torn"):
        journal2, records2 = EventJournal.open_existing(jp)
    assert len(records2) == 5
    assert os.path.getsize(jp) == good       # damaged tail physically gone
    assert journal2.append("tick", {"i": 5}) == 6
    journal2.close()
    records3, _ = EventJournal.recover(jp)
    assert len(records3) == 6                # extends the clean prefix


def test_journal_checksum_and_sequence_breaks(tmp_path):
    jp = str(tmp_path / JOURNAL_FILE)
    journal = EventJournal(jp)
    for i in range(4):
        journal.append("tick", {"i": i})
    journal.close()
    with open(jp, "rb") as f:
        lines = f.read().splitlines(keepends=True)
    # checksum flip in record 3 -> prefix of 2 survives
    bad = lines[:2] + [lines[2].replace(b'"i":2', b'"i":9', 1)] + lines[3:]
    with open(jp, "wb") as f:
        f.writelines(bad)
    with pytest.warns(RuntimeWarning, match="checksum"):
        records, _ = EventJournal.recover(jp)
    assert len(records) == 2
    # sequence gap -> same prefix rule
    with open(jp, "wb") as f:
        f.writelines([lines[0], lines[2]])
    with pytest.warns(RuntimeWarning, match="sequence"):
        records, _ = EventJournal.recover(jp)
    assert len(records) == 1


def test_snapshot_retention_and_fallback(tmp_path):
    store = SnapshotStore(str(tmp_path), retain=2)
    for seq in (3, 7, 11):
        store.write({"v": seq}, seq)
    files = sorted(glob.glob(str(tmp_path / "snapshot-*.json")))
    assert len(files) == 2                   # N-1 retention pruned seq 3
    state, seq = store.load_latest()
    assert (state, seq) == ({"v": 11}, 11)
    with open(files[-1], "r+b") as f:        # corrupt the newest
        f.seek(10)
        f.write(b"~~~~")
    with pytest.warns(RuntimeWarning, match="corrupt"):
        state, seq = store.load_latest()
    assert (state, seq) == ({"v": 7}, 7)     # fell back one snapshot
    assert store.load_latest(max_seq=5) == (None, 0)   # future snaps skipped


def test_session_store_snapshot_cadence(tmp_path):
    store = SessionStore.create(str(tmp_path / "s"), snapshot_every=3)
    store.capture = lambda: {"n": store.journal.last_seq}
    for i in range(7):
        store.record("tick", i=i)
        store.flush_snapshot()
    assert store.load_snapshot() == ({"n": 6}, 6)      # wrote at 3 and 6
    store.close()


# ---------------------------------------------------------------------------
# satellite: poisoned telemetry cannot corrupt a later snapshot
# ---------------------------------------------------------------------------
def _poison(chunk, kind, rng_val):
    e = np.asarray(chunk.energy_j, np.float64).copy()
    b = np.asarray(chunk.busy_s, np.float64).copy()
    i = int(rng_val * (len(e) - 1))
    dt = chunk.sample_dt
    if kind == "nan-energy":
        e[i] = np.nan
    elif kind == "neg-energy":
        e[i] = -abs(e[i]) - 1.0
    elif kind == "backwards-energy":
        e[i] = e[i] * 0.25 - 1.0
        e[:i] = np.maximum.accumulate(e[:i]) + 2.0 + e[i]
    elif kind == "nan-busy":
        b[i] = np.nan
    elif kind == "backwards-busy":
        b[-1] = -1.0
    elif kind == "bad-dt":
        dt = 0.0
    return TelemetryChunk(energy_j=e, busy_s=b, sample_dt=dt,
                          start_index=chunk.start_index)


@settings(max_examples=12, deadline=None)
@given(st.sampled_from(["nan-energy", "neg-energy", "backwards-energy",
                        "nan-busy", "backwards-busy", "bad-dt"]),
       st.floats(min_value=0.0, max_value=1.0),
       st.integers(min_value=0, max_value=5))
def test_poisoned_chunk_never_corrupts_snapshot(kind, where, after):
    """Property: offer a poisoned chunk at an arbitrary stream position —
    ingest raises ValueError with job/device context and the builder's
    later snapshots are byte-identical to never having seen the poison."""
    meta, chunks = stream_telemetry(micro_gemm(), 1.0, MODEL, seed=42,
                                    target_duration=0.3,
                                    device_id="tpu-v5e/000")
    chunks = list(chunks)
    after = min(after, len(chunks) - 1)
    clean = ProfileBuilder(meta, tdp=TDP)
    poisoned = ProfileBuilder(meta, tdp=TDP)
    for chunk in chunks[:after]:
        clean.ingest(chunk)
        poisoned.ingest(chunk)
    with pytest.raises(ValueError) as err:
        poisoned.ingest(_poison(chunks[after], kind, where))
    assert meta.name in str(err.value)
    assert "tpu-v5e/000" in str(err.value)
    for chunk in chunks[after:]:             # the intact stream continues
        clean.ingest(chunk)
        poisoned.ingest(chunk)
    a, b = clean.finalize(), poisoned.finalize()
    assert np.array_equal(a.power_trace, b.power_trace)
    for c in (0.1, 0.25):
        assert np.array_equal(a.spike_vec(c), b.spike_vec(c))


# ---------------------------------------------------------------------------
# satellite: corrupt spike cache degrades to a cold rebuild
# ---------------------------------------------------------------------------
def test_library_load_survives_corrupt_spike_cache(micro_library, tmp_path):
    directory = str(tmp_path / "lib")
    micro_library.save(directory)
    intact = ReferenceLibrary.load(directory)        # byte-identity pin path
    for c in intact.bin_sizes:
        assert np.array_equal(intact.spike_matrix(c),
                              micro_library.spike_matrix(c))
    with open(os.path.join(directory, "spike_cache.npz"), "r+b") as f:
        f.truncate(100)                              # truncated mid-write
    with pytest.warns(RuntimeWarning, match="cold spike-matrix rebuild"):
        cold = ReferenceLibrary.load(directory)
    for c in cold.bin_sizes:
        assert np.array_equal(cold.spike_matrix(c),
                              micro_library.spike_matrix(c))
    # corrupt library.json: same degradation, still loads
    with open(os.path.join(directory, "library.json"), "w") as f:
        f.write("{not json")
    with pytest.warns(RuntimeWarning, match="cold spike-matrix rebuild"):
        cold2 = ReferenceLibrary.load(directory)
    assert [p.name for p in cold2] == [p.name for p in micro_library]


# ---------------------------------------------------------------------------
# journal-derived windowed reports
# ---------------------------------------------------------------------------
def test_windowed_report_from_scripted_run(scripted_store):
    path, _ = scripted_store
    windows = store_report(path, window_s=3600.0)
    assert windows, "journal should produce at least one window"
    totals = {k: sum(w[k] for w in windows)
              for k in ("admits", "decisions", "retires", "migrations",
                        "failures", "degrades", "restores")}
    assert totals["admits"] == 3
    assert totals["decisions"] == 3
    assert totals["retires"] == 1
    assert totals["failures"] == 1
    assert totals["degrades"] == 1
    assert totals["restores"] == 1
    assert totals["migrations"] >= 1         # the fail drained job A
    last = windows[-1]
    assert last["budget_w"] == 5000.0
    assert last["headroom_w"] == pytest.approx(5000.0 - last["planned_w"])
    assert 0.0 <= last["utilization"] <= 1.0


def test_windowed_report_handles_unbounded_budget():
    recs = [
        {"seq": 1, "ts": 0.0, "kind": "open",
         "data": {"budget_w": {"__float__": "inf"}}},
        {"seq": 2, "ts": 1.0, "kind": "admit", "data": {"job_id": "a"}},
        {"seq": 3, "ts": 2.0, "kind": "decision",
         "data": {"job_id": "a", "plan": {"job_id": "a",
                                          "predicted_p90_w": 123.0}}},
        {"seq": 4, "ts": 7200.0, "kind": "retire", "data": {"job_id": "a"}},
    ]
    windows = windowed_report(recs, window_s=3600.0)
    assert len(windows) == 3                 # gap windows are emitted too
    assert windows[0]["planned_w"] == 123.0
    assert windows[0]["utilization"] is None
    assert windows[0]["headroom_w"] == math.inf
    assert windows[1]["records"] == 0
    assert windows[2]["retires"] == 1 and windows[2]["planned_w"] == 0.0
    with pytest.raises(ValueError, match="positive"):
        windowed_report(recs, window_s=0.0)
    assert windowed_report([], window_s=60.0) == []


def test_meta_roundtrip_preserves_traces():
    """Admit-record codec: a TraceMeta rebuilt from its journal record is
    equal to the original (kernel rows back to tuples, floats exact)."""
    from repro.fleet.records import meta_from_record, meta_record
    meta, _ = _telemetry(micro_gemm(), 3)
    rebuilt = meta_from_record(json.loads(json.dumps(meta_record(meta))))
    assert rebuilt == meta
    assert isinstance(rebuilt, TraceMeta)


# ---------------------------------------------------------------------------
# one-pass record encoding: the journal's bytes are those of the two-pass
# encoder it replaced
# ---------------------------------------------------------------------------
def _two_pass_line(seq, ts, kind, data) -> str:
    """A journal line as the store and journal wrote it before the
    one-pass encoding: every payload value walked by ``to_dict`` (a
    ``TraceMeta`` first deep-copied by ``dataclasses.asdict``), then the
    payload serialized once for the checksum and again for the line."""
    payload = {k: to_dict(dataclasses.asdict(v) if isinstance(v, TraceMeta)
                          else v)
               for k, v in data.items()}
    rec = {"seq": seq, "ts": ts, "kind": kind, "data": payload}
    rec["sha"] = journal_mod._checksum(seq, ts, kind, payload)
    return json.dumps(rec, sort_keys=True, separators=(",", ":"),
                      allow_nan=False) + "\n"


def _odd_meta() -> TraceMeta:
    """A meta the flat codec cannot prove identical: a NumPy float among
    the fields, a NaN among the kernel rows."""
    meta, _ = _telemetry(micro_stencil(), 9)
    rows = list(meta.kernel_rows)
    rows[0] = (rows[0][0], math.nan, rows[0][2])
    return dataclasses.replace(meta, exec_time=np.float64(meta.exec_time),
                               app_sm_util=np.float32(0.25),
                               kernel_rows=rows)


@pytest.fixture(scope="module")
def encoded_records(micro_library, tmp_path_factory):
    """A durable session through one of each record payload: an admit
    spanning two devices with a mesh, a decision with its plan, the
    failure's events, an unbounded budget, and, through the store, a
    reprofile whose meta must take the generic walk.  Returns
    ``(journal_path, {case: (seq, kind, reference payload)},
    fallbacks)``."""
    path = str(tmp_path_factory.mktemp("encode") / "session")
    inventory = _inventory()
    session = MinosSession(micro_library, inventory=inventory,
                           budget_w=20000.0, store=path, **GATES)
    store = session.store
    calls = []
    real_record = store.record

    def spy(kind, **data):
        seq = real_record(kind, **data)
        calls.append((seq, kind, data))
        return seq

    store.record = spy
    tele = _telemetry(micro_spmv_memory(), 100)
    span = (inventory[0], inventory[1])
    a = session.submit(tele, device=span[0], devices=span, chips=4,
                       mesh=MeshConfig((2, 2), ("data", "model")),
                       global_batch=64)
    a.run()
    session.fail_device(a.device.device_id)
    session.set_budget(math.inf)
    obs.reset()
    obs.enable()
    try:
        store.record("reprofile", job_id=a.job_id,
                     meta=Encoded(meta_record(_odd_meta())))
        fallbacks = obs.report()["counters"]["journal.encode_fallbacks"]
    finally:
        obs.disable()
        obs.reset()
    store.close()

    def first(kind):
        return next((seq, k, d) for seq, k, d in calls if k == kind)

    seq, kind, data = first("admit")
    assert isinstance(data["meta"], Encoded)
    cases = {"admit": (seq, kind, dict(data, meta=tele[0])),
             "decision": first("decision"), "event": first("event"),
             "budget_inf": first("budget"),
             "meta_nan_numpy": calls[-1][:2] + (
                 dict(calls[-1][2], meta=_odd_meta()),)}
    return os.path.join(path, JOURNAL_FILE), cases, fallbacks


@pytest.mark.parametrize("case", ["admit", "decision", "event",
                                  "budget_inf", "meta_nan_numpy"])
def test_journal_lines_match_the_two_pass_encoder(encoded_records, case):
    jp, cases, _ = encoded_records
    seq, kind, reference = cases[case]
    with open(jp, "rb") as f:
        line = f.read().decode().splitlines(keepends=True)[seq - 1]
    ts = json.loads(line)["ts"]
    assert line == _two_pass_line(seq, ts, kind, reference)
    if case == "admit":
        # a real multi-row meta, on the flat path: nothing fell back
        assert len(reference["meta"].kernel_rows) > 1
        assert reference["mesh"] is not None
    if case == "budget_inf":
        assert '"budget_w":{"__float__":"inf"}' in line


def test_one_pass_journal_recovers_every_line(encoded_records):
    """The odd meta took the generic walk, counted once; the recovery's
    checksum verifier accepts every line the one-pass encoder wrote."""
    jp, cases, fallbacks = encoded_records
    assert fallbacks == 1
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        records, good = EventJournal.recover(jp)
    assert good == os.path.getsize(jp)
    with open(jp, "rb") as f:
        assert len(records) == len(f.read().splitlines())
    assert records[-1].kind == "reprofile"
    assert records[-1].data["meta"]["kernel_rows"][0][1] \
        == {"__float__": "nan"}
    assert {r.kind for r in records} >= {"admit", "decision", "event",
                                         "budget", "fail", "reprofile"}


def test_encoded_value_that_is_not_json_raises_without_an_encoder(tmp_path):
    """A store with no codec cannot walk a refused value: the record
    raises and the journal stays as it was."""
    store = SessionStore.create(str(tmp_path / "s"))
    store.record("open", a=1)
    with pytest.raises(ValueError):
        store.record("reprofile", meta=Encoded({"x": math.nan}))
    assert store.journal.last_seq == 1
    store.record("tick", i=0)
    store.close()
    records, _ = EventJournal.recover(store.journal.path)
    assert [r.seq for r in records] == [1, 2]


def test_journal_batch_coalesces_flushes_and_recovers(tmp_path):
    """ISSUE 7 satellite: appends inside ``batch()`` defer their flush to
    batch exit — small records stay in the stdio buffer mid-batch — yet the
    file recovers every record intact afterwards."""
    jp = str(tmp_path / JOURNAL_FILE)
    journal = EventJournal(jp)
    journal.append("open", {})               # unbatched: flushed eagerly
    base = os.path.getsize(jp)
    with journal.batch():
        for i in range(3):                   # 3 tiny records << 8K buffer
            journal.append("tick", {"i": i})
        assert os.path.getsize(jp) == base   # nothing flushed mid-batch
        with journal.batch():                # re-entrant: still deferred
            journal.append("tick", {"i": 3})
        assert os.path.getsize(jp) == base
    assert os.path.getsize(jp) > base        # one flush at batch exit
    journal.close()
    records, good = EventJournal.recover(jp)
    assert [r.kind for r in records] == ["open"] + ["tick"] * 4
    assert good == os.path.getsize(jp)


def test_journal_batch_preserves_fsync_per_record(tmp_path):
    """fsync=True journals keep per-record flush (+fsync) inside a batch —
    explicit durability is never weakened by coalescing."""
    jp = str(tmp_path / JOURNAL_FILE)
    journal = EventJournal(jp, fsync=True)
    with journal.batch():
        journal.append("tick", {"i": 0})
        size_after_first = os.path.getsize(jp)
        assert size_after_first > 0          # hit the OS immediately
        journal.append("tick", {"i": 1})
        assert os.path.getsize(jp) > size_after_first
    journal.close()
    records, _ = EventJournal.recover(jp)
    assert len(records) == 2


# ---------------------------------------------------------------------------
# journal compaction (PR 9 satellite): folded segments, unbroken sequences
# ---------------------------------------------------------------------------
def _ticked_store(path, n=40, snapshot_every=5, rotate_every=4,
                  compact_every=None):
    store = SessionStore.create(path, snapshot_every=snapshot_every,
                                rotate_every=rotate_every,
                                compact_every=compact_every)
    store.capture = lambda: {"n": store.journal.last_seq}
    store.record("open", a=1)
    for i in range(n):
        store.record("tick", i=i)
        store.flush_snapshot()
    return store


def test_compact_folds_segments_and_keeps_sequences(tmp_path):
    path = str(tmp_path / "s")
    store = _ticked_store(path, compact_every=10)
    last = store.journal.last_seq
    base = store.journal.base
    assert base is not None and base["base_seq"] > 0
    assert base["open"]["kind"] == "open"         # open record preserved
    live_segments = [k for k, _ in EventJournal.segments(store.journal.path)]
    assert live_segments and min(live_segments) > base["through_segment"]
    store.close()
    # recovery: one unbroken sequence from the base floor to the tip
    reopened = SessionStore.open_existing(path)
    assert reopened.journal.last_seq == last
    seqs = [r.seq for r in reopened.recovered_records]
    assert seqs == list(range(base["base_seq"] + 1, last + 1))
    opened = reopened.open_record()
    assert opened.kind == "open" and opened.seq == 1
    assert reopened.load_snapshot()[0] is not None
    # appends extend the same sequence
    assert reopened.record("tick", i=99) == last + 1
    reopened.close()


def test_compact_respects_n1_snapshot_fallback(tmp_path):
    """Nothing folds while fewer than two intact snapshots exist — the N-1
    fallback must always stay replayable."""
    store = SessionStore.create(str(tmp_path / "s"), rotate_every=3)
    for i in range(10):
        store.record("tick", i=i)
    assert store.compact() == 0                    # no snapshots at all
    store.capture = lambda: {"n": store.journal.last_seq}
    store.flush_snapshot(force=True)
    assert store.compact() == 0                    # one snapshot: still no
    store.record("tick", i=10)
    assert store.compact() >= 1                    # second snapshot -> folds
    store.close()


def test_compact_only_folds_fully_covered_segments(tmp_path):
    """A segment folds only when the OLDEST retained snapshot sits at or
    past its last record: restoring the fallback never needs folded data."""
    path = str(tmp_path / "s")
    store = _ticked_store(path, n=20, snapshot_every=50, rotate_every=3)
    store.snapshots.write({"n": 6}, 6)
    store.snapshots.write({"n": 18}, 18)
    store.capture = None                # no fresh tip snapshot: pin the floor
    folded = store.compact()
    base = store.journal.base
    assert folded >= 1
    assert base["base_seq"] == 6                   # floor = oldest snapshot
    store.close()


def test_compact_every_cadence_triggers_automatically(tmp_path):
    store = _ticked_store(str(tmp_path / "auto"), compact_every=10)
    assert store.journal.base is not None          # folded without compact()
    plain = _ticked_store(str(tmp_path / "plain"))
    assert plain.journal.base is None              # knob off -> no base file
    store.close()
    plain.close()


def test_compacted_session_resumes_identically(micro_library, tmp_path):
    """Recovery-equivalence pin: the same scripted session driven through a
    compacting store and a plain store resumes to the identical state, with
    zero classifier calls, and the compacted store really shed segments."""
    from repro.api.results import to_dict as _td
    paths, states = {}, {}
    for mode, compact_every in (("plain", None), ("compact", 6)):
        path = str(tmp_path / mode)
        store = SessionStore.create(path, encode=_td, snapshot_every=4,
                                    rotate_every=3,
                                    compact_every=compact_every)
        session = MinosSession(micro_library, inventory=_inventory(),
                               budget_w=20000.0, store=store, **GATES)
        _drive_scripted(session)
        session.close()
        paths[mode] = path
        resumed, calls = _resume_spied(path, micro_library)
        assert calls["n"] == 0
        states[mode] = _state(resumed)
        resumed.close()
    assert states["compact"] == states["plain"]
    assert os.path.exists(EventJournal.base_path(
        os.path.join(paths["compact"], JOURNAL_FILE)))
    jp_plain = os.path.join(paths["plain"], JOURNAL_FILE)
    jp_compact = os.path.join(paths["compact"], JOURNAL_FILE)
    assert len(EventJournal.segments(jp_compact)) \
        < len(EventJournal.segments(jp_plain))


def test_corrupt_base_file_warns_and_fails_closed(tmp_path):
    """A damaged base file means the folded records are gone: recovery
    warns, and a store whose surviving snapshot cannot cover the loss
    refuses to fabricate state."""
    path = str(tmp_path / "s")
    store = _ticked_store(path, compact_every=10)
    store.close()
    bp = EventJournal.base_path(os.path.join(path, JOURNAL_FILE))
    with open(bp, "r+b") as f:
        f.seek(5)
        f.write(b"XXXX")
    with pytest.warns(RuntimeWarning, match="journal base"):
        with pytest.raises(StoreError, match="no intact records"):
            # with the base gone, the surviving segments start mid-sequence
            # and chain to nothing: the store refuses to fabricate state
            SessionStore.open_existing(path)


def test_session_store_batch_delegates_and_snapshots_stay_safe(tmp_path):
    """SessionStore.batch() wraps the journal; a snapshot written mid-batch
    (past the unflushed tail) is skipped by load_snapshot after a crash
    that tears the tail — the max_seq guard."""
    store = SessionStore.create(str(tmp_path / "s"))
    with store.batch():
        for i in range(4):
            store.record("tick", i=i)
    assert store.journal.last_seq == 4
    store.close()
    reopened = SessionStore.open_existing(str(tmp_path / "s"))
    assert [r.data["i"] for r in reopened.recovered_records] == [0, 1, 2, 3]
    reopened.close()
