"""Repo-specific contract configuration consumed by the lint passes.

Everything policy lives here — the passes themselves are mechanism.  When
an architectural decision changes (a new package edge, a new derived
cache, a new journal delegate), this is the one file to touch; ROADMAP's
"Checked contracts" section documents the why behind each entry.
"""
from __future__ import annotations

# -- W1xx · write-ahead discipline ---------------------------------------

#: files whose journaled classes the write-ahead pass audits.  A class is
#: "journaled" when any of its methods makes a journal call (see
#: ``writeahead.is_journal_call``); plain value classes in the same file
#: are skipped automatically.
JOURNALED_FILES = (
    "src/repro/fleet/controller.py",
    "src/repro/api/session.py",
    "src/repro/discovery/controller.py",
    "src/repro/store/",
)

#: ``self._fleet.<name>(...)`` calls that count as journal dominators: the
#: callee journals write-ahead itself, so the caller's follow-up mutations
#: (handle tables, mirrors) are covered by the delegate's record.
JOURNAL_DELEGATES = frozenset({
    "admit", "admit_jobs", "retire", "set_budget", "fail_device",
    "degrade_device", "restore_device", "restart_profile", "_decide",
    "apply_event", "quarantine",
})

#: attributes exempt from W101: derived caches, counters, and mirrors that
#: replay rebuilds from the journaled causes — mutating them is not state
#: the journal must capture first.
DERIVED_ATTRS = frozenset({
    # fleet controller: telemetry/bookkeeping regenerated by replay
    "events", "repacks", "repack_s", "_dropped", "_packer", "clf",
    "builder", "scheduler",
    # api session: handle mirrors + the round-robin cursor (the cursor is
    # journaled *after* advancing, by design: the record carries the
    # post-advance value replay assigns verbatim).  _default_device is a
    # memoized deterministic constant (DeviceInventory.generate(1)[0]).
    "_handles", "_retired", "_rr", "_store", "_discovery",
    "_default_device",
    # fleet controller: discovery attachment is construction-time wiring
    # that resume re-establishes explicitly, not journaled state
    "discovery",
    # store bookkeeping: snapshot/compaction cadence counters
    "_since_snapshot", "_snapshot_due", "_since_compact",
    # discovery controller: profiler/cadence internals
    "profiler", "_since_recluster",
})

#: methods exempt from W101 wholesale: the *apply* halves of the
#: journal-then-apply split (replay calls them directly, so by definition
#: they mutate without journaling), plus restore/adoption paths that
#: reconstruct state from already-durable records.
APPLY_METHODS = frozenset({
    "_admit_apply", "_set_plan", "_apply_record", "_replay_admit",
    "_restore_state", "_attach_store", "adopt_promoted", "admit_record",
    "restore", "rollback", "_load_snapshot", "resume",
})

# -- W2xx · journal-record exhaustiveness --------------------------------

#: the canonical record-kind registry (constants + ALL_KINDS).
KINDS_REGISTRY = "src/repro/store/kinds.py"

#: the resume/replay dispatch function: every registered kind must be
#: handled by (exactly) one ``case`` here, or be a registry marker.
DISPATCH_FUNC = "_apply_record"

# -- W3xx · determinism discipline ---------------------------------------

#: byte-identity-pinned packages: classification must be bit-reproducible
#: (tests pin sha256 of pipeline output and journal checksums), so no
#: wall-clock, no unseeded RNG, no iteration-order leaks in these trees.
PINNED_DIRS = (
    "src/repro/pipeline/",
    "src/repro/sched/",
    "src/repro/kernels/",
    "src/repro/store/",
)

# -- W4xx · layering / import boundary -----------------------------------

#: the allowed package DAG under ``repro``: package -> packages it may
#: import.  ``core`` never importing ``api`` (or ``fleet``) is the
#: north-star edge; ``store`` importing nothing but the stdlib-only
#: recorder ``obs`` keeps durability stdlib-only and cycle-free.
ALLOWED_EDGES = {
    "analysis": {"configs"},
    "api": {"core", "discovery", "fleet", "ft", "obs", "pipeline", "sched",
            "store", "telemetry"},
    "checkpoint": set(),
    "configs": set(),
    "core": {"kernels", "pipeline"},
    "data": {"configs"},
    "discovery": {"analysis", "core", "pipeline", "telemetry"},
    "fleet": {"analysis", "configs", "core", "ft", "obs", "pipeline",
              "sched", "store", "telemetry"},
    "ft": {"configs"},
    "kernels": set(),
    "launch": {"analysis", "api", "configs", "models", "sched", "serve",
               "telemetry", "train"},
    "legacy": {"core", "telemetry"},
    "lint": set(),
    "models": {"configs"},
    "obs": set(),
    "optim": {"configs"},
    "pipeline": {"analysis", "core", "kernels", "obs", "telemetry"},
    "sched": {"analysis", "core"},
    "serve": {"configs", "models"},
    "store": {"obs"},
    "telemetry": {"analysis", "configs", "core", "pipeline"},
    "train": {"checkpoint", "configs", "data", "ft", "models", "optim"},
}

#: facade files: user-facing entry points that must consume the library
#: only through its public surface.
FACADE_FILES = (
    "examples/quickstart.py",
    "examples/fleet_power_planner.py",
    "benchmarks/bench_fleet.py",
    "benchmarks/bench_fleet_scale.py",
    "benchmarks/bench_online_cap.py",
    "benchmarks/bench_chaos.py",
    "benchmarks/bench_recovery.py",
    "benchmarks/bench_discovery.py",
)

#: the only repro modules a facade file may import.
FACADE_ALLOWED = ("repro.api", "repro.fleet")

#: ``repro.legacy`` is the frozen pre-PR-3 surface: only its
#: characterization tests and the throughput benchmark may import it.
LEGACY_ALLOWED_DIRS = ("tests/", "benchmarks/")

# -- W5xx · float contract -----------------------------------------------

#: modules whose tests pin 1e-9 agreement or byte identity: bare float
#: equality against a non-integral literal is a latent flake there.
FLOAT_EQ_DIRS = PINNED_DIRS + ("src/repro/core/",)

#: modules where an implicit float32 downcast silently breaks the float64
#: reference contract.  ``kernels/`` is deliberately absent: Pallas TPU
#: kernels compute in float32 by design, and the float64 *reference*
#: implementations they are pinned against live in ``core``/``pipeline``.
DOWNCAST_DIRS = ("src/repro/pipeline/", "src/repro/sched/",
                 "src/repro/store/")
