"""``repro.api`` — the one front door to the Minos reproduction.

The paper's pitch is that a single low-cost profiling + classification
mechanism serves many objectives across diverse workloads and heterogeneous
devices.  This package is that pitch as an API: a ``MinosSession`` owns the
reference library, the device inventory, the budget, and the policy plugins,
and every scenario — one job on one chip, a heterogeneous fleet under an
oversubscribed budget, a custom objective — is a few calls on it:

    from repro.api import MinosSession

    session = MinosSession.from_config({
        "library": "results/reference_store",
        "devices": {"tpu-v5e": 6, "tpu-v5p": 2},
        "variability": {},
        "budget_fraction_of_nameplate": 0.75,
    })
    job = session.submit(stream, chips=256)     # -> JobHandle
    decision = job.run()                        # early, confidence-gated cap
    report = session.run()                      # SessionReport (JSON-able)

Everything the facade builds on is re-exported here, so application code
(examples, benchmarks, launchers) needs imports from ``repro.api`` (and
``repro.fleet`` for fleet-specific types) only — enforced for the migrated
entry points by ``tests/test_import_boundary.py``.

Deprecated entry points routing through this stack: the batch
``repro.telemetry.profile_once``/``profile_workload`` (use
``stream_profile_once``/``stream_profile_workload`` or ``session.submit``)
and ``repro.core.reference_store`` (use ``ReferenceLibrary``).
"""
from repro.api.registry import (ACTUATORS, OBJECTIVES, QUANTILES,
                                QuantilePolicy, Registry, register_actuator,
                                register_objective, register_quantile)
from repro.api.results import (SessionReport, from_dict, from_json, to_dict,
                               to_json)
from repro.api.compile_cache import enable_compilation_cache
from repro.api.session import JobHandle, MinosSession

# the engine underneath, re-exported so facade users need one import root
from repro.core.algorithm1 import (FreqSelection, ObjectivePolicy,
                                   profiling_savings, resolve_objective,
                                   select_optimal_freq)
from repro.core.classify import (FreqPoint, MinosClassifier, WorkloadProfile,
                                 count_classifier_calls)
from repro.discovery import (DiscoveryController, QuarantinePool,
                             ShadowEvaluator, stream_profiler,
                             truth_selection)
from repro.fleet.controller import FleetCapController, FleetEvent, FleetResult
from repro.fleet.inventory import (DeviceInstance, DeviceInventory,
                                   VariabilityModel)
from repro.fleet.mux import FleetChunk, FleetTelemetryMux
from repro.ft.fleetwatch import FleetStragglerAdapter
from repro.ft.heartbeat import StragglerMonitor
from repro.pipeline.batch import BatchProfileEngine, SlotBuilder
from repro.pipeline.builder import (PartialProfile, ProfileBuilder,
                                    stream_profile_once,
                                    stream_profile_workload)
from repro.pipeline.library import ReferenceLibrary, build_reference_library
from repro.pipeline.online import CapDecision, OnlineCapController
from repro.sched.dvfs import FrequencyActuator, SimActuator
from repro.sched.power_sched import (IncrementalPacker, JobPlan,
                                     PowerAwareScheduler, RepackStats,
                                     ScheduleResult)
from repro.store import (EventJournal, JournalRecord, NoStoreError,
                         SessionStore, SnapshotStore, StoreError,
                         store_report, windowed_report)
from repro.telemetry.kernel_stream import (Kernel, KernelStream, build_stream,
                                           micro_gemm, micro_idle_burst,
                                           micro_spmv_compute,
                                           micro_spmv_memory, micro_stencil,
                                           micro_vector_search)
from repro.telemetry.power_model import TPUPowerModel
from repro.telemetry.simulator import (SimTrace, TelemetryChunk, TraceMeta,
                                       simulate, stream_telemetry)
from repro.telemetry.workloads import (fleet_job_mix, holdout_streams,
                                       novel_streams, reference_streams)

__all__ = [
    # facade
    "MinosSession", "JobHandle", "SessionReport", "enable_compilation_cache",
    # registries / plugin policies
    "Registry", "OBJECTIVES", "ACTUATORS", "QUANTILES",
    "register_objective", "register_actuator", "register_quantile",
    "ObjectivePolicy", "QuantilePolicy", "resolve_objective",
    # result objects + codec
    "CapDecision", "JobPlan", "ScheduleResult", "FreqSelection",
    "IncrementalPacker", "RepackStats",
    "to_dict", "from_dict", "to_json", "from_json",
    # streaming pipeline
    "ProfileBuilder", "PartialProfile", "ReferenceLibrary",
    "build_reference_library", "OnlineCapController",
    "stream_profile_once", "stream_profile_workload",
    "BatchProfileEngine", "SlotBuilder",
    # classification core
    "MinosClassifier", "WorkloadProfile", "FreqPoint",
    "select_optimal_freq", "profiling_savings", "count_classifier_calls",
    # fleet
    "DeviceInstance", "DeviceInventory", "VariabilityModel",
    "FleetCapController", "FleetResult", "FleetChunk", "FleetTelemetryMux",
    # fault tolerance
    "FleetEvent", "FleetStragglerAdapter", "StragglerMonitor",
    # durable sessions (repro.store)
    "SessionStore", "EventJournal", "JournalRecord", "SnapshotStore",
    "NoStoreError", "StoreError", "store_report", "windowed_report",
    # online class discovery (repro.discovery)
    "DiscoveryController", "QuarantinePool", "ShadowEvaluator",
    "stream_profiler", "truth_selection",
    # actuation / scheduling
    "FrequencyActuator", "SimActuator", "PowerAwareScheduler",
    # telemetry + workload zoo
    "TPUPowerModel", "simulate", "stream_telemetry", "SimTrace",
    "TelemetryChunk", "TraceMeta", "Kernel", "KernelStream", "build_stream",
    "micro_gemm", "micro_idle_burst", "micro_spmv_compute",
    "micro_spmv_memory", "micro_stencil", "micro_vector_search",
    "reference_streams", "holdout_streams", "novel_streams", "fleet_job_mix",
]
