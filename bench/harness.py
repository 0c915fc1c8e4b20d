"""One benchmark cell: its system, its set-up and its measured window.

The system under test is ``FleetCapController``, built by its public
constructor from the configuration file; a configuration with a ``store``
section makes it a durable ``MinosSession`` over a fresh ``SessionStore``
instead.  Either way the controller is driven through the calls a fleet
front end makes: ``admit_many`` for arrivals, ``ingest_tick`` for each poll
of the telemetry wire (at most one chunk per job), ``finalize_job`` for a
stream that ended undecided, and ``retire`` for job ends.  A configuration
with a ``faults`` section adds ``fail_device`` and ``restore_device`` on
the schedule of ``bench/faults.py``, and ``restart_profile`` with a fresh
trace for every run a migration cut.  The wire is the benchmark's own
(``bench/traffic``).

Two loops, chosen by the traffic mix:

  * ``closed`` (replay): a fixed number of jobs stream at once, one chunk
    each per tick, as fast as the controller takes them; a job whose
    stream ends is retired and the next arrival takes its place.
  * ``open`` (steady): Poisson arrivals at a fixed rate, telemetry in real
    time, exponential lifetimes around a live population that set-up
    builds; every chunk, arrival and retire is due at a set time, and
    latencies run from that time.
"""
from __future__ import annotations

import heapq
import json
import os
import shutil
import time
from dataclasses import dataclass, field

import numpy as np

from bench.faults import FaultProcess
from bench.traffic import generator as gen

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
OUT = os.path.join(ROOT, ".bench_out")


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(workload: str) -> dict:
    """The cell's entry of ``BENCHMARK.json`` with its configuration,
    traffic mix and check settings, all found by name."""
    spec = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    return cell_spec(spec, cells[workload])


def cell_spec(spec: dict, cell: dict) -> dict:
    """``cell`` with its configuration, traffic mix and check settings."""
    configs = {c["name"]: c for c in spec["configs"]}
    return dict(spec=spec, cell=cell,
                config=load_json(ROOT, configs[cell["config"]]["file"]),
                mix=gen.load_mix(cell["traffic"]),
                check=load_json(BENCH, "checks", f"{cell['name']}.json"))


@dataclass
class Job:
    jid: str
    index: int                   # arrival index: picks its pool trace
    tele: gen.Telemetry
    chips: int
    watched: bool
    start: float = 0.0           # arrival time on the window clock (open)
    k: int = 0                   # next chunk
    decided: bool = False
    run: int = 0                 # profiling runs restarted (open loop: stale
                                 # chunks of an earlier run are skipped)


@dataclass
class Record:
    """What the window produced, for the metrics and the check."""
    window_s: float = 0.0
    decisions: int = 0
    decision_ms: list = field(default_factory=list)
    event_ms: list = field(default_factory=list)
    wire_late_ms: list = field(default_factory=list)
    events: int = 0
    attempted: int = 0
    failed: int = 0
    ticks: int = 0
    watched: list = field(default_factory=list)   # (job, its decision)
    repack_s: float = 0.0
    device_calls: int = 0
    compiles: int = 0
    failures: int = 0            # fail_device calls (faults)
    restarts: int = 0            # runs restarted after a migration (faults)
    unanswered: int = 0          # streams ended with a run the program
                                 # never took back (faults)
    paused_s: float = 0.0        # restart traces and fault checks, off the
                                 # window's clock
    journal_records: int = 0     # records the store journaled (store)
    snapshots: int = 0           # snapshots the store wrote (store)


def controller(system):
    """The ``FleetCapController`` that a fleet front end drives tick by tick:
    the system itself, or a durable session's own controller.  The
    program's ``MinosSession`` has no public accessor for it yet, so this
    one place reads the session's attribute until it has one."""
    if hasattr(system, "ingest_tick"):
        return system
    fleet = getattr(system, "fleet", None)
    return fleet if fleet is not None else system._fleet


class Cell:
    """Set-up and window of one cell for one seed."""

    clock = staticmethod(time.perf_counter)

    def __init__(self, spec: dict, seed: int):
        self.spec, self.seed = spec, int(seed)
        self.cfg, self.mix = spec["config"], spec["mix"]
        self.check = spec["check"]
        self.tele = self.cfg["telemetry"]
        self.dt = float(self.tele["sample_dt_s"])
        self.tick_s = int(self.tele["chunk_samples"]) * self.dt
        self.setup: dict[str, float] = {}
        self.rng = np.random.default_rng([self.seed, 1])
        self.spans = None            # bench.spans.Spans in a traced run
        self.faults = None           # FaultProcess with a `faults` section
        self.store = None            # SessionStore with a `store` section
        self.fault_classify_calls = 0
        self.failed_placements = 0
        self.snapshots = 0
        self.t0 = self.paused = 0.0

    # -- set-up -------------------------------------------------------
    def build_library(self):
        from repro.api import TPUPowerModel, build_reference_library
        lib = self.cfg["library"]
        return build_reference_library(TPUPowerModel(),
                                       freqs=tuple(lib["freqs"]),
                                       seed=int(lib["seed"]),
                                       target_duration=float(
                                           lib["profile_s"]),
                                       chunk_samples=int(
                                           lib["chunk_samples"]))

    def inventory(self):
        from repro.api import DeviceInventory, VariabilityModel
        v = self.cfg["variability"]
        return DeviceInventory.generate(
            dict(self.cfg["devices"]),
            VariabilityModel(sigma_perf=float(v["sigma_perf"]),
                             sigma_power=float(v["sigma_power"]),
                             max_z=float(v["max_z"])),
            seed=int(self.cfg["inventory_seed"]))

    def live_jobs(self) -> int:
        """The full cluster's concurrent jobs: its allocated chips over the
        mean job size."""
        c = self.cfg["cluster"]
        chips = c["nodes"] * c["chips_per_node"] * float(c["occupancy"])
        return int(round(chips / gen.mean_chips(self.cfg["job_mix"])))

    def make_traffic(self, inventory):
        """The pool of per-job traces and the arrival schedule."""
        pool_n = int(round(self.live_jobs() * float(self.mix["pool_share"])))
        jobs = gen.job_multiset(self.cfg["job_mix"], pool_n, self.rng)
        devices = [inventory[i % len(inventory)] for i in range(pool_n)]
        self.pool = gen.make_pool(jobs, devices, self.tele, self.seed)
        self.chips = [c for _, c in jobs]
        share = float(self.check["watch_share"])
        self.watch = self.rng.random(1 << 20) < share

    def new_job(self, index: int) -> Job:
        tele = self.pool[index % len(self.pool)]
        return Job(f"j{index:07d}", index, tele,
                   self.chips[index % len(self.chips)],
                   bool(self.watch[index % len(self.watch)]))

    def arrive(self, n: int) -> list[Job]:
        """The next ``n`` arrivals.  Where a pool trace's device is down the
        arrival takes the next trace instead: no job is admitted onto a
        failed device."""
        out = []
        while len(out) < n:
            job = self.new_job(self.next_index)
            self.next_index += 1
            if self.faults is None \
                    or job.tele.device.device_id not in self.faults.failed:
                out.append(job)
        return out

    def make_system(self):
        """``FleetCapController`` by its public constructor or, with a
        ``store`` section, a ``MinosSession`` given the same library,
        inventory, budget, objective, quantile and gates, over a fresh
        ``SessionStore`` under ``.bench_out/store/<cell>/``."""
        gates = self.cfg["gates"]
        common = dict(budget_w=self.budget_w,
                      objective=self.cfg["objective"],
                      min_confidence=float(gates["min_confidence"]),
                      min_fraction=float(gates["min_fraction"]),
                      min_spike_samples=int(gates["min_spike_samples"]),
                      inventory=self.inv)
        if "store" not in self.cfg:
            from repro.api import FleetCapController
            return FleetCapController(
                self.lib, provision_quantile=self.cfg["provision_quantile"],
                **common)
        from repro.api import MinosSession, SessionStore
        s = self.cfg["store"]
        self.store_path = os.path.join(OUT, "store",
                                       self.spec["cell"]["name"])
        shutil.rmtree(self.store_path, ignore_errors=True)
        self.store = SessionStore.create(
            self.store_path, fsync=bool(s["fsync"]),
            snapshot_every=int(s["snapshot_every"]),
            rotate_every=int(s["rotate_every"]))
        write = self.store.snapshots.write

        def counted(*args, **kwargs):
            self.snapshots += 1
            return write(*args, **kwargs)

        self.store.snapshots.write = counted
        return MinosSession(self.lib,
                            quantile=self.cfg["provision_quantile"],
                            store=self.store, **common)

    def build(self, counter=None):
        """Everything before the first due chunk; fills ``self.setup``."""
        t = self.clock()
        self.lib = self.build_library()
        self.setup["library_s"] = self.clock() - t
        t = self.clock()
        self.inv = self.inventory()
        self.make_traffic(self.inv)
        self.setup["telemetry_s"] = self.clock() - t
        t = self.clock()
        n_live = self.live_jobs()
        if "faults" in self.cfg:
            self.faults = FaultProcess(self.cfg["faults"],
                                       [d.device_id for d in self.inv],
                                       np.random.default_rng([self.seed, 4]))
        self.next_index = 0
        first = self.arrive(n_live)
        self.budget_w = float(self.cfg["budget_fraction"]) * sum(
            j.chips * j.tele.device.nameplate_w for j in first)
        self.system = self.make_system()
        self.fleet = controller(self.system)
        self.warm_shapes = self.fleet.engine.warmup(
            n_live, int(self.tele["chunk_samples"]))
        self.setup["warmup_s"] = self.clock() - t
        self.warm_compiles = counter.compiles if counter else 0
        t = self.clock()
        self.jobs: dict[str, Job] = {}
        if self.faults is not None:
            from repro.core.classify import count_classifier_calls
            self.clf_calls = count_classifier_calls(self.fleet.clf)
            for dev in self.faults.initial:       # down before any arrival
                self.fault(dev, "fail")
        self.admit(first)
        if self.mix["loop"] == "open":
            self.fill(first)
        else:
            self.stagger(first)
        self.setup["population_s"] = self.clock() - t

    def admit(self, jobs) -> None:
        self.fleet.admit_many(
            dict(device=j.tele.device, meta=j.tele.meta(), chips=j.chips,
                 job_id=j.jid) for j in jobs)
        for j in jobs:
            self.jobs[j.jid] = j

    def fill(self, jobs) -> None:
        """Stream the live population to its decisions before the window:
        decided jobs stop streaming, and the rest decide at stream end."""
        from repro.api import FleetChunk
        live = list(jobs)
        while live:
            self.fleet.ingest_tick([FleetChunk(j.jid, j.tele.device.device_id,
                                               0.0, j.tele.chunks[j.k])
                                    for j in live])
            nxt = []
            for j in live:
                j.k += 1
                if self.fleet.jobs[j.jid].decision is not None:
                    j.decided = True
                elif j.k < len(j.tele.chunks):
                    nxt.append(j)
            live = nxt
        self.fleet.finalize()
        for j in jobs:
            j.decided = True
            j.k = len(j.tele.chunks)

    def stagger(self, jobs) -> None:
        """Bring the streaming jobs to a steady mix of ages before the
        window: each is fed a seed-drawn number of its chunks, uniform
        over its stream, so streams end, and arrivals replace them, at an
        even pace from the first tick on."""
        from repro.api import FleetChunk
        ages = [int(self.rng.integers(len(j.tele.chunks))) for j in jobs]
        while True:
            due = [j for j, a in zip(jobs, ages) if j.k < a]
            if not due:
                break
            self.fleet.ingest_tick([FleetChunk(j.jid, j.tele.device.device_id,
                                               0.0, j.tele.chunks[j.k])
                                    for j in due if not j.decided])
            for j in due:
                j.k += 1
                if not j.decided \
                        and self.fleet.jobs[j.jid].decision is not None:
                    j.decided = True

    # -- the window ---------------------------------------------------
    def span(self, name):
        return self.spans.span(name) if self.spans else _NULL

    def win(self) -> float:
        """Seconds of the window so far: its clock stops while restart
        traces are built and fault calls are checked."""
        return self.clock() - self.t0 - self.paused

    def note_decision(self, rec: Record, job: Job, due: float,
                      done: float) -> None:
        job.decided = True
        rec.decisions += 1
        rec.attempted += 1
        late = done - due
        rec.decision_ms.append(1e3 * late)
        if self.mix["loop"] == "open" and late > self.profile_s(job):
            rec.failed += 1
        if job.watched:
            rec.watched.append((job, self.fleet.jobs[job.jid].decision))

    def profile_s(self, job: Job) -> float:
        return job.tele.ev.n_samples * self.dt

    def journal_seq(self) -> int:
        return self.store.journal.last_seq if self.store is not None else 0

    def run(self, seconds: float, counter=None) -> Record:
        rec = Record()
        eng = self.fleet.engine
        calls0, repack0 = eng.device_calls, self.fleet.repack_s
        seq0, snaps0 = self.journal_seq(), self.snapshots
        c0 = counter.compiles if counter else 0
        if self.mix["loop"] == "closed":
            self.replay(seconds, rec)
        else:
            self.steady(seconds, rec)
        rec.device_calls = eng.device_calls - calls0
        rec.repack_s = self.fleet.repack_s - repack0
        rec.compiles = (counter.compiles if counter else 0) - c0
        rec.paused_s = self.paused
        rec.journal_records = self.journal_seq() - seq0
        rec.snapshots = self.snapshots - snaps0
        return rec

    def fault(self, dev: str, kind: str) -> list:
        """One ``fail_device`` or ``restore_device`` call, counting the
        classifier calls made inside it and, off the window's clock, the
        plans it leaves on a device that is down."""
        calls = self.clf_calls["n"]
        with self.span("fault"):
            call = (self.fleet.fail_device if kind == "fail"
                    else self.fleet.restore_device)
            events = call(dev)
        self.fault_classify_calls += self.clf_calls["n"] - calls
        t = self.clock()
        self.failed_placements += self.plans_on_down()
        self.paused += self.clock() - t
        return events

    def plans_on_down(self) -> int:
        """Jobs whose plan, placed or deferred, binds them to a device that
        is down."""
        down = self.faults.failed
        return sum(fj.plan is not None and fj.device.device_id in down
                   for fj in self.fleet.jobs.values())

    def inject(self, now: float, rec: Record) -> list[Job]:
        """Every failure and repair due by ``now`` (telemetry clock), and
        a restart of every run they cut; returns the restarted jobs."""
        restarted = []
        for due, kind, dev in self.faults.due(now):
            events = self.fault(dev, kind)
            rec.failures += kind == "fail"
            if self.mix["loop"] == "open":
                rec.event_ms.append(1e3 * (self.win() - due))
            else:
                rec.events += 1
            for ev in events:
                if ev.kind == "strand":
                    raise SystemExit(f"bench: job {ev.job_id} stranded, no "
                                     f"healthy device left; the faults "
                                     f"section takes down too many")
                if ev.kind == "migrate" and ev.detail == "reprofile":
                    job = self.jobs[ev.job_id]
                    self.restart(job, rec)
                    if job not in restarted:     # cut twice: one new run
                        restarted.append(job)
        return restarted

    def restart(self, job: Job, rec: Record) -> None:
        """A run that a migration cut starts again on the job's new device:
        a fresh trace of its own workload with its own noise, streamed from
        chunk 0.  Building the trace is the wire's work, not the
        program's, so the window's clock stops meanwhile."""
        dev = self.fleet.jobs[job.jid].device
        t = self.clock()
        job.tele = gen.make_pool([(job.tele.stream, job.chips)], [dev],
                                 self.tele, self.seed,
                                 noise=[job.index % len(self.pool)])[0]
        self.paused += self.clock() - t
        job.k = 0
        job.run += 1
        self.fleet.restart_profile(job.jid, job.tele.meta())
        rec.restarts += 1

    def end_stream(self, rec: Record, job: Job, due: float, now) -> None:
        """A stream ended undecided: decide it from its whole trace.  A job
        whose run the program never took back after a migration gets no
        answer: a failed attempt, for the check."""
        if self.faults is not None \
                and self.fleet.jobs[job.jid].needs_reprofile:
            rec.unanswered += 1
            rec.attempted += 1
            rec.failed += 1
            return
        with self.span("finalize"):
            self.fleet.finalize_job(job.jid)
        self.note_decision(rec, job, due, now())

    def replay(self, seconds: float, rec: Record) -> None:
        """The closed loop.  With faults, each tick moves the telemetry
        clock on by one chunk, so failures come at a fixed rate per tick
        and per decision however fast the program runs."""
        from repro.api import FleetChunk
        fleet = self.fleet
        live = list(self.jobs.values())
        tele_t = 0.0
        self.t0, self.paused = self.clock(), 0.0
        while self.win() < seconds:
            if self.faults is not None:
                self.inject(tele_t, rec)
                tele_t += self.tick_s
            with self.span("wire"):
                batch = [FleetChunk(j.jid, j.tele.device.device_id,
                                    float(j.tele.chunk_end[j.k]) * self.dt,
                                    j.tele.chunks[j.k]) for j in live]
            due = self.clock()
            fleet.ingest_tick(batch)
            done = self.clock()
            rec.ticks += 1
            ended, keep = [], []
            for j in live:
                j.k += 1
                if not j.decided and fleet.jobs[j.jid].decision is not None:
                    self.note_decision(rec, j, due, done)
                (ended if j.k == len(j.tele.chunks) else keep).append(j)
            for j in ended:
                if not j.decided:
                    self.end_stream(rec, j, due, self.clock)
                with self.span("retire"):
                    fleet.retire(j.jid)
                del self.jobs[j.jid]
            new = self.arrive(len(ended))
            if new:
                with self.span("admit"):
                    self.admit(new)
            live = keep + new
        rec.window_s = self.win()

    def steady(self, seconds: float, rec: Record) -> None:
        """The open loop; failures and repairs are due on the window
        clock."""
        from repro.api import FleetChunk
        fleet = self.fleet
        if self.mix["rate_per_s"] is None:
            raise SystemExit("bench: the open-loop mix has no rate yet; set "
                             "it to 4/5 of the knee that a sweep on the "
                             "chip finds")
        rate = float(self.mix["rate_per_s"])
        n_live = self.live_jobs()
        mean_life = n_live / rate
        # the window's arrivals and every lifetime are fixed sets of
        # exponential quantiles: the seed orders them, and the arrivals
        # span about the window for every seed
        n_arr = max(1, int(round(rate * seconds)))
        arrivals = np.cumsum(gen.exponential_set(n_arr, 1.0 / rate,
                                                 self.rng))
        lives = gen.exponential_set(n_arr, mean_life, self.rng)
        retires: list = []
        for life, j in zip(gen.exponential_set(len(self.jobs), mean_life,
                                               self.rng),
                           list(self.jobs.values())):
            heapq.heappush(retires, (float(life), j.jid))
        chunks: list = []            # (due, job, run): a restart's stale
        nxt = 0                      # chunks are skipped by their run
        self.t0, self.paused = self.clock(), 0.0
        while True:
            now = self.win()
            if now >= seconds:
                break
            busy = False
            if self.faults is not None and self.faults.next_due() <= now:
                for j in self.inject(now, rec):
                    j.start = self.win()
                    heapq.heappush(chunks, (j.start + j.tele.chunk_end[0]
                                            * self.dt, j.jid, j.run))
                busy = True
            if nxt < n_arr and arrivals[nxt] <= now:
                stop = int(np.searchsorted(arrivals, now, side="right"))
                new = self.arrive(stop - nxt)
                for j, a in zip(new, arrivals[nxt:stop]):
                    j.start = float(a)
                with self.span("admit"):
                    self.admit(new)
                done = self.win()
                for j, a in zip(new, arrivals[nxt:stop]):
                    rec.event_ms.append(1e3 * (done - a))
                    heapq.heappush(chunks, (a + j.tele.chunk_end[0] * self.dt,
                                            j.jid, j.run))
                    heapq.heappush(retires, (a + float(lives[nxt]), j.jid))
                    nxt += 1
                busy = True
            if chunks and chunks[0][0] <= now:
                due_jobs = []
                while chunks and chunks[0][0] <= now:
                    due, jid, run = heapq.heappop(chunks)
                    j = self.jobs.get(jid)
                    if j is not None and run == j.run:
                        due_jobs.append((due, j))
                with self.span("wire"):
                    batch = [FleetChunk(j.jid, j.tele.device.device_id, due,
                                        j.tele.chunks[j.k])
                             for due, j in due_jobs]
                handed = self.win()
                rec.wire_late_ms.extend(1e3 * (handed - due)
                                        for due, _ in due_jobs)
                fleet.ingest_tick(batch)
                done = self.win()
                rec.ticks += 1
                for due, j in due_jobs:
                    j.k += 1
                    if not j.decided \
                            and fleet.jobs[j.jid].decision is not None:
                        self.note_decision(rec, j, due, done)
                    if j.k < len(j.tele.chunks):
                        heapq.heappush(chunks, (j.start + j.tele.chunk_end[
                            j.k] * self.dt, j.jid, j.run))
                    elif not j.decided:
                        self.end_stream(rec, j, due, self.win)
                busy = True
            while retires and retires[0][0] <= now:
                due, jid = heapq.heappop(retires)
                with self.span("retire"):
                    fleet.retire(jid)
                rec.event_ms.append(1e3 * (self.win() - due))
                del self.jobs[jid]
                busy = True
            if not busy:
                wake = min([seconds]
                           + ([arrivals[nxt]] if nxt < n_arr else [])
                           + ([chunks[0][0]] if chunks else [])
                           + ([retires[0][0]] if retires else [])
                           + ([self.faults.next_due()] if self.faults
                              else []))
                with self.span("wait"):
                    time.sleep(max(0.0, wake - self.win()))
        rec.window_s = self.win()
        rec.events = len(rec.event_ms)


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _Null()
