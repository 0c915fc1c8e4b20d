"""One benchmark cell: its system, its set-up and its measured window.

The system under test is ``FleetCapController``, built by its public
constructor from the configuration file, and driven through the calls a
fleet front end makes: ``admit_many`` for arrivals, ``ingest_tick`` for each
poll of the telemetry wire (at most one chunk per job), ``finalize_job``
for a stream that ended undecided, and ``retire`` for job ends.  The wire
is the benchmark's own (``bench/traffic``).

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
import time
from dataclasses import dataclass, field

import numpy as np

from bench.traffic import generator as gen

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


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


class Cell:
    """Set-up and window of one cell for one seed."""

    clock = staticmethod(time.perf_counter)

    def __init__(self, spec: dict, seed: int):
        self.spec, self.seed = spec, int(seed)
        self.cfg, self.mix = spec["config"], spec["mix"]
        self.check = spec["check"]
        self.tele = self.cfg["telemetry"]
        self.dt = float(self.tele["sample_dt_s"])
        self.setup: dict[str, float] = {}
        self.rng = np.random.default_rng([self.seed, 1])
        self.spans = None            # bench.spans.Spans in a traced run

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
        from repro.api import FleetCapController
        n_live = self.live_jobs()
        first = [self.new_job(i) for i in range(n_live)]
        self.budget_w = float(self.cfg["budget_fraction"]) * sum(
            j.chips * j.tele.device.nameplate_w for j in first)
        gates = self.cfg["gates"]
        self.fleet = FleetCapController(
            self.lib, budget_w=self.budget_w,
            objective=self.cfg["objective"],
            provision_quantile=self.cfg["provision_quantile"],
            min_confidence=float(gates["min_confidence"]),
            min_fraction=float(gates["min_fraction"]),
            min_spike_samples=int(gates["min_spike_samples"]),
            inventory=self.inv)
        self.warm_shapes = self.fleet.engine.warmup(
            n_live, int(self.tele["chunk_samples"]))
        self.setup["warmup_s"] = self.clock() - t
        self.warm_compiles = counter.compiles if counter else 0
        t = self.clock()
        self.jobs: dict[str, Job] = {}
        self.admit(first)
        self.next_index = n_live
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

    def run(self, seconds: float, counter=None) -> Record:
        rec = Record()
        eng = self.fleet.engine
        calls0, repack0 = eng.device_calls, self.fleet.repack_s
        c0 = counter.compiles if counter else 0
        if self.mix["loop"] == "closed":
            self.replay(seconds, rec)
        else:
            self.steady(seconds, rec)
        rec.device_calls = eng.device_calls - calls0
        rec.repack_s = self.fleet.repack_s - repack0
        rec.compiles = (counter.compiles if counter else 0) - c0
        return rec

    def end_stream(self, rec: Record, job: Job, due: float) -> None:
        """A stream ended undecided: decide it from its whole trace."""
        with self.span("finalize"):
            self.fleet.finalize_job(job.jid)
        self.note_decision(rec, job, due, self.clock())

    def replay(self, seconds: float, rec: Record) -> None:
        from repro.api import FleetChunk
        fleet = self.fleet
        live = list(self.jobs.values())
        t0 = self.clock()
        while self.clock() - t0 < seconds:
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
                    self.end_stream(rec, j, due)
                with self.span("retire"):
                    fleet.retire(j.jid)
                del self.jobs[j.jid]
            new = [self.new_job(self.next_index + i)
                   for i in range(len(ended))]
            self.next_index += len(new)
            if new:
                with self.span("admit"):
                    self.admit(new)
            live = keep + new
        rec.window_s = self.clock() - t0

    def steady(self, seconds: float, rec: Record) -> None:
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
        chunks: list = []
        nxt = 0
        t0 = self.clock()
        while True:
            now = self.clock() - t0
            if now >= seconds:
                break
            busy = False
            if nxt < n_arr and arrivals[nxt] <= now:
                stop = int(np.searchsorted(arrivals, now, side="right"))
                new = [self.new_job(self.next_index + i)
                       for i in range(stop - nxt)]
                for j, a in zip(new, arrivals[nxt:stop]):
                    j.start = float(a)
                self.next_index += len(new)
                with self.span("admit"):
                    self.admit(new)
                done = self.clock() - t0
                for j, a in zip(new, arrivals[nxt:stop]):
                    rec.event_ms.append(1e3 * (done - a))
                    heapq.heappush(chunks, (a + j.tele.chunk_end[0] * self.dt,
                                            j.jid))
                    heapq.heappush(retires, (a + float(lives[nxt]), j.jid))
                    nxt += 1
                busy = True
            if chunks and chunks[0][0] <= now:
                due_jobs = []
                while chunks and chunks[0][0] <= now:
                    due, jid = heapq.heappop(chunks)
                    j = self.jobs.get(jid)
                    if j is not None:
                        due_jobs.append((due, j))
                with self.span("wire"):
                    batch = [FleetChunk(j.jid, j.tele.device.device_id, due,
                                        j.tele.chunks[j.k])
                             for due, j in due_jobs]
                handed = self.clock() - t0
                rec.wire_late_ms.extend(1e3 * (handed - due)
                                        for due, _ in due_jobs)
                fleet.ingest_tick(batch)
                done = self.clock() - t0
                rec.ticks += 1
                for due, j in due_jobs:
                    j.k += 1
                    if not j.decided \
                            and fleet.jobs[j.jid].decision is not None:
                        self.note_decision(rec, j, due + t0, done + t0)
                    if j.k < len(j.tele.chunks):
                        heapq.heappush(chunks, (j.start + j.tele.chunk_end[
                            j.k] * self.dt, j.jid))
                    elif not j.decided:
                        self.end_stream(rec, j, due + t0)
                busy = True
            while retires and retires[0][0] <= now:
                due, jid = heapq.heappop(retires)
                with self.span("retire"):
                    fleet.retire(jid)
                rec.event_ms.append(1e3 * (self.clock() - t0 - due))
                del self.jobs[jid]
                busy = True
            if not busy:
                wake = min([seconds]
                           + ([arrivals[nxt]] if nxt < n_arr else [])
                           + ([chunks[0][0]] if chunks else [])
                           + ([retires[0][0]] if retires else []))
                with self.span("wait"):
                    time.sleep(max(0.0, wake - (self.clock() - t0)))
        rec.window_s = self.clock() - t0
        rec.events = len(rec.event_ms)


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _Null()
