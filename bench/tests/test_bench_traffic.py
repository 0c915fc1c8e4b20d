"""The benchmark's wire: seeded schedules, per-job telemetry and the copy
of the simulator's generator."""
import numpy as np
import pytest

import bench_rehearsal as R  # noqa: F401  (puts the checkout on sys.path)
from bench import harness
from bench.traffic import generator as gen

MIX = dict(weights={"decode": 0, "prefill": 1, "long": 1, "hpc": 1,
                    "train": 2},
           exclude=["llama-3.2-vision-11b"],
           chips={"1": 55, "2": 10, "4": 10, "8": 13, "16": 6, "32": 4,
                  "64": 2})


def schedule(seed):
    rng = np.random.default_rng([seed, 1])
    jobs = gen.job_multiset(MIX, 500, rng)
    gaps = gen.exponential_set(100, 0.01, rng)
    return [(s.name, c) for s, c in jobs], gaps


def test_same_seed_gives_the_same_schedule():
    a, b, c = schedule(2**31 + 5), schedule(2**31 + 5), schedule(6)
    assert a[0] == b[0] and np.array_equal(a[1], b[1])
    assert a[0] != c[0]


def test_seeds_share_sizes_and_change_only_the_order():
    (ja, ga), (jb, gb) = schedule(1), schedule(2)
    assert sorted(ja) != ja or sorted(jb) != jb
    assert sorted(s for s, _ in ja) == sorted(s for s, _ in jb)
    assert sorted(c for _, c in ja) == sorted(c for _, c in jb)
    assert np.array_equal(np.sort(ga), np.sort(gb))
    assert not any(s.startswith("llama-3.2-vision") for s, _ in ja)


def test_mix_weights_hold_exactly():
    jobs = gen.job_multiset(MIX, 1000, np.random.default_rng(0))
    kinds = [gen.stream_kind(s.name) for s, _ in jobs]
    streams = gen.zoo_streams(MIX)
    want = {}
    for s, w in streams:
        want[gen.stream_kind(s.name)] = want.get(gen.stream_kind(s.name),
                                                 0) + w
    total = sum(want.values())
    for kind, w in want.items():
        assert abs(kinds.count(kind) - 1000 * w / total) <= len(streams)
    chips = [c for _, c in jobs]
    for size, w in MIX["chips"].items():
        assert abs(chips.count(int(size)) - 1000 * w / 100) < 1


def test_live_jobs_fill_the_cluster():
    cell = harness.Cell(R.load_cell("hpc.replay"), seed=1)
    assert gen.mean_chips(MIX) == pytest.approx(5.71)
    assert cell.live_jobs() == round(802 * 8 / 5.71) == 1124


def test_generator_copy_matches_the_simulator():
    from repro.api import TPUPowerModel, micro_idle_burst, stream_telemetry
    model = TPUPowerModel()
    stream = micro_idle_burst()
    meta, chunks = stream_telemetry(stream, 0.8, model, seed=9,
                                    target_duration=0.4)
    want = np.concatenate([c.energy_j for c in chunks])
    ev = gen.event_trace(stream, 0.8, model, 1e-3, 0.4)
    got = gen.energy_counter(ev, 0.03, 9)[1:]
    assert ev.n_samples == meta.n_samples
    assert np.array_equal(got, want)


@pytest.mark.parametrize("workload", R.CELLS)
def test_every_job_gets_its_own_telemetry_and_meta(workload):
    cell = harness.Cell(R.small_spec(workload, nodes=12), seed=3)
    inv = cell.inventory()
    cell.make_traffic(inv)
    jobs = [cell.new_job(i) for i in range(40)]
    metas = [j.tele.meta() for j in jobs]
    assert len({id(m) for m in metas}) == len(metas)
    live = jobs[:16]
    assert len({id(j.tele.energy_ctr) for j in live}) == 16
    first = [j.tele.energy_ctr[1:50] for j in live]
    assert all(not np.array_equal(a, b)
               for i, a in enumerate(first) for b in first[i + 1:])
