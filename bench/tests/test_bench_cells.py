"""Every cell end to end on the CPU at a tiny size, with the device
histogram interpreted; and the refusals without a TPU."""
import os
import shutil
import subprocess
import sys

import pytest

import bench_rehearsal as R


@pytest.mark.parametrize("workload", R.CELLS)
def test_cell_runs_and_is_correct(workload, monkeypatch, capsys):
    R.pretend_chip(monkeypatch)
    seconds = 1.5 if workload.endswith("replay") else 3.0
    out = R.result(capsys, R.args(workload, seed=2**31 + 11,
                                  seconds=seconds))
    assert out["correct"] is True
    assert list(out)[-1] == "checks"
    assert out["checks"]["device_calls"]["value"] > 0
    assert out["checks"]["window_compiles"]["value"] == 0
    assert out["checks"]["hist_gap"]["value"] == 0
    assert out["checks"]["decision_gap"]["value"] == 0
    assert out["attempted"] > 0
    names = set(out["metrics"])
    if workload.endswith("replay"):
        assert names == {"decisions_per_s", "setup_s"}
    else:
        assert names == {"decision_p95_ms", "event_p95_ms", "setup_s"}
    assert out["device"]["count"] >= 1


def test_traced_run_reports_the_per_layer_metrics(monkeypatch, capsys):
    R.pretend_chip(monkeypatch)
    out = R.result(capsys, R.args("hpc.replay", seed=5, trace=1))
    assert out["correct"] is True
    got = set(out["metrics"])
    assert {"engine_ms_per_job.replay", "classify_ms_per_job.replay",
            "pack_ms_per_job.replay", "tick_self_ms_per_job.replay"} <= got
    assert "window_s" in out["device"] and "busy_s" in out["device"]
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "hpc.replay",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_refuses_a_host_without_a_tpu():
    r = _run(R.ROOT)
    assert r.returncode != 0
    assert "no TPU found" in r.stderr
    assert '"correct"' not in r.stdout


def test_refuses_a_checkout_of_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(R.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(R.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "hpc.replay",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert '"correct"' not in r.stdout
