"""The chip entry points on the CPU: ``chip_smoke.py`` at a tiny size (its
drive matches the plain references with the device histogram path forced on
and interpreted; its device check refuses the CPU), the persistent compile
cache, and the bench harness, whose parent never holds the chip."""
import os
import subprocess
import sys

import pytest

from repro.pipeline.batch import BatchProfileEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402


@pytest.fixture
def device_engine(monkeypatch):
    """Every engine the drive builds counts its histograms through the
    Pallas kernel (interpret mode here), as on the chip."""
    init = BatchProfileEngine.__init__

    def pallas_init(self, *args, **kwargs):
        init(self, *args, **{**kwargs, "backend": "pallas"})
    monkeypatch.setattr(BatchProfileEngine, "__init__", pallas_init)


def test_smoke_drive_matches_the_references(device_engine, tmp_path):
    n_jobs = 60
    out = chip_smoke.drive(n_jobs=n_jobs,
                           fleet={"tpu-v5e": 2, "tpu-v5p": 1, "tpu-v6e": 1},
                           store=str(tmp_path / "store"),
                           library_duration=0.5)
    assert chip_smoke.failures(out, n_jobs) == []
    assert out["decisions"]["decided"] == n_jobs
    assert out["decisions"]["placed"] + out["decisions"]["deferred"] \
        == n_jobs
    assert out["drive"]["device_calls"] > 0
    assert out["drive"]["device_shapes"] == [(256, 256)]
    assert out["warmup"]["shapes"] == 1
    assert out["reference"]["cap_mismatches"] == 0
    assert out["reference"]["placement_mismatches"] == 0
    assert out["budget"]["violations"] == 0
    assert out["library"]["profiles"] == 28


def test_failures_names_every_broken_expectation():
    out = {"decisions": {"decided": 9}, "drive": {"device_calls": 0,
                                                  "compiles": 2},
           "reference": {"cap_mismatches": 1, "placement_mismatches": 3},
           "budget": {"violations": 4}}
    bad = chip_smoke.failures(out, 10)
    assert len(bad) == 6
    assert any("no device histogram call" in b for b in bad)


def test_device_check_refuses_the_cpu():
    with pytest.raises(SystemExit, match="no TPU found"):
        chip_smoke.require_tpu()


def test_script_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "no TPU found" in r.stderr
    assert '"ok"' not in r.stdout


def test_compile_cache_follows_the_environment(tmp_path):
    """With ``JAX_COMPILATION_CACHE_DIR`` set, the engine's programs land
    there and the helper names that directory."""
    code = ("from repro.api import BatchProfileEngine, "
            "enable_compilation_cache\n"
            "print(enable_compilation_cache())\n"
            "BatchProfileEngine(backend='pallas').warmup(1)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.split() == [str(tmp_path)]
    assert any(f.startswith("jit_spike_hist_packed")
               for f in os.listdir(tmp_path))


def test_bench_harness_parent_never_imports_jax():
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [os.path.join(REPO, "src"), REPO]))
    r = subprocess.run(
        [sys.executable, "-c",
         "import sys, benchmarks.run; print('jax' in sys.modules)"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip() == "False"
