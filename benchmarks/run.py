"""Benchmark harness — one entry per paper table/figure + the roofline table.

Prints ``name,us_per_call,derived`` CSV lines (see each bench module for the
JSON artifacts written under results/).  Each bench runs in a process of its
own, started by this parent, which never imports JAX: a chip belongs to one
process at a time, and ``bench_recovery`` starts a crash child that needs
it.  Run from the repository root::

    PYTHONPATH=src:. python benchmarks/run.py
"""
from __future__ import annotations

import argparse
import importlib
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCHES = ("bench_classification", "bench_cdf", "bench_freq_scaling",
           "bench_case_study", "bench_holdout", "bench_baseline_cmp",
           "bench_binsize", "bench_savings", "bench_kernels",
           "bench_roofline", "bench_profiling_throughput",
           "bench_online_cap", "bench_fleet", "bench_fleet_scale",
           "bench_chaos", "bench_recovery", "bench_discovery")


def run_one(name: str) -> None:
    """One bench in this process, with the persistent compile cache on."""
    from repro.api import enable_compilation_cache
    enable_compilation_cache()
    importlib.import_module(f"benchmarks.{name}").run()


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--bench", choices=BENCHES,
                    help="run one bench in this process")
    args = ap.parse_args()
    if args.bench:
        run_one(args.bench)
        return
    print("name,us_per_call,derived", flush=True)
    failures = [name for name in BENCHES if subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--bench", name],
        cwd=ROOT).returncode]
    if failures:
        print(f"# FAILED: {failures}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
