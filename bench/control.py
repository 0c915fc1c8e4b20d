#!/usr/bin/env python3
"""Readings that the ``correct`` limits are set from, over many seeds.

    python3 bench/control.py --workload hpc.replay --seconds 5 \
        --seeds 11 12 13

For each seed: set-up and a window at the cell's own load, then
``check.run_checks`` twice on what the window produced: for the program
(the lower readings), and for the control, the plain reference computed in
float32 put in the program's place on the same sampled jobs (the upper
readings; its ``correct`` has to come out false).  Prints one JSON line
per seed.  Runs on a TPU only; the benchmark's own runs never run it.
"""
import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def readings(cell, rec) -> dict:
    """The program's and the control's ``correct`` and compared numbers."""
    import numpy as np
    from bench import check
    limits = cell.check["limits"]
    out = {}
    for side, dtype in (("program", None), ("control", np.float32)):
        ok, checks, _ = check.run_checks(cell, rec, limits, dtype)
        out[side] = dict(correct=ok,
                         **{k: c["value"] for k, c in checks.items()})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from bench import harness
    from bench.device import CompileCounter
    from bench.run import require_chips
    from repro.api import enable_compilation_cache
    spec = harness.load_cell(args.workload)
    require_chips(int(spec["cell"]["chips"]))
    enable_compilation_cache()
    counter = CompileCounter()
    for seed in args.seeds:
        cell = harness.Cell(spec, seed)
        cell.build(counter)
        rec = cell.run(args.seconds, counter)
        out = dict(workload=args.workload, seed=seed,
                   decisions=rec.decisions, **readings(cell, rec))
        print(json.dumps(out), flush=True)
        del cell, rec
    return 0


if __name__ == "__main__":
    sys.exit(main())
