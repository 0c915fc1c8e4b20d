#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result.

    python3 bench/run.py --workload hpc.replay --seed 1 --seconds 20 \
        --trace 0

Builds the cell's system and traffic from the seed (set-up, timed as
``setup_s``), measures for ``--seconds`` (with ``--trace 1`` under the
profiler and the program's own recorder, ``repro.obs``), checks what the
window produced against the plain reference, and prints one JSON object
as the last line of standard output: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last the compared numbers beside their limits under
``checks``.  Exits non-zero, printing no result, where JAX finds no TPU
or fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
OUT = os.path.join(ROOT, ".bench_out")


def p95(values) -> float | None:
    """The 95th percentile, as ``statistics.quantiles`` (exclusive) gives
    it; ``None`` under 20 values."""
    if len(values) < 20:
        return None
    return float(statistics.quantiles(values, n=20)[-1])


def end_to_end(rec, setup_s: float) -> dict:
    """Every end-to-end number the window gives; the cell reports those
    that ``BENCHMARK.json`` lists for it."""
    out = {"setup_s": setup_s}
    if rec.window_s > 0 and rec.ticks:
        out["decisions_per_s"] = rec.decisions / rec.window_s
    d, e = p95(rec.decision_ms), p95(rec.event_ms)
    if d is not None:
        out["decision_p95_ms"] = d
    if e is not None:
        out["event_p95_ms"] = e
    return out


def listed(metrics: list, workload: str) -> list:
    return [m for m in metrics
            if "workloads" not in m or workload in m["workloads"]]


def read_layer(name: str, layer: dict):
    """Run the reader ``bench/metrics/<name>.py`` on the traced record."""
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(layer)


def require_chips(n: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU found (JAX platform "
                         f"{devices[0].platform!r}); the benchmark runs "
                         f"only on a TPU")
    if len(devices) < n:
        raise SystemExit(f"bench: the cell needs {n} chips, JAX found "
                         f"{len(devices)}")
    return devices


def layer_record(rec, spans, reduced, peaks, program, idle_by_span) -> dict:
    """What the readers of the per-layer metrics read: the window's
    record, the benchmark's spans, the reduced device trace, the program's
    own spans and counters (``program``) and the device-idle time by
    innermost program span (``idle_by_span``)."""
    return dict(window_s=rec.window_s, decisions=rec.decisions,
                events=rec.events, spans=dict(spans.total),
                pack_in_tick_s=spans.pack_in_tick, repack_s=rec.repack_s,
                wire_late_ms=list(rec.wire_late_ms),
                hist_calls=list(spans.hist_calls), trace=reduced,
                peaks=peaks, program=program, idle_by_span=idle_by_span)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from bench import check, harness, program_trace, spans as spans_mod
    from bench import tracing
    spec = harness.load_cell(args.workload)
    devices = require_chips(int(spec["cell"]["chips"]))
    import jax
    from bench.device import CompileCounter, memory_peak_bytes, peaks_for
    from repro.api import enable_compilation_cache
    dev = devices[0]
    peaks = peaks_for(dev.device_kind)
    print(f"device: platform={dev.platform} kind={dev.device_kind!r} "
          f"count={len(devices)} jax={jax.__version__}", flush=True)
    print(f"compile cache: {enable_compilation_cache()}", flush=True)
    counter = CompileCounter()
    t_import = time.perf_counter() - T_START
    cell = harness.Cell(spec, args.seed)
    cell.build(counter)
    spans = None
    if args.trace:
        spans = spans_mod.Spans()
        spans.install(cell.fleet)
        cell.spans = spans
        trace_dir = os.path.join(OUT, "trace", args.workload)
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
        tracing.start(trace_dir)
    setup_s = time.perf_counter() - T_START
    if spans:
        recorder = program_trace.start()
        with spans.span("window"):
            rec = cell.run(args.seconds, counter)
        program = program_trace.finish(recorder)
    else:
        rec = cell.run(args.seconds, counter)
    reduced = idle_by_span = None
    if args.trace:
        tracing.stop()
        profile = tracing.load(trace_dir)
        reduced = tracing.reduce(profile)
        if program is not None:
            idle_by_span = program_trace.attribute(profile)
    mem = memory_peak_bytes(devices)
    print(f"set-up: import {t_import!r} s, library "
          f"{cell.setup['library_s']!r} s, telemetry "
          f"{cell.setup['telemetry_s']!r} s, warm-up "
          f"{cell.setup['warmup_s']!r} s ({cell.warm_shapes} histogram "
          f"shapes), population {cell.setup['population_s']!r} s; "
          f"setup_s {setup_s!r}", flush=True)
    print(f"compiles: {counter.compiles} in all "
          f"({counter.cache_hits} from the persistent cache), "
          f"{cell.warm_compiles} in warm-up, {rec.compiles} in the window",
          flush=True)
    third = len(rec.wire_late_ms) // 3
    print(f"window: {rec.window_s!r} s, {rec.ticks} ticks, "
          f"{rec.device_calls} device histogram calls, {rec.decisions} "
          f"decisions, {rec.events} events, {len(rec.watched)} watched, "
          f"repack {rec.repack_s!r} s, live {len(cell.fleet.jobs)}; wire "
          f"late p95 ms in the first and last third "
          f"{p95(rec.wire_late_ms[:third])!r}, "
          f"{p95(rec.wire_late_ms[-third:]) if third else None!r}",
          flush=True)
    if cell.faults is not None:
        print(f"faults: {rec.failures} failures, {rec.restarts} restarts "
              f"in the window, {len(cell.faults.failed)} devices down at "
              f"the close; {rec.paused_s!r} s off the window clock "
              f"(restart traces, placement checks)", flush=True)
    if cell.store is not None:
        print(f"store: {rec.journal_records} journal records, "
              f"{rec.snapshots} snapshots in the window", flush=True)
    metas = {id(fj.builder.meta) for fj in cell.fleet.jobs.values()}
    print(f"telemetry: {len(metas)} distinct TraceMeta objects over "
          f"{len(cell.fleet.jobs)} live jobs", flush=True)
    t = time.perf_counter()
    limits = spec["check"]["limits"]
    correct, checks, other = check.run_checks(cell, rec, limits)
    print(f"check: {time.perf_counter() - t!r} s; {json.dumps(other)}",
          flush=True)
    workload = args.workload
    if args.trace:
        layer = layer_record(rec, spans, reduced, peaks, program,
                             idle_by_span)
        metrics = {}
        for m in listed(spec["spec"]["per_layer"], workload):
            v = read_layer(m["name"], layer)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        print(f"trace: {json.dumps(reduced)}", flush=True)
        print(f"program spans: {json.dumps(program)}", flush=True)
        print(f"idle by program span: {json.dumps(idle_by_span)}",
              flush=True)
    else:
        values = end_to_end(rec, setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in listed(spec["spec"]["end_to_end"], workload)
                   if m["name"] in values}
    device = dict(platform=dev.platform, kind=dev.device_kind,
                  count=len(devices), memory_peak_bytes=mem)
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
    result = dict(correct=correct, attempted=rec.attempted,
                  failed=rec.failed, metrics=metrics, device=device)
    if reduced is not None:
        result["breakdown"] = dict(device_ops=reduced["ops"],
                                   idle_gaps=reduced["gaps"])
    result["checks"] = checks
    for name, c in checks.items():
        rel = ">=" if c.get("at_least") else "<="
        print(f"check {name}: {c['value']!r} {rel} {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
