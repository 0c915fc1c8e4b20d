"""Device profile of the traced window, reduced to the numbers the metrics
read: busy time (the union of device operation intervals), time per device
operation, the operations that took most time, and the longest idle gaps
named by the benchmark span (``bench.<name>``) that the host was in."""
from __future__ import annotations

import glob
import os

WINDOW = "bench.window"
SPAN_PREFIX = "bench."
OPS_LINES = ("XLA Ops",)


def start(log_dir: str) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def load(log_dir: str):
    from jax.profiler import ProfileData
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")),
                   key=os.path.getmtime)
    if not found:
        raise RuntimeError(f"no profile written under {log_dir}")
    return ProfileData.from_file(found[-1])


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce(profile, top: int = 10) -> dict:
    """``busy_s`` (mean over device planes), ``window_s``, ``op_s`` (device
    seconds per operation name), and ``ops`` and ``gaps`` (the ``top``
    largest, in seconds)."""
    window = None
    host_spans = []
    devices = []
    for plane in profile.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = [ln for ln in plane.lines if ln.name in OPS_LINES]
            if lines:
                devices.append(lines)
            continue
        for ln in plane.lines:
            for ev in ln.events:
                if ev.name == WINDOW:
                    window = (ev.start_ns, ev.end_ns)
                elif ev.name.startswith(SPAN_PREFIX):
                    host_spans.append((ev.start_ns, ev.end_ns,
                                       ev.name[len(SPAN_PREFIX):]))
    if window is None:
        raise RuntimeError(f"no {WINDOW!r} span in the profile")
    w0, w1 = window
    busy_total, ops = 0.0, {}
    gaps = []
    for lines in devices:
        iv = []
        for ln in lines:
            for ev in ln.events:
                s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
                if e <= s:
                    continue
                iv.append((s, e))
                name = ev.name.split("{")[0].strip()   # drop the HLO text
                ops[name] = ops.get(name, 0.0) + (e - s)
        merged = _union(iv)
        busy_total += sum(e - s for s, e in merged)
        edges = [w0] + [x for s, e in merged for x in (s, e)] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, a, b))
    n_dev = max(len(devices), 1)
    gaps.sort(reverse=True)
    host_spans.sort(key=lambda x: x[1] - x[0])      # innermost first
    named = []
    for length, a, b in gaps[:top]:
        mid = (a + b) / 2
        name = next((n for s, e, n in host_spans if s <= mid <= e), "none")
        named.append([name, length * 1e-9])
    return dict(
        busy_s=busy_total * 1e-9 / n_dev, window_s=(w1 - w0) * 1e-9,
        devices=len(devices),
        op_s={n: t * 1e-9 / n_dev for n, t in ops.items()},
        ops=[[n, t * 1e-9 / n_dev] for n, t in
             sorted(ops.items(), key=lambda x: -x[1])[:top]],
        gaps=named)
