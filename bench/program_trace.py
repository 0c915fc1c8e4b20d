"""The program's own spans and counters (``repro.obs``) in a traced run.

``start()`` resets and switches on the program's recorder just before the
window, and ``finish(recorder)`` takes its report and switches it off
after; the report goes into the layer record under ``program``.  While
the profiler runs, each program span is also a ``minos.<name>`` event on
the host, and ``attribute(profile)`` cuts the window's device-idle time
by the innermost such span open over it, weighted by overlap.  A checkout
whose program has no recorder gives ``None`` from ``start``, and the
readers of these metrics then report nothing.
"""
from __future__ import annotations

from bench import tracing

PREFIX = "minos."


def start():
    """The program's recorder, reset and on; ``None`` where it has none."""
    try:
        import repro.obs as recorder
    except ImportError:
        return None
    recorder.reset()
    recorder.enable()
    return recorder


def finish(recorder) -> dict | None:
    """The recorder's report (``spans`` and ``counters``); it goes off."""
    if recorder is None:
        return None
    report = recorder.report()
    recorder.disable()
    return report


def _pieces(spans):
    """Split the time that ``(start, end, name)`` spans cover into sorted,
    disjoint ``(start, end, name)`` pieces, each named by the innermost
    span open over it.  The spans come from one thread, so they nest."""
    out, stack, t = [], [], 0
    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, outer = stack.pop()
            if end > t:
                out.append((t, end, outer))
                t = end
        if stack and s > t:
            out.append((t, s, stack[-1][1]))
        stack.append((e, name))
        t = s
    while stack:
        end, outer = stack.pop()
        if end > t:
            out.append((t, end, outer))
            t = end
    return out


def attribute(profile) -> dict:
    """Device-idle seconds of the window per innermost program span
    (``spans``, mean over device planes), ``unspanned_s``: idle time in
    which no program span was open (the benchmark's loop, the collector,
    the interpreter), and ``devices``: the device planes read."""
    window = None
    host_spans = []
    devices = []
    for plane in profile.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = [ln for ln in plane.lines if ln.name in tracing.OPS_LINES]
            if lines:
                devices.append(lines)
            continue
        for ln in plane.lines:
            for ev in ln.events:
                if ev.name == tracing.WINDOW:
                    window = (ev.start_ns, ev.end_ns)
                elif ev.name.startswith(PREFIX):
                    host_spans.append((ev.start_ns, ev.end_ns,
                                       ev.name[len(PREFIX):]))
    if window is None:
        raise RuntimeError(f"no {tracing.WINDOW!r} span in the profile")
    w0, w1 = window
    pieces = _pieces(host_spans)
    idle_by: dict[str, float] = {}
    unspanned = 0.0
    for lines in devices:
        busy = tracing._union(
            (max(ev.start_ns, w0), min(ev.end_ns, w1))
            for ln in lines for ev in ln.events
            if min(ev.end_ns, w1) > max(ev.start_ns, w0))
        edges = [w0] + [x for s, e in busy for x in (s, e)] + [w1]
        k = 0
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            covered = 0.0
            while k < len(pieces) and pieces[k][1] <= a:
                k += 1
            j = k
            while j < len(pieces) and pieces[j][0] < b:
                s, e, name = pieces[j]
                over = min(e, b) - max(s, a)
                if over > 0:
                    idle_by[name] = idle_by.get(name, 0.0) + over
                    covered += over
                j += 1
            unspanned += (b - a) - covered
    n_dev = max(len(devices), 1)
    return dict(spans={n: t * 1e-9 / n_dev for n, t in
                       sorted(idle_by.items(), key=lambda x: -x[1])},
                unspanned_s=unspanned * 1e-9 / n_dev, devices=len(devices))
