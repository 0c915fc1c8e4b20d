"""Spans and counters inside the control plane, off by default.

    import repro.obs as obs

    obs.reset()
    obs.enable()
    ...                             # drive the fleet
    print(obs.report())             # {"spans": {...}, "counters": {...}}
    obs.disable()

``span(name)`` is a context manager placed at the layer boundaries of the
served path (``tick``, ``engine``, ``classify``, ``finalize_job``, ...),
``count(name, n)`` adds to a counter.  Off, ``span`` returns one shared null
context and ``count`` returns at once: no clock read, no allocation.  On,
each span reads ``time.perf_counter_ns`` at entry and exit and keeps its
calls, total and self time (total less the time of the spans opened inside
it), and opens a ``jax.profiler.TraceAnnotation("minos.<name>", tick=<n>)``,
so that a running ``jax.profiler`` trace holds the span on the host, on the
same clock as the device's events.  ``tick`` counts the ``tick`` spans
opened since ``reset``; every span carries the current one.

The recorder observes and never feeds back: nothing it records reaches a
decision, a placement or the journal.  One process, one thread: the open
spans are one stack.
"""
from __future__ import annotations

import time
from contextlib import nullcontext

__all__ = ["span", "count", "enable", "disable", "reset", "report"]

_NULL = nullcontext()
_on = False
_annotation = None           # jax.profiler.TraceAnnotation, once enabled
_tick = 0
_stack: list["_Span"] = []
_spans: dict[str, list[int]] = {}     # name -> [calls, total_ns, self_ns]
_counters: dict[str, int] = {}


class _Span:
    __slots__ = ("name", "start", "child", "ann")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        global _tick
        if self.name == "tick":
            _tick += 1
        self.ann = _annotation(f"minos.{self.name}", tick=_tick)
        self.ann.__enter__()
        self.child = 0
        _stack.append(self)
        self.start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        took = time.perf_counter_ns() - self.start
        _stack.pop()
        if _stack:
            _stack[-1].child += took
        rec = _spans.get(self.name)
        if rec is None:
            rec = _spans[self.name] = [0, 0, 0]
        rec[0] += 1
        rec[1] += took
        rec[2] += took - self.child
        self.ann.__exit__(*exc)
        return False


def span(name: str):
    """A context manager that times ``name`` while the recorder is on."""
    if not _on:
        return _NULL
    return _Span(name)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the counter ``name`` while the recorder is on."""
    if _on:
        _counters[name] = _counters.get(name, 0) + int(n)


def enable() -> None:
    """Switch the recorder on (imports ``jax.profiler`` the first time)."""
    global _on, _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    _on = True


def disable() -> None:
    """Switch the recorder off; what it holds stays until ``reset``."""
    global _on
    _on = False


def reset() -> None:
    """Forget every span, counter and the tick number.  Spans open at the
    call still close on the stack and record into the fresh totals."""
    global _tick
    _tick = 0
    _spans.clear()
    _counters.clear()


def report() -> dict:
    """``{"spans": {name: {calls, total_s, self_s}}, "counters": {...}}``."""
    return {"spans": {name: {"calls": c, "total_s": t * 1e-9,
                             "self_s": s * 1e-9}
                      for name, (c, t, s) in _spans.items()},
            "counters": dict(_counters)}
