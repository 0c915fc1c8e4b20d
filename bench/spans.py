"""The traced run's spans and counters, wrapped around calls into each layer.

Only a ``--trace 1`` run installs them.  Every span adds its wall time to a
per-name total and, while the profiler runs, writes a ``bench.<name>``
annotation into the trace so that the reduction can name the host work
that each idle gap of the device fell in.
"""
from __future__ import annotations

import time
from contextlib import contextmanager

import numpy as np


class Spans:
    def __init__(self):
        self.total: dict[str, float] = {}
        self.hist_calls: list[tuple[int, int]] = []
        self.pack_in_tick = 0.0

    @contextmanager
    def span(self, name: str):
        import jax
        with jax.profiler.TraceAnnotation(f"bench.{name}"):
            t = time.perf_counter()
            try:
                yield
            finally:
                self.total[name] = (self.total.get(name, 0.0)
                                    + time.perf_counter() - t)

    def install(self, fleet) -> None:
        """Wrap the layer entry points of one controller: the fleet tick,
        the engine's columnar ingest, the batched snapshot and classify
        sweep, and the device histogram call (whose counted samples and
        rows it records)."""
        import repro.fleet.controller as controller
        import repro.kernels.ops as ops
        spans = self

        def wrap(name, fn):
            def wrapped(*a, **k):
                with spans.span(name):
                    return fn(*a, **k)
            return wrapped

        tick = fleet.ingest_tick

        def ingest_tick(batch):
            before = fleet.repack_s
            with spans.span("tick"):
                out = tick(batch)
            spans.pack_in_tick += fleet.repack_s - before
            return out

        fleet.ingest_tick = ingest_tick
        fleet.engine.ingest_batch = wrap("engine", fleet.engine.ingest_batch)
        controller.observe_fleet = wrap("classify", controller.observe_fleet)
        hist = ops.spike_hist_packed

        def spike_hist_packed(packed, fields, *a, **k):
            counted = np.asarray(packed) >= 0
            spans.hist_calls.append((int(counted.sum()),
                                     int(counted.any(axis=1).sum())))
            with spans.span("hist"):
                return hist(packed, fields, *a, **k)

        ops.spike_hist_packed = spike_hist_packed
