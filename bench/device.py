"""What the benchmark reads of the device: compiles, memory and peaks."""
from __future__ import annotations

import json
import os

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


class CompileCounter:
    """Counts backend compiles (and persistent-cache loads) with JAX's
    monitoring events."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.compiles += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1


def memory_peak_bytes(devices) -> int | None:
    """Peak bytes in use on the fullest chip, where the backend says."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def peaks_for(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown device is an
    error, never a default."""
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise SystemExit(f"bench: no peaks for device kind {device_kind!r} "
                         f"in {PEAKS}")
    return table[device_kind]
