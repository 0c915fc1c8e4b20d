"""Device failures and repairs: the process a configuration's ``faults``
section describes.

Keys, both on the telemetry clock (in the closed loop one chunk of
telemetry per tick, in the open loop the window clock):

  * ``failures_per_device_s``: failures per device per second, over the
    whole inventory, so that the cluster fails ``rate x devices`` times a
    second whatever its health;
  * ``repair_s``: seconds from a device's failure to its restore.

The gaps between failures are fixed sets of ``BLOCK`` exponential
quantiles; the seed only orders each set and picks which healthy device
fails.  Set-up starts the process at its steady state, as the stagger does
for ages: ``rate x devices x repair_s`` devices (rounded) are down from the
start, their repairs due at evenly spaced times over one repair period, so
failures and repairs both run at an even pace from the first tick.
"""
from __future__ import annotations

import heapq

import numpy as np

from bench.traffic import generator as gen

BLOCK = 64          # gaps per fixed set of exponential quantiles


class FaultProcess:
    def __init__(self, spec: dict, device_ids, rng: np.random.Generator):
        self.ids = sorted(device_ids)
        self.rate = float(spec["failures_per_device_s"]) * len(self.ids)
        self.repair_s = float(spec["repair_s"])
        if self.rate <= 0 or self.repair_s <= 0:
            raise SystemExit("bench: faults need failures_per_device_s > 0 "
                             "and repair_s > 0")
        down = int(round(self.rate * self.repair_s))
        if down >= len(self.ids):
            raise SystemExit(f"bench: faults keep {down} of {len(self.ids)} "
                             f"devices down; no healthy device would remain")
        self.rng = rng
        self.gaps: list[float] = []
        pick = np.sort(rng.choice(len(self.ids), size=down, replace=False))
        dues = (np.arange(down) + 0.5) / max(down, 1) * self.repair_s
        rng.shuffle(dues)
        self.initial = [self.ids[int(i)] for i in pick]
        self.repairs = sorted((float(t), d)
                              for t, d in zip(dues, self.initial))
        self.failed = set(self.initial)
        self.next_fail = self._gap()

    def _gap(self) -> float:
        if not self.gaps:
            self.gaps = list(gen.exponential_set(BLOCK, 1.0 / self.rate,
                                                 self.rng))
        return self.gaps.pop()

    def next_due(self) -> float:
        return min(self.next_fail,
                   self.repairs[0][0] if self.repairs else np.inf)

    def due(self, now: float):
        """Yield the ``(time, "fail" | "restore", device)`` events due by
        ``now``, in time order, moving the process past each one as it is
        taken: ``failed`` is the set of devices down once the caller has
        acted on the events taken so far."""
        while self.next_due() <= now:
            if self.repairs and self.repairs[0][0] <= self.next_fail:
                t, dev = heapq.heappop(self.repairs)
                self.failed.discard(dev)
                yield t, "restore", dev
                continue
            t = self.next_fail
            healthy = [d for d in self.ids if d not in self.failed]
            dev = healthy[int(self.rng.integers(len(healthy)))]
            self.failed.add(dev)
            heapq.heappush(self.repairs, (t + self.repair_s, dev))
            self.next_fail = t + self._gap()
            yield t, "fail", dev
