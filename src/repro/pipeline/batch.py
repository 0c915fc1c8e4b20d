"""Batched multi-job profiling: one columnar pass over the fleet's telemetry.

``BatchProfileEngine`` holds the state of *many* concurrent ``ProfileBuilder``
runs as slot-indexed columnar arrays — energy/busy prefix counters, blocked-EMA
carry state, per-bin-size spike histograms stacked ``(capacity, n_bins)``, and
idle-trim flags — so one stacked NumPy pass (diff → EMA prefix-doubling →
trim fold → ``np.add.at`` histogram scatter) advances every live job per mux
tick instead of looping Python per job.  Slots are allocated on admit and
freed on retire, so dynamic arrival/departure keeps working; freed slots are
recycled.

Bit-for-bit identity with the per-job ``ProfileBuilder`` (the reference
implementation) is a hard contract, pinned by a hypothesis property in
``tests/test_fleet.py``:

  * every elementwise stage (counter diff, ``p_raw = de/dt``, the blocked-EMA
    prefix-doubling, idle-trim slicing) evaluates the *same float expression
    per element* as the 1D path — NumPy elementwise ops on stacked rows are
    bitwise equal to the per-row ops;
  * rows are grouped per tick by ``(chunk_len, n_pending, has_ema_state)`` so
    stacked EMA blocks line up at identical absolute positions;
  * histogram counts are sums of 1.0s — exact integers in float64 — so the
    batched ``np.add.at`` scatter accumulates to bit-identical values
    regardless of ordering.

``SlotBuilder`` is the per-job view over one slot: it quacks exactly like a
``ProfileBuilder`` (``ingest``/``snapshot``/``finalize``/``spike_count``/
``fraction``/...), so ``OnlineCapController`` and the fleet controller drive
it unchanged.

On a TPU backend the histogram scatter of each tick's newly committed
block counts on the device (promoted idle tails and the finalize flush stay
host bincounts).  The host computes every spike sample's bin index in
float64 with the NumPy branch's own expression and packs the indices of all
bin sizes into one int32 per sample (``kernels.spike_hist.pack_fields``);
one jitted Pallas call per tick (``kernels.ops.spike_hist_packed``) returns
the integer counts of every row.  The device never bins a float, so both
backends produce the same counts integer for integer, and autodetection
does not change the arithmetic.  Rows and samples pad up to power-of-two
buckets (``device_shape``), so a drive compiles a bounded set of programs,
which ``warmup`` compiles up front.  Elsewhere the scatter is pure NumPy.

Error semantics: the engine validates every chunk of a tick *before* mutating
any slot, so a poisoned chunk leaves the whole tick's builders untouched
(strictly stronger than the per-chunk path, which mutates earlier jobs in the
tick before raising) — the raised message is byte-identical to the per-job
``ProfileBuilder`` message for the first offending chunk in batch order.
"""
from __future__ import annotations

import math

import numpy as np

import repro.obs as obs
from repro.core import spikes
from repro.pipeline.builder import (DEFAULT_BIN_SIZES, EMA_BLOCK,
                                    PartialProfile, _ema_filter_block,
                                    _fold_trim, _validate_readings)
from repro.telemetry.simulator import TelemetryChunk, TraceMeta

__all__ = ["BatchProfileEngine", "SlotBuilder", "device_shape"]

#: smallest padded (rows, samples) of a device histogram call; both axes
#: round up to a power of two at or above these floors
DEVICE_ROW_FLOOR = 256
DEVICE_SAMPLE_FLOOR = 256


def device_shape(rows: int, samples: int) -> tuple[int, int]:
    """The padded shape of a device histogram call over ``rows`` rows of
    up to ``samples`` committed samples each."""
    return (max(DEVICE_ROW_FLOOR, 1 << (int(rows) - 1).bit_length()),
            max(DEVICE_SAMPLE_FLOOR, 1 << (int(samples) - 1).bit_length()))


#: the trace quantile every emitted profile carries (``choose_bin_size``'s)
PQ = 90.0
#: ``np.percentile``'s own fraction: ``q / 100`` in float64
_PQ_FRAC = float(np.true_divide(PQ, 100.0))
#: the rank window keeps about this top share of the committed trace, with
#: one EMA block of slack on either side
_WINDOW_SHARE = 0.15
_WINDOW_SLACK = EMA_BLOCK
_EMPTY = np.empty(0, np.float64)


class _RankWindow:
    """Exact upper order statistics of one slot's committed trace.

    Holds every committed sample at or above ``theta``, sorted, and the
    count of those below it.  ``absorb`` folds in only the committed pieces
    appended since the last call (the pieces are immutable), so its work
    grows with the new samples and the window, not with the whole trace.
    ``percentile`` takes the two order statistics ``np.percentile``'s
    linear method interpolates between, across the window and a snapshot's
    uncommitted extras, and combines them with numpy's own ``_lerp``
    expression: the result equals ``np.percentile(trace, PQ)`` bit for bit.
    When a rank falls below ``theta`` (the trace's power stepped down), the
    window is rebuilt from the whole committed trace, and the counter
    ``snapshot.pq_rebuilds`` counts it."""

    __slots__ = ("win", "theta", "below", "pieces")

    def __init__(self):
        self.win = _EMPTY
        self.theta = -np.inf
        self.below = 0
        self.pieces = 0

    @staticmethod
    def _keep(n: int) -> int:
        """The window size to trim to over ``n`` committed samples."""
        return n - max(0, int((1.0 - _WINDOW_SHARE) * n) - _WINDOW_SLACK)

    def absorb(self, committed: list) -> None:
        k = len(committed)
        if self.pieces == k:
            return
        x = committed[-1] if k - self.pieces == 1 \
            else np.concatenate(committed[self.pieces:])
        self.pieces = k
        if self.theta != -np.inf:
            hi = x[x >= self.theta]
            self.below += len(x) - len(hi)
            x = hi
        if len(x):
            # timsort merges the sorted window with the new run
            win = np.concatenate((self.win, x))
            win.sort(kind="stable")
            self.win = win
        keep = self._keep(self.below + len(self.win))
        if len(self.win) > keep + _WINDOW_SLACK:
            # theta rises to the lowest copy of the value at the cut, so
            # every sample left below it is strictly smaller
            cut = int(self.win.searchsorted(self.win[-keep]))
            if cut:
                self.theta = float(self.win[cut])
                self.below += cut
                self.win = self.win[cut:]

    def _rebuild(self, committed: list, room: int) -> None:
        """Reset the window over the whole committed trace, with at most
        ``room`` samples below ``theta``."""
        obs.count("snapshot.pq_rebuilds")
        x = np.concatenate(committed)
        t = min(len(x) - self._keep(len(x)), room)
        if t > 0:
            x.partition(t)
            self.theta = float(x[t])
            hi = x[x >= self.theta]
            self.below = len(x) - len(hi)
        else:
            self.theta, hi, self.below = -np.inf, x, 0
        hi.sort()
        self.win = hi
        self.pieces = len(committed)

    def percentile(self, committed: list, extras: np.ndarray) -> float:
        """``np.percentile`` at ``PQ`` of the committed trace followed by
        ``extras`` (absorbs ``committed`` first)."""
        self.absorb(committed)
        m = len(extras)
        n = self.below + len(self.win) + m
        if n == 0:
            return 0.0
        vi = (n - 1) * _PQ_FRAC                  # numpy's virtual index
        if vi >= n - 1:                          # one sample: numpy takes
            lo = hi = n - 1                      # index -1 twice, and its
            gamma = vi + 1.0                     # gamma is vi - (-1)
        else:
            lo = math.floor(vi)
            hi = lo + 1
            gamma = vi - lo
        ex = extras[extras >= self.theta] if m else extras
        under = self.below + m - len(ex)
        if lo < under:
            self._rebuild(committed, lo - m)
            ex = extras[extras >= self.theta] if m else extras
            under = self.below + m - len(ex)
        r0, r1 = lo - under, hi - under
        if len(ex):
            # window samples below rank r0 - len(ex) precede every extra
            # at or above rank r0, and those past r1 follow them: the two
            # ranks lie in the merge of this slice with the extras
            s = max(0, r0 - len(ex))
            part = np.concatenate((self.win[s:r1 + 1], ex))
            part.sort()
            a, b = float(part[r0 - s]), float(part[r1 - s])
        else:
            a, b = float(self.win[r0]), float(self.win[r1])
        # numpy's _lerp(a, b, gamma), in the same float64 operations
        diff = b - a
        if gamma >= 0.5:
            return b - diff * (1.0 - gamma)
        return a + diff * gamma


class SlotBuilder:
    """Per-job view over one ``BatchProfileEngine`` slot.

    Duck-types the ``ProfileBuilder`` surface (``meta``/``tdp``/``ingest``/
    ``snapshot``/``finalize``/``spike_vector``/``spike_count``/``fraction``/
    ``n_ingested``/``n_committed``/``bin_sizes``) so every consumer of a
    per-job builder — ``OnlineCapController.observe`` above all — works
    unchanged.  Created via ``BatchProfileEngine.builder``; ``release()``
    frees the slot for reuse (after which the view rejects every call).
    """

    __slots__ = ("engine", "slot", "meta", "_released")

    def __init__(self, engine: "BatchProfileEngine", slot: int,
                 meta: TraceMeta):
        self.engine = engine
        self.slot = slot
        self.meta = meta
        self._released = False

    def _check(self) -> int:
        if self._released:
            raise ValueError(
                f"slot builder for job {self.meta.name!r} was released")
        return self.slot

    @property
    def tdp(self) -> float:
        return float(self.engine._tdp[self._check()])

    @property
    def bin_sizes(self):
        return self.engine.bin_sizes

    @property
    def n_ingested(self) -> int:
        return int(self.engine._next_index[self._check()])

    @property
    def n_committed(self) -> int:
        return int(self.engine._n_committed[self._check()])

    @property
    def fraction(self) -> float:
        return self.n_ingested / max(self.meta.n_samples, 1)

    def ingest(self, chunk: TelemetryChunk) -> None:
        self.engine.ingest_batch((self._check(),), (chunk,))

    def spike_vector(self, bin_size: float) -> np.ndarray:
        return self.engine.spike_vector(self._check(), bin_size)

    def spike_count(self, bin_size: float | None = None) -> int:
        return self.engine.spike_count(self._check(), bin_size)

    def snapshot(self) -> PartialProfile:
        return self.engine.snapshot(self._check())

    def finalize(self) -> PartialProfile:
        return self.engine.finalize(self._check())

    def release(self) -> None:
        """Free the underlying slot for reuse (idempotent)."""
        if not self._released:
            self.engine.free(self.slot)
            self._released = True


class BatchProfileEngine:
    """Slot-indexed columnar state for many concurrent profiling runs."""

    def __init__(self, bin_sizes=DEFAULT_BIN_SIZES, alpha: float = 0.5,
                 ema_block: int = EMA_BLOCK, capacity: int = 64,
                 backend: str | None = None):
        """``backend`` selects where the commit-time histogram scatter
        counts: ``"numpy"`` (host bincount), ``"pallas"`` (one packed-index
        Pallas call per tick; interpreted off-TPU), or ``None`` to
        autodetect — the device on a TPU backend, NumPy elsewhere.  Both
        give identical counts."""
        if not 0.0 < alpha <= 1.0:
            raise ValueError(f"alpha must be in (0, 1], got {alpha}")
        self.bin_sizes = tuple(float(c) for c in bin_sizes)
        if any(c <= 0 for c in self.bin_sizes):
            raise ValueError(f"bin sizes must be positive: {self.bin_sizes}")
        if backend not in (None, "numpy", "pallas"):
            raise ValueError(f"backend must be 'numpy', 'pallas', or None "
                             f"(autodetect), got {backend!r}")
        self.alpha = float(alpha)
        self.w = 1.0 - self.alpha
        self.block = int(ema_block)
        self._backend = backend
        self._fields = None          # packed-index layout of the device path
        self._device_pending: list[tuple] = []   # this tick's device rows
        self.device_calls = 0        # one per tick that commits a block
        self.device_shapes: set[tuple[int, int]] = set()   # compiled shapes
        cap = max(int(capacity), 1)
        # columnar scalar state (one row per slot)
        self._tdp = np.zeros(cap, np.float64)
        self._energy = np.zeros(cap, np.float64)
        self._busy = np.zeros(cap, np.float64)
        self._next_index = np.zeros(cap, np.int64)
        self._n_pending = np.zeros(cap, np.int64)
        self._ema_state = np.zeros(cap, np.float64)
        self._ema_has = np.zeros(cap, bool)
        self._seen_busy = np.zeros(cap, bool)
        self._n_committed = np.zeros(cap, np.int64)
        self._final = np.zeros(cap, bool)
        self._live = np.zeros(cap, bool)
        # stacked per-bin-size spike histograms: (capacity, n_bins)
        self._hist = {c: np.zeros((cap, spikes.num_bins(c)), np.float64)
                      for c in self.bin_sizes}
        # ragged per-slot state (sample runs of varying length)
        self._meta: list[TraceMeta | None] = [None] * cap
        self._pending: list[list[np.ndarray]] = [[] for _ in range(cap)]
        self._busyq: list[list[np.ndarray]] = [[] for _ in range(cap)]
        self._tail: list[list[np.ndarray]] = [[] for _ in range(cap)]
        self._committed: list[list[np.ndarray]] = [[] for _ in range(cap)]
        # order statistics of each committed trace, for the emitted p90
        self._rank: list[_RankWindow | None] = [None] * cap
        self._free: list[int] = list(range(cap - 1, -1, -1))

    # -- capacity --------------------------------------------------------
    @property
    def capacity(self) -> int:
        return len(self._live)

    @property
    def n_live(self) -> int:
        return int(self._live.sum())

    def _grow(self) -> None:
        # quadruple: growth is a stop-the-world copy of every column, and a
        # slot row is tiny (~576 B of histogram), so fewer bigger steps beat
        # doubling on the fleet admission path
        old = self.capacity
        new = old * 4
        add = new - old
        for name in ("_tdp", "_energy", "_busy", "_ema_state"):
            setattr(self, name, np.concatenate(
                [getattr(self, name), np.zeros(add, np.float64)]))
        for name in ("_next_index", "_n_pending", "_n_committed"):
            setattr(self, name, np.concatenate(
                [getattr(self, name), np.zeros(add, np.int64)]))
        for name in ("_ema_has", "_seen_busy", "_final", "_live"):
            setattr(self, name, np.concatenate(
                [getattr(self, name), np.zeros(add, bool)]))
        for c, h in self._hist.items():
            self._hist[c] = np.vstack(
                [h, np.zeros((add, h.shape[1]), np.float64)])
        self._meta.extend([None] * add)
        self._rank.extend([None] * add)
        for lst in (self._pending, self._busyq, self._tail, self._committed):
            lst.extend([] for _ in range(add))
        self._free.extend(range(new - 1, old - 1, -1))

    # -- slot lifecycle --------------------------------------------------
    def alloc(self, meta: TraceMeta, tdp: float) -> int:
        """Claim a slot for one profiling run; returns its index."""
        if not self._free:
            self._grow()
        s = self._free.pop()
        self._tdp[s] = float(tdp)
        self._energy[s] = 0.0
        self._busy[s] = 0.0
        self._next_index[s] = 0
        self._n_pending[s] = 0
        self._ema_state[s] = 0.0
        self._ema_has[s] = False
        self._seen_busy[s] = False
        self._n_committed[s] = 0
        self._final[s] = False
        self._live[s] = True
        # histogram rows are already zero: ``_grow`` allocates zeros and
        # ``free`` scrubs a slot's rows on release, keeping the (hot) admit
        # path free of the six per-bin-size clears
        self._meta[s] = meta
        self._pending[s] = []
        self._busyq[s] = []
        self._tail[s] = []
        self._committed[s] = []
        self._rank[s] = _RankWindow()
        return s

    def builder(self, meta: TraceMeta, tdp: float) -> SlotBuilder:
        """Allocate a slot and return its ``ProfileBuilder``-shaped view."""
        return SlotBuilder(self, self.alloc(meta, tdp), meta)

    def free(self, slot: int) -> None:
        """Release a slot (idempotent); its state is recycled on next alloc."""
        if self._live[slot]:
            self._live[slot] = False
            self._meta[slot] = None
            # scrub the histogram rows now so alloc() can skip the clears
            # (free-list invariant: every parked slot's rows are zero)
            for c in self.bin_sizes:
                self._hist[c][slot, :] = 0.0
            # drop the ragged trace state now — the slot may idle on the
            # free list for a while
            self._pending[slot] = []
            self._busyq[slot] = []
            self._tail[slot] = []
            self._committed[slot] = []
            self._rank[slot] = None
            self._free.append(slot)

    def _check_live(self, slot: int) -> None:
        if not self._live[slot]:
            raise ValueError(f"slot {slot} is not allocated")

    # -- ingestion -------------------------------------------------------
    def ingest_batch(self, slots, chunks) -> None:
        """Advance many slots by one chunk each — the per-tick columnar pass.

        ``slots``/``chunks`` are parallel sequences; each slot may appear at
        most once (the mux emits at most one chunk per job per tick).  The
        whole batch is validated before any slot mutates, and the raised
        error for bad telemetry matches the per-job ``ProfileBuilder``
        message for the first offending chunk in batch order.
        """
        with obs.span("engine"):
            with obs.span("engine.validate"):
                groups = self._validate_batch(slots, chunks)
            if not groups:
                return
            # phase 4: mutate, one stacked pass per group
            with obs.span("engine.advance"):
                for (length, pend, has_state), grp in groups.items():
                    idx, er2, br2, dt, d_e, d_b = grp.pop()
                    self._advance_group(idx, er2, br2, dt, d_e, d_b, length,
                                        pend, has_state)
            self._flush_device()

    def _validate_batch(self, slots, chunks) -> dict:
        """Phases 1-3 of ``ingest_batch``: check every chunk, group the rows
        by (chunk length, pending count, EMA-state presence) and stack each
        group's arrays, mutating nothing.  Returns the groups, each a list
        of rows ending in its stacked arrays; empty when no chunk holds a
        reading."""
        slots = list(slots)
        chunks = list(chunks)
        if len(slots) != len(chunks):
            raise ValueError("slots and chunks differ in length")
        if len(set(slots)) != len(slots):
            raise ValueError("duplicate slot in one ingest_batch tick")
        # phase 1: per-row scalar checks (finalized / contiguity / shape),
        # mirroring ProfileBuilder.ingest's check order and messages
        rows = []            # (batch_pos, slot, chunk, er, br)
        for pos, (s, chunk) in enumerate(zip(slots, chunks)):
            self._check_live(s)
            if self._final[s]:
                raise ValueError("ProfileBuilder already finalized")
            if chunk.start_index != self._next_index[s]:
                raise ValueError(
                    f"chunk starts at sample {chunk.start_index}, expected "
                    f"{self._next_index[s]} (chunks must be contiguous and "
                    f"ordered)")
            er = np.asarray(chunk.energy_j, np.float64)
            br = np.asarray(chunk.busy_s, np.float64)
            if er.shape != br.shape:
                raise ValueError("energy_j and busy_s readings differ in "
                                 "length")
            if len(er) == 0:
                continue                    # empty chunk: a no-op
            rows.append((pos, s, chunk, er, br))
        if not rows:
            return {}
        # phase 2: group rows so stacked 2D passes line up — equal chunk
        # length for the counter diff, equal pending count + state presence
        # for fixed-position EMA blocks
        groups: dict[tuple, list] = {}
        for row in rows:
            _, s, chunk, er, _ = row
            key = (len(er), int(self._n_pending[s]), bool(self._ema_has[s]))
            groups.setdefault(key, []).append(row)
        # phase 3: validate every group before any state mutates (the
        # all-or-nothing tick contract)
        bad_pos = None
        for (length, _, _), grp in groups.items():
            idx = np.fromiter((r[1] for r in grp), np.int64, len(grp))
            er2 = np.stack([r[3] for r in grp])
            br2 = np.stack([r[4] for r in grp])
            dt = np.fromiter((r[2].sample_dt for r in grp), np.float64,
                             len(grp))
            d_e = np.diff(er2, axis=1)
            d_b = np.diff(br2, axis=1)
            ok = (np.isfinite(dt) & (dt > 0)
                  & np.isfinite(er2).all(axis=1) & np.isfinite(br2).all(axis=1)
                  & (er2[:, 0] >= self._energy[idx])
                  & (d_e >= 0).all(axis=1)
                  & (br2[:, 0] >= self._busy[idx])
                  & (d_b >= 0).all(axis=1))
            for j in np.nonzero(~ok)[0]:
                pos = grp[j][0]
                if bad_pos is None or pos < bad_pos[0]:
                    bad_pos = (pos, grp[j])
            grp.append((idx, er2, br2, dt, d_e, d_b))  # stash stacked arrays
        if bad_pos is not None:
            _, (_, s, chunk, er, br) = bad_pos
            _validate_readings(self._meta[s], float(self._energy[s]),
                               float(self._busy[s]), chunk.start_index,
                               chunk.sample_dt, er, br)
            raise AssertionError("vectorized validation flagged a chunk the "
                                 "reference validator accepts")  # unreachable
        return groups

    def _advance_group(self, idx: np.ndarray, er2: np.ndarray,
                       br2: np.ndarray, dt: np.ndarray, d_e: np.ndarray,
                       d_b: np.ndarray, length: int,
                       pend: int, has_state: bool) -> None:
        """One stacked columnar advance for rows sharing (chunk length,
        pending count, EMA-state presence).  ``d_e``/``d_b`` are the
        validator's intra-chunk counter diffs, reused here: prepending the
        prefix-state column gives the identical elementwise subtractions as
        ``np.diff(concat([[prev], er]))``."""
        k = len(idx)
        de = np.concatenate([er2[:, :1] - self._energy[idx, None], d_e],
                            axis=1)
        db = np.concatenate([br2[:, :1] - self._busy[idx, None], d_b],
                            axis=1)
        self._energy[idx] = er2[:, -1]
        self._busy[idx] = br2[:, -1]
        self._next_index[idx] += length
        p_raw = de / dt[:, None]
        busy = (db > 0).astype(np.float64)

        total = pend + length
        nblocks = total // self.block
        if nblocks == 0:
            # nothing commits this tick: everything stays pending
            for j, s in enumerate(idx.tolist()):
                self._pending[s].append(p_raw[j].copy())
                self._busyq[s].append(busy[j].copy())
            self._n_pending[idx] = total
            return
        # stack the pending buffers (equal length across the group) and the
        # new samples into (k, total); commit whole fixed-position blocks
        if pend:
            prev_p = np.stack([np.concatenate(self._pending[s])
                               if len(self._pending[s]) != 1
                               else self._pending[s][0] for s in idx])
            prev_b = np.stack([np.concatenate(self._busyq[s])
                               if len(self._busyq[s]) != 1
                               else self._busyq[s][0] for s in idx])
            buf = np.concatenate([prev_p, p_raw], axis=1)
            busy_buf = np.concatenate([prev_b, busy], axis=1)
        else:
            buf, busy_buf = p_raw, busy
        take = nblocks * self.block
        filt = np.empty((k, take), np.float64)
        state = self._ema_state[idx]
        for b in range(nblocks):
            blk = buf[:, b * self.block:(b + 1) * self.block]
            out = self.alpha * blk
            if has_state or b > 0:
                out[:, 0] += self.w * state
            else:
                out[:, 0] = blk[:, 0]       # batch seeding: out_0 = p_0
            shift, decay = 1, self.w
            while shift < out.shape[1] and decay != 0.0:
                out[:, shift:] += decay * out[:, :-shift]
                shift *= 2
                decay *= decay
            state = out[:, -1]
            filt[:, b * self.block:(b + 1) * self.block] = out
        self._ema_state[idx] = state
        self._ema_has[idx] = True
        rest_p = buf[:, take:]
        rest_b = busy_buf[:, take:]
        for j, s in enumerate(idx.tolist()):
            self._pending[s] = [rest_p[j].copy()] if rest_p.shape[1] else []
            self._busyq[s] = [rest_b[j].copy()] if rest_b.shape[1] else []
        self._n_pending[idx] = total - take
        self._fold_commit(idx, filt, busy_buf[:, :take])

    def _fold_commit(self, idx: np.ndarray, filt: np.ndarray,
                     busy: np.ndarray) -> None:
        """Columnar idle-trim fold + histogram commit over (k, F) filtered
        samples — the batched twin of ``_fold_trim`` + ``_commit``."""
        k, F = filt.shape
        busy_pos = busy > 0
        has_busy = busy_pos.any(axis=1)
        first = np.where(has_busy, np.argmax(busy_pos, axis=1), F)
        last = np.where(has_busy,
                        F - 1 - np.argmax(busy_pos[:, ::-1], axis=1), -1)
        seen = self._seen_busy[idx]
        start = np.where(seen, 0, first)
        commit_end = np.where(has_busy, last + 1, start)
        # pass 1: histogram contribution of the newly-committed spans
        cols = np.arange(F)
        commit_mask = (cols >= start[:, None]) & (cols < commit_end[:, None])
        r = filt / self._tdp[idx][:, None]
        self._scatter_hist(idx, r, commit_mask)
        # pass 2: old-tail pieces promoted by a fresh busy sample, plus the
        # ragged per-row trace bookkeeping (plain Python ints — NumPy scalar
        # indexing in this loop costs more than the work it guards)
        tail_vals: list[np.ndarray] = []
        tail_rows: list[np.ndarray] = []
        n_add = [0] * k
        hb_l, seen_l = has_busy.tolist(), seen.tolist()
        start_l, end_l = start.tolist(), commit_end.tolist()
        for j, s in enumerate(idx.tolist()):
            if hb_l[j]:
                if self._tail[s]:
                    for piece in self._tail[s]:
                        n_add[j] += len(piece)
                        tail_vals.append(piece / self._tdp[s])
                        tail_rows.append(np.full(len(piece), s, np.int64))
                    self._committed[s].extend(self._tail[s])
                    self._tail[s] = []
                span = filt[j, start_l[j]:end_l[j]]
                self._committed[s].append(span)
                n_add[j] += len(span)
                if end_l[j] < F:
                    self._tail[s] = [filt[j, end_l[j]:]]
            elif seen_l[j]:
                self._tail[s].append(filt[j])
            # rows with no busy yet: leading idle, dropped entirely
        if tail_vals:
            rr = np.concatenate(tail_vals)
            rows = np.concatenate(tail_rows)
            keep = rr >= spikes.SPIKE_LO
            rr, rows = rr[keep], rows[keep]
            if len(rr):
                for c in self.bin_sizes:
                    h = self._hist[c]
                    n = h.shape[1]
                    bidx = np.minimum(((rr - spikes.SPIKE_LO) / c)
                                      .astype(np.int64), n - 1)
                    np.add.at(h, (rows, bidx), 1.0)
        self._n_committed[idx] += n_add
        self._seen_busy[idx] = seen | has_busy

    def _scatter_hist(self, idx: np.ndarray, r: np.ndarray,
                      mask: np.ndarray) -> None:
        """Accumulate the masked (k, F) relative-power block into every
        tracked histogram.  Counts are exact float64 integers, so the
        scatter is bit-identical to per-piece ``np.bincount`` adds.  The
        device path bins here too and queues the packed indices for the
        tick's one device call (``_flush_device``)."""
        device = self._resolve_backend() == "pallas"
        spike = r >= spikes.SPIKE_LO
        np.logical_and(spike, mask, out=spike)
        ri, ci = np.nonzero(spike)
        if not len(ri):
            return
        vals = r[ri, ci]
        shifted = vals - spikes.SPIKE_LO     # shared first step of every bin
        k = len(idx)
        # scratch buffers shared across bin sizes: the per-bin pass is pure
        # elementwise work, so reusing the output arrays saves six rounds of
        # large allocations per tick without changing a single bit
        q = np.empty_like(shifted)
        bidx = np.empty(len(shifted), np.int64)
        flat = np.empty(len(shifted), np.int64)
        if device:
            packed = np.zeros(len(shifted), np.int64)
        for b, c in enumerate(self.bin_sizes):
            h = self._hist[c]
            n = h.shape[1]
            np.divide(shifted, c, out=q)
            np.copyto(bidx, q, casting="unsafe")  # C truncation == astype
            np.minimum(bidx, n - 1, out=bidx)     # quotients are >= 0
            if device:
                np.left_shift(bidx, self._fields[b][0], out=flat)
                packed |= flat
                continue
            np.multiply(ri, n, out=flat)
            flat += bidx
            # one flat bincount + dense row add: the same exact integer
            # counts as np.add.at, without its scattered read-modify-write
            counts = np.bincount(flat, minlength=k * n)
            h[idx] += counts.reshape(k, n)
        if device:
            self._device_pending.append((idx, ri, ci, packed, r.shape[1]))

    def _flush_device(self) -> None:
        """Count the tick's queued spike samples in one device call and add
        the counts to every tracked histogram.  Slots are unique within a
        tick, so the row add is a plain fancy-index add."""
        pending, self._device_pending = self._device_pending, []
        if not pending:
            return
        from repro.kernels.ops import spike_hist_packed
        with obs.span("engine.device"):
            rows = sum(len(p[0]) for p in pending)
            shape = device_shape(rows, max(p[4] for p in pending))
            buf = np.full(shape, -1, np.int32)
            r0 = 0
            for idx, ri, ci, packed, _ in pending:
                buf[r0 + ri, ci] = packed
                r0 += len(idx)
            slots = np.concatenate([p[0] for p in pending])
            counts = np.asarray(spike_hist_packed(buf, self._fields))[:rows]
            self.device_calls += 1
            self.device_shapes.add(shape)
            for (_, _, offset), c in zip(self._fields, self.bin_sizes):
                h = self._hist[c]
                h[slots] += counts[:, offset:offset + h.shape[1]]

    def warmup(self, rows: int, samples: int = EMA_BLOCK) -> int:
        """Compile the device histogram call for every padded shape a tick
        of up to ``rows`` rows, each committing up to ``samples`` samples,
        can take — so no compile lands inside a drive.  Returns the number
        of shapes warmed (0 on the NumPy backend)."""
        if self._resolve_backend() != "pallas":
            return 0
        from repro.kernels.ops import spike_hist_packed
        top_rows, width = device_shape(rows, samples)
        n_rows, warmed = DEVICE_ROW_FLOOR, 0
        while n_rows <= top_rows:
            shape = (n_rows, width)
            np.asarray(spike_hist_packed(np.full(shape, -1, np.int32),
                                         self._fields))
            self.device_shapes.add(shape)
            warmed += 1
            n_rows *= 2
        return warmed

    def _resolve_backend(self) -> str:
        if self._backend is None:
            import jax
            self._backend = "pallas" \
                if jax.default_backend() == "tpu" else "numpy"
        if self._backend == "pallas" and self._fields is None:
            from repro.kernels.spike_hist import pack_fields
            self._fields = pack_fields(
                [self._hist[c].shape[1] for c in self.bin_sizes])
        return self._backend

    # -- incremental queries ---------------------------------------------
    def spike_vector(self, slot: int, bin_size: float) -> np.ndarray:
        self._check_live(slot)
        c = float(bin_size)
        if c not in self._hist:
            raise ValueError(f"bin size {bin_size} not tracked; "
                             f"tracked: {self.bin_sizes}")
        h = self._hist[c][slot]
        tot = h.sum()
        if tot == 0:
            return np.zeros(len(h))
        return h / tot

    def spike_count(self, slot: int, bin_size: float | None = None) -> int:
        self._check_live(slot)
        c = self.bin_sizes[0] if bin_size is None else float(bin_size)
        if c not in self._hist:
            raise ValueError(f"bin size {bin_size} not tracked; "
                             f"tracked: {self.bin_sizes}")
        return int(self._hist[c][slot].sum())

    def spike_count_batch(self, slots) -> np.ndarray:
        """Vector ``spike_count`` over many slots: one stacked row-sum.
        Histogram counts are exact float64 integers, so each row's sum
        equals the scalar call regardless of reduction order."""
        idx = np.asarray(list(slots), np.int64)
        if len(idx) and not self._live[idx].all():
            bad = int(idx[np.nonzero(~self._live[idx])[0][0]])
            raise ValueError(f"slot {bad} is not allocated")
        return self._hist[self.bin_sizes[0]][idx].sum(axis=1).astype(np.int64)

    # -- profile emission ------------------------------------------------
    def _profile(self, slot: int, trace: np.ndarray,
                 complete: bool) -> PartialProfile:
        m = self._meta[slot]
        n_ing = int(self._next_index[slot])
        return PartialProfile(
            name=m.name, tdp=float(self._tdp[slot]), power_trace=trace,
            sm_util=m.app_sm_util, dram_util=m.app_dram_util,
            exec_time=m.exec_time, scaling={}, domain=m.domain,
            fraction=n_ing / max(m.n_samples, 1), n_samples=n_ing,
            complete=complete)

    def _pending_view(self, slot: int) -> np.ndarray:
        if not self._n_pending[slot]:
            return np.empty(0, np.float64)
        state = float(self._ema_state[slot]) if self._ema_has[slot] else None
        return _ema_filter_block(np.concatenate(self._pending[slot]), state,
                                 self.alpha, self.w)

    def snapshot(self, slot: int) -> PartialProfile:
        """A valid partial profile over everything this slot ingested so
        far; pure — mirrors ``ProfileBuilder.snapshot`` bit-for-bit."""
        self._check_live(slot)
        filt = self._pending_view(slot)
        pieces = list(self._committed[slot])
        extras: list[np.ndarray] = []
        if len(filt):
            busy = np.concatenate(self._busyq[slot])[:len(filt)] \
                if self._busyq[slot] else np.zeros(len(filt))
            extras, _, _ = _fold_trim(filt, busy, bool(self._seen_busy[slot]),
                                      list(self._tail[slot]))
            pieces += extras
        trace = np.concatenate(pieces) if pieces else np.empty(0, np.float64)
        prof = self._profile(slot, trace, complete=False)
        self._prefill_spike_memo(prof, slot, extras)
        self._prefill_pq(prof, slot,
                         np.concatenate(extras) if extras else _EMPTY)
        return prof

    def _prefill_pq(self, prof: PartialProfile, slot: int,
                    extras: np.ndarray) -> None:
        """Seed the profile's ``p_quantile`` memo at ``PQ`` from the slot's
        rank window, over the committed trace and the snapshot's uncommitted
        ``extras``: the same float ``spikes.p_quantile`` computes from the
        whole trace."""
        v = self._rank[slot].percentile(self._committed[slot], extras)
        prof.__dict__["_pq_memo"] = {PQ: v / prof.tdp}
        obs.count("snapshot.pq_prefilled")

    def _memo_mats(self, idx: np.ndarray, rr: np.ndarray | None,
                   rows: np.ndarray | None) -> dict[float, np.ndarray]:
        """Stacked spike-memo prefill for the slots in ``idx``: per bin size
        one (k, n_bins) histogram slice, one flat bincount folding the rows'
        uncommitted extras (``rr``: relative spike samples, ``rows``: the
        local row each belongs to), one row-wise normalization.  Counts are
        exact float64 integers and the divide is elementwise, so every row
        matches the scalar ``_prefill_spike_memo`` bit-for-bit."""
        k = len(idx)
        mats: dict[float, np.ndarray] = {}
        shifted = None if rr is None else rr - spikes.SPIKE_LO
        for c in self.bin_sizes:
            H = self._hist[c][idx]               # fancy index: a fresh copy
            n = H.shape[1]
            if shifted is not None:
                bidx = np.minimum((shifted / c).astype(np.int64), n - 1)
                H += np.bincount(rows * n + bidx,
                                 minlength=k * n).reshape(k, n)
            tot = H.sum(axis=1)
            M = H / np.where(tot > 0.0, tot, 1.0)[:, None]
            M[tot == 0.0] = 0.0          # empty rows pin to exact zeros
            mats[c] = M
        return mats

    def snapshot_batch(self, slots) -> list[PartialProfile]:
        """``snapshot`` over many slots in one columnar pass.

        The ragged per-row work (the EMA view of mid-block pending samples,
        the idle-trim fold, the trace concat) stays per slot, but the memo
        prefill — the expensive part of ``snapshot`` — runs stacked through
        ``_memo_mats``.  Every returned profile is bit-identical to
        ``snapshot(slot)``; each also carries the shared memo matrix so the
        classifier's sweep can gather target rows without a Python stack."""
        idx = np.asarray(list(slots), np.int64)
        k = len(idx)
        if not k:
            return []
        live = self._live[idx]
        if not live.all():
            bad = int(idx[np.nonzero(~live)[0][0]])
            raise ValueError(f"slot {bad} is not allocated")
        npend = self._n_pending[idx].tolist()
        traces: list[np.ndarray] = []
        ex_all = [_EMPTY] * k             # each row's uncommitted extras
        rr_parts: list[np.ndarray] = []
        row_parts: list[np.ndarray] = []
        empty = np.empty(0, np.float64)
        for j, s in enumerate(idx.tolist()):
            pieces = self._committed[s]
            if npend[j]:
                filt = self._pending_view(s)
                if len(filt):
                    busy = np.concatenate(self._busyq[s])[:len(filt)] \
                        if self._busyq[s] else np.zeros(len(filt))
                    extras, _, _ = _fold_trim(
                        filt, busy, bool(self._seen_busy[s]),
                        list(self._tail[s]))
                    if extras:
                        pieces = pieces + extras
                        ex_all[j] = np.concatenate(extras) \
                            if len(extras) > 1 else extras[0]
                        r = ex_all[j] / self._tdp[s]
                        r = r[r >= spikes.SPIKE_LO]
                        if len(r):
                            rr_parts.append(r)
                            row_parts.append(np.full(len(r), j, np.int64))
            if not pieces:
                traces.append(empty)
            elif len(pieces) == 1:
                traces.append(pieces[0])  # committed pieces are immutable
            else:
                traces.append(np.concatenate(pieces))
        obs.count("snapshot.samples", sum(map(len, traces)))
        rr = np.concatenate(rr_parts) if rr_parts else None
        rows = np.concatenate(row_parts) if rr_parts else None
        mats = self._memo_mats(idx, rr, rows)
        bins = self.bin_sizes
        out = []
        for j, s in enumerate(idx.tolist()):
            prof = self._profile(s, traces[j], complete=False)
            prof.__dict__["_spike_memo"] = {c: mats[c][j] for c in bins}
            prof.__dict__["_spike_mat"] = (mats, j)
            self._prefill_pq(prof, s, ex_all[j])
            out.append(prof)
        return out

    def _prefill_spike_memo(self, prof: PartialProfile, slot: int,
                            extras: list[np.ndarray]) -> None:
        """Seed the profile's per-bin-size spike-vector memo from the slot's
        incremental histograms, so the classifier's bin-size sweep never
        re-histograms the trace.  Histogram counts are exact float64
        integers, so ``committed counts + extras counts`` equals the
        one-pass ``spikes.spike_vector`` bincount bit-for-bit, and the
        shared normalization divide produces the identical vector."""
        extra_r = None
        if extras:
            r = np.concatenate(extras) / self._tdp[slot]
            r = r[r >= spikes.SPIKE_LO]
            extra_r = r if len(r) else None
        memo: dict[float, np.ndarray] = {}
        for c in self.bin_sizes:
            h = self._hist[c][slot]
            n = len(h)
            if extra_r is not None:
                bidx = np.minimum(((extra_r - spikes.SPIKE_LO) / c)
                                  .astype(np.int64), n - 1)
                h = h + np.bincount(bidx, minlength=n).astype(np.float64)
            tot = h.sum()
            # h / tot allocates, so the memo never aliases the live columns
            memo[c] = np.zeros(n) if tot == 0 else h / tot
        prof.__dict__["_spike_memo"] = memo

    def _commit_row(self, slot: int, arr: np.ndarray) -> None:
        """Per-slot twin of ``ProfileBuilder._commit`` (finalize path)."""
        if not len(arr):
            return
        self._committed[slot].append(arr)
        self._n_committed[slot] += len(arr)
        r = arr / self._tdp[slot]
        r = r[r >= spikes.SPIKE_LO]
        if len(r):
            for c in self.bin_sizes:
                h = self._hist[c]
                n = h.shape[1]
                bidx = np.minimum(((r - spikes.SPIKE_LO) / c)
                                  .astype(np.int64), n - 1)
                h[slot] += np.bincount(bidx, minlength=n).astype(np.float64)

    def _flush(self, slot: int) -> None:
        """Commit the slot's pending EMA tail and seal it (idempotent)."""
        if self._final[slot]:
            return
        filt = self._pending_view(slot)
        if len(filt):
            self._ema_state[slot] = float(filt[-1])
            self._ema_has[slot] = True
            busy = np.concatenate(self._busyq[slot])[:len(filt)]
            commits, seen, tail = _fold_trim(
                filt, busy, bool(self._seen_busy[slot]),
                list(self._tail[slot]))
            self._seen_busy[slot] = seen
            self._tail[slot] = tail
            for arr in commits:
                self._commit_row(slot, arr)
        self._pending[slot] = []
        self._n_pending[slot] = 0
        self._busyq[slot] = []
        self._final[slot] = True

    def finalize(self, slot: int) -> PartialProfile:
        """Flush the slot's EMA tail and emit its completed profile."""
        self._check_live(slot)
        self._flush(slot)
        trace = np.concatenate(self._committed[slot]) \
            if self._committed[slot] else np.empty(0, np.float64)
        prof = self._profile(slot, trace, complete=True)
        # after the flush the histograms cover the whole committed trace
        self._prefill_spike_memo(prof, slot, [])
        self._prefill_pq(prof, slot, _EMPTY)
        return prof

    def finalize_batch(self, slots) -> list[PartialProfile]:
        """``finalize`` over many slots: the ragged EMA-tail flush stays per
        slot, the memo prefill and profile assembly batch like
        ``snapshot_batch``.  Bit-identical to per-slot ``finalize``."""
        idx = np.asarray(list(slots), np.int64)
        k = len(idx)
        if not k:
            return []
        if len(set(idx.tolist())) != k:
            # a repeated slot would collide in the fancy-index scatter
            # below; the scalar path is idempotent, so take it verbatim
            return [self.finalize(s) for s in idx.tolist()]
        traces: list[np.ndarray] = []
        rr_parts: list[np.ndarray] = []
        row_parts: list[np.ndarray] = []
        empty = np.empty(0, np.float64)
        for j, s in enumerate(idx.tolist()):
            self._check_live(s)
            if not self._final[s]:
                # inline ``_flush``, deferring the histogram commit: the
                # per-piece bincounts it would do sum to the one flat
                # scatter below (counts are exact float64 integers)
                filt = self._pending_view(s)
                if len(filt):
                    self._ema_state[s] = float(filt[-1])
                    self._ema_has[s] = True
                    busy = np.concatenate(self._busyq[s])[:len(filt)]
                    commits, seen, tail = _fold_trim(
                        filt, busy, bool(self._seen_busy[s]),
                        list(self._tail[s]))
                    self._seen_busy[s] = seen
                    self._tail[s] = tail
                    commits = [a for a in commits if len(a)]
                    if commits:
                        self._committed[s].extend(commits)
                        self._n_committed[s] += sum(len(a) for a in commits)
                        r = np.concatenate(commits) if len(commits) > 1 \
                            else commits[0]
                        r = r / self._tdp[s]
                        r = r[r >= spikes.SPIKE_LO]
                        if len(r):
                            rr_parts.append(r)
                            row_parts.append(np.full(len(r), j, np.int64))
                self._pending[s] = []
                self._n_pending[s] = 0
                self._busyq[s] = []
                self._final[s] = True
            pieces = self._committed[s]
            if not pieces:
                traces.append(empty)
            elif len(pieces) == 1:
                traces.append(pieces[0])  # committed pieces are immutable
            else:
                traces.append(np.concatenate(pieces))
        if rr_parts:
            rr = np.concatenate(rr_parts)
            rows = np.concatenate(row_parts)
            shifted = rr - spikes.SPIKE_LO
            for c in self.bin_sizes:
                h = self._hist[c]
                n = h.shape[1]
                bidx = np.minimum((shifted / c).astype(np.int64), n - 1)
                h[idx] += np.bincount(rows * n + bidx,
                                      minlength=k * n).reshape(k, n)
        # post-flush the histograms cover each whole committed trace
        mats = self._memo_mats(idx, None, None)
        bins = self.bin_sizes
        out = []
        for j, s in enumerate(idx.tolist()):
            prof = self._profile(s, traces[j], complete=True)
            prof.__dict__["_spike_memo"] = {c: mats[c][j] for c in bins}
            prof.__dict__["_spike_mat"] = (mats, j)
            self._prefill_pq(prof, s, _EMPTY)
            out.append(prof)
        return out
