"""Power-spike histograms as Pallas TPU kernels — Minos's own telemetry
binning (paper §4.1.1) as on-device streaming ops.

``spike_hist_pallas`` bins one trace of float32 relative magnitudes.  The
fleet engine's kernel, ``spike_hist_packed_pallas``, only *counts*: the host
computes every spike sample's bin index in float64 (the exact expression of
the NumPy reference), packs the indices of all tracked bin sizes into one
int32 per sample (``pack_fields``), and the device accumulates integer
counts per row.  Binning float32 values on the device would move samples
that sit within an ulp of a bin edge, so the engine never does that.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_OUT_COLS = 128   # one padded output tile; n_bins <= 128
_SUB = 8          # rows per one-hot slab: bounds the (rows, 128, 128) temp


def _lane_counts(idx: jax.Array) -> jax.Array:
    """(rows, 128) int32 lane ids (-1 = none) -> (rows, 128) int32 counts:
    a one-hot over the 128 lanes, reduced across each row's samples."""
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, 1, _OUT_COLS), 2)
    return jnp.sum((idx[:, :, None] == lanes).astype(jnp.int32), axis=1)


def _hist_kernel(r_ref, o_ref, *, n_bins: int, lo: float, hi: float):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    r = r_ref[...].astype(jnp.float32)            # (rows, 128)
    width = (hi - lo) / n_bins
    # bin index per sample; out-of-range -> -1 (not counted)
    idx = jnp.floor((r - lo) / width).astype(jnp.int32)
    idx = jnp.where(r >= lo, jnp.minimum(idx, n_bins - 1), -1)
    sub = min(_SUB, idx.shape[0])
    acc = jnp.zeros((1, _OUT_COLS), jnp.int32)
    for s in range(0, idx.shape[0], sub):
        acc += jnp.sum(_lane_counts(idx[s:s + sub]), axis=0, keepdims=True)
    o_ref[0:1, :] += acc.astype(jnp.float32)


def spike_hist_pallas(rel_power: jax.Array, n_bins: int, lo: float = 0.5,
                      hi: float = 2.0, block_rows: int = 64,
                      interpret: bool | None = None) -> jax.Array:
    """rel_power: (n,) f32 relative magnitudes -> (n_bins,) counts.

    n is padded to a (rows x 128) layout; padding uses -inf (never counted).
    ``interpret=None`` autodetects: compiled on TPU, interpreter elsewhere.
    """
    assert n_bins <= _OUT_COLS
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n = rel_power.shape[0]
    cols = 128
    block_rows = min(block_rows, -(-n // cols))
    # pad the row count up to a block multiple (padding is -inf, never
    # counted) so every grid step runs a full requested block — strictly
    # better than shrinking block_rows to a divisor of rows (the seed's
    # decrement search, or math.gcd, which can degrade to 1-row blocks)
    rows = -(-n // (cols * block_rows)) * block_rows
    pad = rows * cols - n
    r = jnp.pad(rel_power.astype(jnp.float32), (0, pad),
                constant_values=-jnp.inf).reshape(rows, cols)
    grid = (rows // block_rows,)
    kernel = functools.partial(_hist_kernel, n_bins=n_bins, lo=lo, hi=hi)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, cols), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((8, _OUT_COLS), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((8, _OUT_COLS), jnp.float32),
        interpret=interpret,
    )(r)
    return out[0, :n_bins]


def pack_fields(n_bins) -> tuple[tuple[int, int, int], ...]:
    """The packed-index layout for histograms of ``n_bins[b]`` bins each:
    one ``(shift, mask, lane_offset)`` per histogram.  Bin index ``i`` of
    histogram ``b`` sits in bits ``shift:shift + width`` of the packed int32
    and is counted in output lane ``lane_offset + i``.  Raises
    ``ValueError`` when the histograms need more than 31 bits or 128
    lanes."""
    fields, shift, offset = [], 0, 0
    for n in n_bins:
        width = max(1, (int(n) - 1).bit_length())
        fields.append((shift, (1 << width) - 1, offset))
        shift += width
        offset += int(n)
    if shift > 31 or offset > _OUT_COLS:
        raise ValueError(
            f"histograms of {list(n_bins)} bins need {shift} index bits and "
            f"{offset} lanes; one packed int32 holds 31 bits and 128 lanes")
    return tuple(fields)


def _packed_count_kernel(p_ref, o_ref, *, fields):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    p = p_ref[...]                                 # (block_rows, 128) int32
    acc = jnp.zeros(o_ref.shape, jnp.int32)
    for shift, mask, offset in fields:
        lane = jnp.where(p >= 0, ((p >> shift) & mask) + offset, -1)
        acc += _lane_counts(lane)
    o_ref[...] += acc


def spike_hist_packed_pallas(packed: jax.Array, fields, block_rows: int = 8,
                             interpret: bool | None = None) -> jax.Array:
    """(rows, samples) int32 packed bin indices -> (rows, 128) int32 counts.

    Each non-negative entry is one spike sample whose per-histogram bin
    indices were packed on the host by the ``fields`` layout
    (``pack_fields``); -1 entries are never counted.  ``rows`` must be a
    multiple of ``block_rows`` and ``samples`` a multiple of 128 — the
    engine pads to fixed buckets so a drive compiles a bounded set of
    programs.  Counts are exact int32.  ``interpret=None`` autodetects like
    ``spike_hist_pallas``."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    rows, n = packed.shape
    cols = 128
    if rows % block_rows or n % cols:
        raise ValueError(f"packed shape {packed.shape} must tile by "
                         f"({block_rows}, {cols})")
    kernel = functools.partial(_packed_count_kernel, fields=tuple(fields))
    return pl.pallas_call(
        kernel,
        grid=(rows // block_rows, n // cols),
        in_specs=[pl.BlockSpec((block_rows, cols), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((block_rows, _OUT_COLS), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, _OUT_COLS), jnp.int32),
        interpret=interpret,
    )(packed)
