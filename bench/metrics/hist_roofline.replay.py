"""Device histogram (``kernels/spike_hist.py``): the least time the chip
needs for the window's histogram work, over the kernel's device time, in
percent.  The work is the same whatever implements it: 4 bytes in per
counted (unpadded) spike sample and 4 bytes out per lane of each row that
holds one, over the chip's HBM bandwidth from ``bench/peaks.json``.
Moves ``decisions_per_s``."""

KERNEL = ("spike_hist", "packed_count")
LANES = 128


def read(layer):
    trace = layer["trace"]
    if trace is None or not layer["hist_calls"]:
        return None
    kernel_s = sum(t for name, t in trace["op_s"].items()
                   if any(k in name for k in KERNEL))
    if kernel_s <= 0:
        return None
    moved = sum(4 * samples + 4 * LANES * rows
                for samples, rows in layer["hist_calls"])
    least = moved / layer["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / kernel_s
