"""The descent kernel's share of the HBM roofline: the bytes the law must
write (two int32 per candidate slot, bench.workcount) over the kernel's
device time and the chip's HBM bandwidth.  The v5e publishes no integer
VPU peak, so this is the memory side of the roofline only."""

from bench.metrics._trace import ms_per_graph


def read(ctx):
    ms = ms_per_graph(ctx, "descent")
    if ms is None:
        return None
    rate = ctx["work"]["descent_bytes_per_graph"] / (ms * 1e-3)
    return 100.0 * rate / ctx["peak"]["hbm_bytes_per_s"]
