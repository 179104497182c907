"""Seconds the warm-up graph took beyond a window graph: the compile, or
the load from the persistent cache, of the round programs."""

import statistics


def read(ctx):
    graphs = [r["t_end"] - r["t0"] for r in ctx["records"] if not r["failed"]]
    if not graphs:
        return None
    return ctx["spans"].total("warmup") - statistics.median(graphs)
