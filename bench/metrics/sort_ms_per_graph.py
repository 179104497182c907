"""Device time of the XLA sorts (the segmented dedup) per graph (trace)."""

from bench.metrics._trace import ms_per_graph


def read(ctx):
    return ms_per_graph(ctx, "sort")
