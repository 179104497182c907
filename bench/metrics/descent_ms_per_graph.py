"""Device time of the Pallas descent kernel per graph (trace)."""

from bench.metrics._trace import ms_per_graph


def read(ctx):
    return ms_per_graph(ctx, "descent")
