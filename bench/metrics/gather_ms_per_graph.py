"""Device time of the XLA gathers (the config -> node lookup) per graph
(trace)."""

from bench.metrics._trace import ms_per_graph


def read(ctx):
    return ms_per_graph(ctx, "gather")
