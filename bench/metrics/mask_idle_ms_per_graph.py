"""Device idle time per graph in the NumPy build of the keep mask
(``quilt.mask`` less the copies nested in it)."""

from bench.metrics._program import idle_ms_per_graph


def read(ctx):
    return idle_ms_per_graph(ctx, ("quilt.mask",))
