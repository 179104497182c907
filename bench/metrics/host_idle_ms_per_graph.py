"""Device idle time per graph while the host runs the program's own code
inside a sampler call and no JAX event: the NumPy filter by ``keep``, the
edge array's assembly, chunking and the engine's decisions."""

from bench.metrics._trace import idle_ms_per_graph


def read(ctx):
    return idle_ms_per_graph(ctx, "bench.graph")
