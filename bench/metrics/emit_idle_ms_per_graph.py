"""Device idle time per graph in the host emission of the edges: the
filter, stack and cast of ``QuiltRun.edges()`` (``quilt.emit``), the
stream's windows (``stream.window``) and chunk assembly
(``stream.rechunk``), each less the copies nested in it."""

from bench.metrics._program import idle_ms_per_graph


def read(ctx):
    return idle_ms_per_graph(
        ctx, ("quilt.emit", "stream.window", "stream.rechunk")
    )
