"""Device idle time per graph while the quilt engine's host code was the
innermost program span: its decisions (``quilt.run``), the dispatch of a
round (``quilt.round``) and the wait on it (``quilt.round_wait``)."""

from bench.metrics._program import idle_ms_per_graph


def read(ctx):
    return idle_ms_per_graph(
        ctx, ("quilt.run", "quilt.round", "quilt.round_wait")
    )
