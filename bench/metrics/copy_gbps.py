"""Device-to-host copy rate: ``quilt.d2h_bytes`` over the summed time of
the program's ``quilt.copy`` spans, in GB/s."""

from bench.metrics._program import counter, span_s


def read(ctx):
    moved = counter(ctx, "quilt.d2h_bytes")
    s = span_s(ctx, "quilt.copy")
    return moved / s / 1e9 if moved is not None and s else None
