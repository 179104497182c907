"""Megabytes the program copied from the device to the host per graph
(``quilt.d2h_bytes``: the bytes of each transfer, counted where it
happens; a second fetch of one array is served from its host copy)."""

from bench.metrics._program import counter


def read(ctx):
    moved = counter(ctx, "quilt.d2h_bytes")
    n = len(ctx["records"])
    return moved / n / 1e6 if moved is not None and n else None
