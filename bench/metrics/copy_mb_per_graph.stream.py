"""``copy_mb_per_graph`` in the stream cell, where it moves the stream's
throughput (``edges_per_s.stream``)."""

from bench.metrics.copy_mb_per_graph import read  # noqa: F401
