"""``copy_gbps`` in the stream cell, where it moves the stream's
throughput (``edges_per_s.stream``)."""

from bench.metrics.copy_gbps import read  # noqa: F401
