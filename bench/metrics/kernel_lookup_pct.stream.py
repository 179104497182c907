"""``kernel_lookup_pct`` in the stream cell, where it moves the stream's
throughput (``edges_per_s.stream``)."""

from bench.metrics.kernel_lookup_pct import read  # noqa: F401
