"""``engine_idle_ms_per_graph`` in the stream cell, where it moves the stream's
throughput (``edges_per_s.stream``)."""

from bench.metrics.engine_idle_ms_per_graph import read  # noqa: F401
