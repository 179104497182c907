"""``device_idle_pct`` in the stream cell, where it moves the stream's
throughput (``edges_per_s.stream``)."""

from bench.metrics.device_idle_pct import read  # noqa: F401
