"""``descent_roofline`` in the stream cell, where it moves the stream's
throughput (``edges_per_s.stream``)."""

from bench.metrics.descent_roofline import read  # noqa: F401
