"""``window_compiles`` in the stream cell, where it moves the stream's
throughput (``edges_per_s.stream``)."""

from bench.metrics.window_compiles import read  # noqa: F401
