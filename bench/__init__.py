"""On-chip benchmark of the MAGM quilt sampler (see PERF.md)."""
