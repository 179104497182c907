"""Share of the candidate slots the device rounds drew that became edges:
100 x ``quilt.kept_edges`` / ``quilt.candidate_slots``."""

from bench.metrics._program import counter


def read(ctx):
    kept = counter(ctx, "quilt.kept_edges")
    slots = counter(ctx, "quilt.candidate_slots")
    return 100.0 * kept / slots if kept is not None and slots else None
