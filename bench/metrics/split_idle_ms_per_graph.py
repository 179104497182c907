"""Device idle time per graph while the sub-round loop of a split
exact-cell round (``quilt.split``) was the innermost program span: the
host's steps between one sub-round and the next that no other span
covers.  A window of rounds that were not split reads 0; a program that
cannot split a round (it counts no ``quilt.dispatched_slots``) reads
nothing."""

from bench.metrics._program import idle_ms_per_graph
from bench.metrics.split_fill_pct import counts_dispatched_slots


def read(ctx):
    if not counts_dispatched_slots():
        return None
    return idle_ms_per_graph(ctx, ("quilt.split",))
