"""Share of the slots the quilt's round programs ran that were real
candidate slots: 100 x ``quilt.candidate_slots`` / ``quilt.dispatched_slots``.
A split exact-cell round pads its last sub-round with zero-target rows,
so its fill falls below 100; one unpadded round reads 100.  A program
that counts no dispatched slots reads nothing."""

from bench.metrics._program import counter

NAME = "quilt.dispatched_slots"


def counts_dispatched_slots() -> bool:
    """Whether the program registers the counter at all."""
    from repro import tracing

    return NAME in tracing.snapshot()


def read(ctx):
    if not counts_dispatched_slots():
        return None
    slots = counter(ctx, "quilt.candidate_slots")
    dispatched = counter(ctx, NAME)
    return 100.0 * slots / dispatched if slots and dispatched else None
