"""Share of the candidate slots whose config -> node lookup ran inside the
descent kernel: 100 x ``quilt.kernel_lookup_slots`` /
``quilt.candidate_slots``.  A program that counts no such slots (one with
no lookup in the kernel) reads nothing; one that counts them and looked
none up there reads 0."""

from bench.metrics._program import counter

NAME = "quilt.kernel_lookup_slots"


def counts_kernel_lookups() -> bool:
    """Whether the program registers the counter at all (a counter that
    did not rise over the window is left out of its records)."""
    from repro import tracing

    return NAME in tracing.snapshot()


def read(ctx):
    slots = counter(ctx, "quilt.candidate_slots")
    if not slots or not counts_kernel_lookups():
        return None
    return 100.0 * (counter(ctx, NAME) or 0) / slots
