"""Shared arithmetic of the readers that take the program's own counters
and spans (``repro.tracing``), recorded over the window of a ``--trace 1``
run and laid over its trace by ``bench/program_trace.py``.  Each returns
None where the program records none."""

from bench import program_trace


def counter(ctx, name):
    """A program counter's rise over the window, or None."""
    window = program_trace.window(ctx)
    if window is None or name not in window["counters"]:
        return None
    return window["counters"][name]


def span_s(ctx, name):
    """Summed seconds of the program's spans named ``name``, or None."""
    window = program_trace.window(ctx)
    if window is None:
        return None
    ns = sum(r.t1_ns - r.t0_ns for r in window["records"] if r.name == name)
    return ns * 1e-9 if ns > 0 else None


def idle_ms_per_graph(ctx, names):
    """Milliseconds per window graph in which the device was idle while
    one of ``names`` was the innermost program span."""
    window = program_trace.window(ctx)
    n = len(ctx["records"])
    if window is None or window["attribution"] is None or not n:
        return None
    idle = window["attribution"]["idle_by_program_span_s"]
    return sum(idle.get(name, 0.0) for name in names) * 1e3 / n
