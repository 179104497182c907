"""Shared arithmetic of the readers that take a device trace."""


def ms_per_graph(ctx, cls):
    """Device milliseconds of one op class per window graph, or None when
    the trace holds no such op."""
    trace = ctx.get("trace")
    n = len(ctx["records"])
    if not trace or not n:
        return None
    s = trace["class_s"].get(cls, 0.0)
    return s * 1e3 / n if s > 0.0 else None


def idle_ms_per_graph(ctx, label):
    """Milliseconds per window graph in which the device was idle while
    ``label`` was the innermost event on the benchmark's host thread."""
    trace = ctx.get("trace")
    n = len(ctx["records"])
    if not trace or not n:
        return None
    return trace["idle_by_span_s"].get(label, 0.0) * 1e3 / n
