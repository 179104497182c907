"""Device rounds (first and top-up) the engine dispatched per graph, from
the program's DISPATCH_COUNTERS over the window."""


def read(ctx):
    d = ctx["dispatch"]
    n = len(ctx["records"])
    return (d["device_rounds"] + d["device_topup_rounds"]) / n if n else None
