"""Seconds to build the sampler (attribute draw, Theorem-2 plan, lookup
tables): the benchmark's span around the session's construction."""


def read(ctx):
    return ctx["spans"].total("plan_build")
