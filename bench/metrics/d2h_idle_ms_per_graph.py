"""Device idle time per graph while the host is inside a device-to-host
copy (JAX's ``np.asarray(jax.Array)`` event on the benchmark's thread):
how long the chip waits on the copy of the candidate buffers.  The v5e
trace carries no device-side event for the transfer itself."""

from bench.metrics._trace import idle_ms_per_graph


def read(ctx):
    return idle_ms_per_graph(ctx, "np.asarray(jax.Array)")
