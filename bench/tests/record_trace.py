#!/usr/bin/env python3
"""Record the small TPU trace that ``test_trace_reduce.py`` reads.

    python3 bench/tests/record_trace.py bench/tests/data/small.xplane.pb

Runs, on one TPU, under the benchmark's own profiler options and spans:
a ``bench.window`` span holding two ``bench.graph`` spans, each of which
runs the program's Pallas descent kernel, an XLA sort and an XLA gather.
Between the two graphs the host sleeps ``GAP_S`` inside the window span
only, then runs one small op; inside the second graph it sleeps ``GAP_S``
before its device work.  So the trace has two idle gaps of at least
``GAP_S``, one attributed to ``bench.window`` and one to ``bench.graph``.
"""

import pathlib
import shutil
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
GAP_S = 0.2
SLOTS = 1 << 16


def main(out: str) -> int:
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    import jax.numpy as jnp

    from bench import instruments, run
    from repro.kernels import quadrant_descent as qd

    run.chip_devices(1)
    seed = jnp.asarray([[3, 5]], jnp.int32)
    cum = jnp.cumsum(jnp.full((8, 4), 0.25, jnp.float32), axis=1)
    gids = jnp.zeros((1,), jnp.int32)
    table = jnp.arange(1 << 16, dtype=jnp.int32)[::-1]

    @jax.jit
    def after(src, dst):
        key = jnp.sort(src * 256 + dst)
        return jnp.take(table, key & 0xFFFF)

    def graph():
        src, dst = qd.descent_prng(seed, gids, cum, a_tot=SLOTS, interpret=False)
        return after(src, dst).block_until_ready()

    def between():
        return jnp.add(table, 1).block_until_ready()

    graph()  # compile outside the trace
    between()
    trace_dir = ROOT / ".bench_trace" / "record"
    spans = instruments.Spans(annotate=True)
    run.start_trace(trace_dir)
    with spans("window"):
        with spans("graph"):
            graph()
        time.sleep(GAP_S)
        between()
        with spans("graph"):
            time.sleep(GAP_S)
            graph()
    jax.profiler.stop_trace()
    pathlib.Path(out).parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(run.trace_file(trace_dir), out)
    print(f"wrote {out}: {pathlib.Path(out).stat().st_size} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
