"""The program's own spans (``repro.tracing``) over a traced window.

A sampler call begun while the JAX profiler captures a trace records its
spans in memory (``repro.tracing.root``), on ``time.perf_counter_ns()``,
the clock of the benchmark's ``Spans``.  :func:`window_records` takes those
of the window; :func:`map_spans` lays them onto the trace's clock by one
offset, the start of ``bench.window`` in the trace less its start in
``Spans``; :func:`attribute` puts the device's idle gaps down to them, as
``trace_reduce.reduce`` does to the benchmark's own spans, and checks that
JAX's copy events lie inside the program's copy spans.

:func:`window` computes all of it once per run, reading the trace the
profiler wrote to the capture directory the records name, and prints the
idle split, its coverage and the copies' misalignment on stderr.  For a
program without ``repro.tracing``, or one that recorded nothing in the
window, it is None.
"""

from __future__ import annotations

import bisect
import pathlib
import sys
import time
from typing import Dict, List, Optional, Sequence, Tuple

from bench.trace_reduce import SPAN_PREFIX, Op, Interval, Timeline, clip, gaps
from bench.trace_reduce import merge, read

JAX_COPY = "np.asarray(jax.Array)"
PROGRAM_COPY = "quilt.copy"
UNCOVERED = "none"
ROOT_PREFIX = "sampler."
# a record counts as inside the window up to this far past its ends, in ns
# (the window's ends are float seconds)
SLACK_NS = 1000

_LAST: list = [None, None]  # the Spans object of the last run, its result


def window_bounds(spans) -> Optional[Tuple[float, float]]:
    """The window's (start, end) on the host clock, in seconds."""
    found = [(a, b) for n, a, b in spans.records if n == "window"]
    return found[-1] if found else None


def window_records(spans) -> Optional[list]:
    """The program's span records inside the window, or None where the
    program has no tracing module or recorded nothing there."""
    try:
        from repro import tracing
    except ImportError:
        return None
    bounds = window_bounds(spans)
    if bounds is None:
        return None
    lo, hi = bounds[0] * 1e9 - SLACK_NS, bounds[1] * 1e9 + SLACK_NS
    recs = [r for r in tracing.records() if lo <= r.t0_ns and r.t1_ns <= hi]
    return recs or None


def counters(records) -> Dict[str, int]:
    """The rise of each program counter over the window: the sum of the
    rises its root spans (``sampler.*``) record over their calls."""
    out: Dict[str, int] = {}
    for r in records:
        if r.parent is None:
            for k, v in r.attrs.get("counters", {}).items():
                out[k] = out.get(k, 0) + v
    return out


def map_spans(records, bounds: Tuple[float, float], window: Interval) -> tuple:
    """The program's spans as (name, start, end) on the trace's clock, by
    one offset: the window's start in the trace less its start on the host
    clock.  Also returns the drift, the window's length in the trace less
    its length on the host clock, in ns."""
    t0, t1 = bounds
    offset = window[0] - round(t0 * 1e9)
    drift = (window[1] - window[0]) - (t1 - t0) * 1e9
    return [(r.name, r.t0_ns + offset, r.t1_ns + offset) for r in records], drift


def attribute(
    device_ops: Dict[str, List[Op]],
    host: Sequence[Tuple[str, float, float]],
    window: Interval,
    program: Sequence[Tuple[str, float, float]],
) -> dict:
    """Device idle time by the innermost program span open over it, and how
    closely the program's ``quilt.copy`` spans hold JAX's own copy events.

    ``program`` holds the program's spans as (name, start_ns, end_ns) on
    the trace's clock.  Each idle gap of each device is cut at the program
    spans' boundaries and at those of the benchmark's ``bench.graph``
    spans (``host``); a piece under a graph span and no program span is
    ``none``, a piece outside every graph span is left out.  Seconds are
    averaged over the devices, as in ``trace_reduce.reduce``.

    For each ``np.asarray(jax.Array)`` host event in the window, the
    ``quilt.copy`` span that overlaps it most is its copy span, and its
    misalignment is how far the event reaches past that span at either end
    (0 inside it); an event that overlaps no copy span is ``uncovered``."""
    lo, hi = window
    ndev = max(len(device_ops), 1)
    graphs = [(UNCOVERED, s, e) for n, s, e in host if n == SPAN_PREFIX + "graph"]
    timeline = Timeline(list(program) + graphs)
    idle: Dict[str, float] = {}
    for ops in device_ops.values():
        busy = merge(clip(((s, e) for _, s, e in ops), lo, hi))
        for s, e in gaps(busy, lo, hi):
            for name, dur in timeline.split(s, e):
                idle[name] = idle.get(name, 0.0) + dur
    idle.pop("outside", None)

    spans = sorted((s, e) for n, s, e in program if n == PROGRAM_COPY)
    starts = [s for s, _ in spans]
    events = [(s, e) for n, s, e in host if n == JAX_COPY and e > lo and s < hi]
    worst, uncovered = 0.0, 0
    for s, e in events:
        i = bisect.bisect_left(starts, e)
        near = [spans[j] for j in (i - 2, i - 1) if j >= 0]
        best = max(near, key=lambda c: min(e, c[1]) - max(s, c[0]), default=None)
        if best is None or min(e, best[1]) <= max(s, best[0]):
            uncovered += 1
            continue
        worst = max(worst, best[0] - s, e - best[1])
    ns = 1e-9 / ndev
    return {
        "idle_by_program_span_s": {k: v * ns for k, v in sorted(idle.items())},
        "graph_idle_s": sum(idle.values()) * ns,
        "copy_events": len(events),
        "copy_events_uncovered": uncovered,
        "copy_misalignment_s": worst * 1e-9,
    }


def program_line(att: dict, n_graphs: int, drift_ns: float) -> str:
    """One stderr line: idle ms a graph by program span, the share no
    layer's span covers, and how the copy spans hold JAX's copy events.
    The root spans ``sampler.*`` cover a whole call, so what no layer
    covers is their own idle time and the ``none`` class outside them."""
    idle = att["idle_by_program_span_s"]
    n = max(n_graphs, 1)
    table = ", ".join(
        f"{k} {v * 1e3 / n:.1f}"
        for k, v in sorted(idle.items(), key=lambda kv: -kv[1])
    )
    graph = att["graph_idle_s"]
    none = idle.get(UNCOVERED, 0.0)
    roots = sum(v for k, v in idle.items() if k.startswith(ROOT_PREFIX))

    def share(v):
        return 100.0 * v / graph if graph > 0 else 0.0

    return (
        f"program idle ms/graph: {table}; uncovered "
        f"{share(none + roots):.2f}% (none {share(none):.2f}%, sampler.* "
        f"itself {share(roots):.2f}%) of "
        f"{graph * 1e3 / n:.1f} ms idle under bench.graph; "
        f"{att['copy_events']} np.asarray events, "
        f"{att['copy_events_uncovered']} outside every quilt.copy span, "
        f"worst misalignment {att['copy_misalignment_s'] * 1e3:.4f} ms; "
        f"clock drift over the window {drift_ns / 1e6:.4f} ms"
    )


def slowest_copies(records, k: int = 5) -> str:
    """The ``k`` longest ``quilt.copy`` spans: ms, MB, page faults."""
    copies = sorted((r for r in records if r.name == PROGRAM_COPY),
                    key=lambda r: r.t0_ns - r.t1_ns)[:k]
    return "slowest quilt.copy spans (ms, MB, minflt, majflt): " + "; ".join(
        f"{(r.t1_ns - r.t0_ns) / 1e6:.1f}, {r.attrs.get('bytes', 0) / 1e6:.1f}, "
        f"{r.attrs.get('minflt')}, {r.attrs.get('majflt')}"
        for r in copies
    )


def trace_of(records) -> Optional[str]:
    """The trace file of the capture the window's root spans name."""
    dirs = {r.attrs.get("capture") for r in records if r.parent is None}
    dirs.discard(None)
    if len(dirs) != 1:
        return None
    found = sorted(pathlib.Path(dirs.pop()).glob("plugins/profile/*/*.xplane.pb"))
    return str(found[-1]) if found else None


def window(ctx) -> Optional[dict]:
    """``{"records", "counters", "attribution"}`` of a run's window, once
    per run (its ``ctx["spans"]``); ``attribution`` is None without a
    trace of the window.  None where the program recorded nothing."""
    spans = ctx["spans"]
    if _LAST[0] is spans:
        return _LAST[1]
    records = window_records(spans)
    out = None
    if records is not None:
        out = {"records": records, "counters": counters(records),
               "attribution": None}
        path = trace_of(records)
        if path is not None:
            t0 = time.perf_counter()
            device_ops, host, trace_window = read(path)
            mapped, drift = map_spans(records, window_bounds(spans), trace_window)
            out["attribution"] = attribute(device_ops, host, trace_window, mapped)
            print(f"program spans: {len(records)} over "
                  f"{len(ctx['records'])} graphs; trace read and attributed "
                  f"in {time.perf_counter() - t0:.3f} s", file=sys.stderr)
            print(program_line(out["attribution"], len(ctx["records"]), drift),
                  file=sys.stderr)
            print(slowest_copies(records), file=sys.stderr)
    _LAST[:] = [spans, out]
    return out
