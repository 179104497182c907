#!/usr/bin/env python3
"""Reduce a JAX profiler trace to device busy time, op classes and gaps.

    python bench/trace_reduce.py <file.xplane.pb>          # the summary
    python bench/trace_reduce.py <file.xplane.pb> --dump   # planes, lines,
                                                           # busiest names

``jax.profiler.ProfileData`` reads the ``.xplane.pb``.  Device planes are
``/device:<KIND>:<n>``; on each, the ``XLA Ops`` line holds one event per
HLO operation run, named by its HLO text (``%sort.14 = (...) sort(...)``).
Busy time is the union of those intervals inside the window, averaged over
the devices; every maximal interval with no op running is an idle gap.
A gap is split at the boundaries of the events on the host thread that
holds the benchmark's spans, and each piece goes to the innermost event
open over it (a ``bench.*`` span, or one of JAX's own events there, such
as ``np.asarray(jax.Array)`` for a device-to-host copy), so a gap that
spans a copy and the host code after it is shared between the two by
their overlap with it.
"""

from __future__ import annotations

import argparse
import bisect
import json
import re
import sys
from typing import Dict, Iterable, List, Sequence, Tuple

SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
COPY_OPS = frozenset({
    "copy", "copy-start", "copy-done", "slice", "dynamic-slice",
    "dynamic-update-slice", "transpose",
})
TOP = 10

Interval = Tuple[float, float]
Op = Tuple[str, float, float]  # HLO text, start_ns, end_ns


def opcode(name: str) -> str:
    """HLO opcode of an op event named by its HLO text, ``%x = <shape>
    <opcode>(...)``; the shape may be a tuple with nested parentheses."""
    _, sep, rest = name.partition(" = ")
    if not sep:
        return ""
    i = 0
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        i += 1
    m = re.match(r"\S*\s+([a-z][\w-]*)\(", rest[i:])
    return m.group(1) if m else ""


def classify(name: str) -> str:
    """Layer class of a device op.  The v5e lowers a gather to a fusion
    of kind ``kCustom`` (a ``jnp.take`` shows so in the recorded trace),
    and a Pallas kernel to a ``tpu_custom_call``."""
    op = opcode(name)
    if op == "custom-call" and "tpu_custom_call" in name:
        return "descent"
    if op == "sort":
        return "sort"
    if op == "gather" or (op == "fusion" and "kind=kCustom" in name):
        return "gather"
    if op in COPY_OPS:
        return "copy"
    return "other"


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Union of intervals as sorted, disjoint intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """Maximal intervals of [lo, hi] that no busy interval covers."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


class Timeline:
    """What one host thread was doing at a time: the innermost of its
    (properly nested) events open then, found by bisection and a walk up
    the enclosing events."""

    def __init__(self, events: Sequence[Tuple[str, float, float]]):
        self.events = sorted(events, key=lambda ev: (ev[1], -ev[2]))
        self.starts = [s for _, s, _ in self.events]
        self.parent: List[int] = []
        stack: List[int] = []
        for i, (_, s, _) in enumerate(self.events):
            while stack and self.events[stack[-1]][2] <= s:
                stack.pop()
            self.parent.append(stack[-1] if stack else -1)
            stack.append(i)
        self.bounds = sorted({t for _, s, e in self.events for t in (s, e)})

    def label(self, t: float) -> str:
        """Innermost event open at time t, or ``outside``."""
        i = bisect.bisect_right(self.starts, t) - 1
        while i >= 0 and self.events[i][2] < t:
            i = self.parent[i]
        return self.events[i][0] if i >= 0 else "outside"

    def split(self, lo: float, hi: float) -> List[Tuple[str, float]]:
        """[lo, hi] cut at every event boundary inside it, as (innermost
        event, length) pieces in time order; neighbours of one event are
        joined."""
        i = bisect.bisect_right(self.bounds, lo)
        j = bisect.bisect_left(self.bounds, hi)
        cuts = [lo] + self.bounds[i:j] + [hi]
        pieces: List[Tuple[str, float]] = []
        for a, b in zip(cuts, cuts[1:]):
            name = self.label((a + b) / 2)
            if pieces and pieces[-1][0] == name:
                pieces[-1] = (name, pieces[-1][1] + b - a)
            else:
                pieces.append((name, b - a))
        return pieces


def reduce(
    device_ops: Dict[str, List[Op]],
    host: Sequence[Tuple[str, float, float]],
    window: Interval,
) -> dict:
    """Summary of the device ops of each device (times in ns) over the
    window; seconds are averaged over the devices."""
    lo, hi = window
    ndev = max(len(device_ops), 1)
    busy_ns = 0.0
    by_class: Dict[str, float] = {}
    by_name: Dict[str, float] = {}
    gap_list: List[Tuple[str, float]] = []
    timeline = Timeline(host)
    for ops in device_ops.values():
        merged = merge(clip(((s, e) for _, s, e in ops), lo, hi))
        busy_ns += sum(e - s for s, e in merged)
        for name, s, e in ops:
            dur = max(min(e, hi) - max(s, lo), 0.0)
            if dur <= 0.0:
                continue
            cls = classify(name)
            by_class[cls] = by_class.get(cls, 0.0) + dur
            short = f"{name.partition(' = ')[0]} ({cls})"
            by_name[short] = by_name.get(short, 0.0) + dur
        for s, e in gaps(merged, lo, hi):
            gap_list.extend(timeline.split(s, e))
    gap_by_span: Dict[str, float] = {}
    for name, dur in gap_list:
        gap_by_span[name] = gap_by_span.get(name, 0.0) + dur
    ns = 1e-9 / ndev
    return {
        "devices": len(device_ops),
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_ns * ns,
        "class_s": {k: v * ns for k, v in sorted(by_class.items())},
        "top_ops": [
            [n, v * ns]
            for n, v in sorted(by_name.items(), key=lambda x: -x[1])[:TOP]
        ],
        "idle_gaps": [
            [n, v * 1e-9] for n, v in sorted(gap_list, key=lambda x: -x[1])[:TOP]
        ],
        "idle_by_span_s": {k: v * ns for k, v in sorted(gap_by_span.items())},
    }


def _stats(obj) -> dict:
    try:
        return {k: v for k, v in obj.stats}
    except (TypeError, ValueError):
        return {}


def read(path: str, span_prefix: str = SPAN_PREFIX):
    """(device ops per device plane, host events, window) of a trace.

    The host events are those of the thread that holds the
    ``<span_prefix>window`` span, which is the window."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device_ops: Dict[str, List[Op]] = {}
    host: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        if re.match(r"^/device:[A-Z]+:\d+$", plane.name) and "CPU" not in plane.name:
            device_ops[plane.name] = [
                (ev.name, ev.start_ns, ev.end_ns)
                for line in plane.lines
                if line.name == OPS_LINE
                for ev in line.events
            ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events = [(ev.name, ev.start_ns, ev.end_ns) for ev in line.events]
                if any(n == span_prefix + "window" for n, _, _ in events):
                    host.extend(events)
    windows = [(s, e) for n, s, e in host if n == span_prefix + "window"]
    if len(windows) != 1:
        raise ValueError(f"{path}: {len(windows)} {span_prefix}window spans, not 1")
    return device_ops, host, windows[0]


def reduce_file(path: str) -> dict:
    return reduce(*read(path))


def dump(path: str, top: int = 12) -> None:
    """Print every plane and line with its busiest event names."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        print(f"PLANE {plane.name!r} stats={_stats(plane)}")
        for line in plane.lines:
            evs = list(line.events)
            tot: Dict[str, float] = {}
            for ev in evs:
                tot[ev.name] = tot.get(ev.name, 0.0) + ev.duration_ns
            first = (evs[0].start_ns, _stats(evs[0])) if evs else None
            print(f"  LINE {line.name!r} events={len(evs)} first={first}")
            for n, v in sorted(tot.items(), key=lambda x: -x[1])[:top]:
                print(f"    {v / 1e6:12.3f} ms  {n}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path")
    ap.add_argument("--dump", action="store_true")
    args = ap.parse_args(argv)
    if args.dump:
        dump(args.path)
    else:
        print(json.dumps(reduce_file(args.path), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
