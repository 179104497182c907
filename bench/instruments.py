"""The benchmark's own spans, and its count of compilations.

Spans are kept in memory and, while a trace runs, also written into the
profiler's trace as ``bench.<name>`` host annotations, on the device
trace's clock.  Compilations are counted from JAX's monitoring events:
every backend compile request, served from the persistent cache or not.
"""

from __future__ import annotations

import collections
import contextlib
import time
from typing import Dict, List, Tuple

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_MISS = "/jax/compilation_cache/cache_misses"

# JAX's listeners are process-wide and cannot be removed, so one listener
# feeds one process-wide tally; CompileLog reads differences of it
_TALLY: collections.Counter = collections.Counter()
_LISTENING = False


def _on_event(event: str, **_kw) -> None:
    _TALLY[event] += 1


def _on_duration(event: str, duration: float, **_kw) -> None:
    _TALLY[event] += 1
    _TALLY[event + ":s"] += duration


class CompileLog:
    """Compilations, persistent-cache hits and misses since ``mark()``."""

    def __init__(self):
        global _LISTENING
        import jax

        if not _LISTENING:
            jax.monitoring.register_event_listener(_on_event)
            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            _LISTENING = True
        self._base = collections.Counter(_TALLY)

    def mark(self) -> None:
        self._base = collections.Counter(_TALLY)

    def since_mark(self) -> Dict[str, float]:
        return {
            "compiles": _TALLY[BACKEND_COMPILE] - self._base[BACKEND_COMPILE],
            "compile_s": _TALLY[BACKEND_COMPILE + ":s"]
            - self._base[BACKEND_COMPILE + ":s"],
            "cache_hits": _TALLY[CACHE_HIT] - self._base[CACHE_HIT],
            "cache_misses": _TALLY[CACHE_MISS] - self._base[CACHE_MISS],
        }


class Spans:
    """Named host spans: ``with spans("graph"): ...``."""

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.records: List[Tuple[str, float, float]] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        if self.annotate:
            import jax

            ann = jax.profiler.TraceAnnotation("bench." + name)
        else:
            ann = contextlib.nullcontext()
        t0 = time.perf_counter()
        try:
            with ann:
                yield
        finally:
            self.records.append((name, t0, time.perf_counter()))

    def total(self, name: str) -> float:
        return sum(e - s for n, s, e in self.records if n == name)
