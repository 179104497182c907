"""The program's spans and counters.

Spans time the host side of a sampler call, layer by layer::

    from repro import tracing

    tracing.enable()
    sampler.sample(key)
    for rec in tracing.records():
        print(rec.name, (rec.t1_ns - rec.t0_ns) / 1e6, "ms", rec.parent)
    tracing.disable()

Tracing is off by default.  Off, :func:`span` returns one shared no-op
object after a single check: it records nothing, reads no clock and never
waits for the device.  On, each span closed appends a :class:`Record` to
an in-memory list, timed with ``time.perf_counter_ns()``.  A span opened
with no span open on its thread is a root: it mints a new ``call_id``,
which every span opened inside it carries.  No span goes inside jitted
code, and none writes into a profiler trace, so a trace taken at the same
time keeps its own host events as they were.

A sampler call opens its root with :func:`root`.  A root's record also
holds the rise of every counter over the call (``attrs["counters"]``).
A call begun while a JAX profiler trace is being captured in this process
is recorded even with tracing off, and its root holds the capture's
directory (``attrs["capture"]``): its spans share the clock of the
trace's host events, so they can be laid over that trace.  Off, with no
capture, a root costs one look at JAX's profiler state per call.

Counters always count, as plain integer increments on dicts that their
modules own.  :data:`COUNTERS` holds each such dict by identity under a
namespace (``quilt.dispatch`` is ``quilt.DISPATCH_COUNTERS`` itself), and
:func:`snapshot` reads them all under ``<namespace>.<name>``.
"""

from __future__ import annotations

import functools
import itertools
import resource
import sys
import threading
import time
from typing import Dict, List, NamedTuple, Optional

__all__ = [
    "COUNTERS",
    "Record",
    "disable",
    "enable",
    "records",
    "register",
    "reset",
    "root",
    "snapshot",
    "span",
    "traced",
]


class Record(NamedTuple):
    """One closed span: times in ``perf_counter_ns`` nanoseconds; ``parent``
    is the name of the span it was opened in (None for a root)."""

    name: str
    t0_ns: int
    t1_ns: int
    parent: Optional[str]
    call_id: int
    attrs: dict


_ENABLED = False  # enable() / disable()
_ON = False  # spans record: enabled, or inside a call begun under a capture
_RECORDS: List[Record] = []
_CALL_IDS = itertools.count(1)
_OPEN = threading.local()  # .stack: the spans open on this thread

COUNTERS: Dict[str, Dict[str, int]] = {}


def enable() -> None:
    """Start recording spans (records already taken are kept)."""
    global _ENABLED, _ON
    _ENABLED = _ON = True


def disable() -> None:
    """Stop recording; spans open now still close into the records."""
    global _ENABLED, _ON
    _ENABLED = _ON = False


def records() -> List[Record]:
    """The spans closed so far, in the order they closed."""
    return list(_RECORDS)


def reset() -> None:
    """Drop every record."""
    _RECORDS.clear()


def _faults() -> tuple:
    ru = resource.getrusage(resource.RUSAGE_SELF)
    return ru.ru_minflt, ru.ru_majflt


class _Off:
    """The span of a disabled tracer."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_OFF = _Off()


class _Span:
    __slots__ = (
        "name", "attrs", "faults", "t0", "parent", "call_id", "f0", "stack"
    )

    def __init__(self, name: str, faults: bool, attrs: dict):
        self.name = name
        self.faults = faults
        self.attrs = attrs

    def __enter__(self):
        stack = getattr(_OPEN, "stack", None)
        if stack is None:
            stack = _OPEN.stack = []
        if stack:
            self.parent, self.call_id = stack[-1].name, stack[-1].call_id
        else:
            self.parent, self.call_id = None, next(_CALL_IDS)
        stack.append(self)
        self.stack = stack
        if self.faults:
            self.f0 = _faults()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        if self.faults:
            minflt, majflt = _faults()
            self.attrs["minflt"] = minflt - self.f0[0]
            self.attrs["majflt"] = majflt - self.f0[1]
        stack = self.stack
        # a span held open by a suspended generator may close out of order
        if stack[-1] is self:
            stack.pop()
        else:
            stack.remove(self)
        _RECORDS.append(
            Record(self.name, self.t0, t1, self.parent, self.call_id, self.attrs)
        )
        return False


class _Root(_Span):
    __slots__ = ("capture", "c0")

    def __init__(self, name: str, attrs: dict, capture: Optional[str]):
        super().__init__(name, False, attrs)
        self.capture = capture

    def __enter__(self):
        global _ON
        if self.capture is not None:
            _ON = True
            self.attrs["capture"] = self.capture
        self.c0 = snapshot()
        return super().__enter__()

    def __exit__(self, *exc):
        global _ON
        c1 = snapshot()
        self.attrs["counters"] = {
            k: v - self.c0.get(k, 0) for k, v in c1.items()
            if v != self.c0.get(k, 0)
        }
        if self.capture is not None:
            _ON = _ENABLED
        return super().__exit__(*exc)


def _capture() -> Optional[str]:
    """The directory of the JAX profiler trace being captured in this
    process, or None.  JAX keeps no public flag for it; its profiler
    module's session state is read without importing JAX (with that module
    not loaded, nothing is being captured)."""
    profiler = sys.modules.get("jax._src.profiler")
    state = getattr(profiler, "_profile_state", None)
    if getattr(state, "profile_session", None) is None:
        return None
    return str(state.log_dir)


def span(name: str, *, faults: bool = False, **attrs):
    """Context manager timing one piece of host work as ``name``.

    ``attrs`` are stored with the record; ``faults=True`` adds the
    process's minor and major page faults over the span (``minflt``,
    ``majflt``, from ``getrusage``).  Off, returns a shared no-op."""
    if not _ON:
        return _OFF
    return _Span(name, faults, attrs)


def root(name: str, **attrs):
    """:func:`span` for the entry point of a sampler call: its record also
    holds the rise of every counter over the call, and a call begun while
    a JAX profiler trace is being captured is recorded with tracing off."""
    if _ON:
        return _Root(name, attrs, None)
    capture = _capture()
    if capture is None:
        return _OFF
    return _Root(name, attrs, capture)


def traced(name: str, *, is_root: bool = False):
    """Decorator: run the function inside ``span(name)``, or inside
    ``root(name)`` with ``is_root=True``."""

    def wrap(fn):
        if is_root:

            @functools.wraps(fn)
            def call_root(*args, **kwargs):
                with root(name):
                    return fn(*args, **kwargs)

            return call_root

        @functools.wraps(fn)
        def inner(*args, **kwargs):
            if not _ON:
                return fn(*args, **kwargs)
            with _Span(name, False, {}):
                return fn(*args, **kwargs)

        return inner

    return wrap


def register(namespace: str, counters: Dict[str, int]) -> Dict[str, int]:
    """Put a module's counter dict in :data:`COUNTERS` (by identity, so
    its module's readers and :func:`snapshot` see one store) and return
    it; a namespace holds one dict, the last registered."""
    COUNTERS[namespace] = counters
    return counters


def snapshot() -> Dict[str, int]:
    """Every registered counter as ``{"<namespace>.<name>": value}``."""
    return {
        f"{ns}.{k}": v for ns, d in COUNTERS.items() for k, v in d.items()
    }
