"""Device-to-host transfers of the sampling engines, and the counters of
the engines' work.

Every transfer a sampler call makes goes through :func:`to_host`, inside a
``quilt.copy`` span.  :data:`ENGINE_COUNTERS`, read as ``quilt.<name>``
through repro.tracing: ``candidate_slots`` drawn by the device rounds,
``kernel_lookup_slots``, those of them whose config -> node lookup ran
inside the descent kernel (``quilt.kernel_lookup``), ``dispatched_slots``,
the slots the quilt's round programs ran, padding rows included (a split
round's padded last sub-round, mesh padding, a top-up's re-drawn
prefix), ``kept_edges`` emitted from the candidate buffers (counted from
the emitted arrays' lengths), ``d2h_bytes`` moved by :func:`to_host`.
"""

from __future__ import annotations

import jax
import numpy as np

from repro import tracing

__all__ = ["ENGINE_COUNTERS", "to_host"]

ENGINE_COUNTERS = tracing.register(
    "quilt",
    {"candidate_slots": 0, "dispatched_slots": 0, "kernel_lookup_slots": 0,
     "kept_edges": 0, "d2h_bytes": 0},
)


def to_host(x, *, moves: bool = True) -> np.ndarray:
    """``jax.device_get(x)`` inside a ``quilt.copy`` span (attrs ``bytes``,
    and the page faults taken while tracing is on).  ``moves=False`` marks
    a second fetch of an array already fetched whole: JAX serves it from
    the host copy it keeps, so it counts 0 bytes in ``quilt.d2h_bytes``,
    as does a host array."""
    nbytes = int(x.nbytes) if moves and isinstance(x, jax.Array) else 0
    ENGINE_COUNTERS["d2h_bytes"] += nbytes
    with tracing.span("quilt.copy", faults=True, bytes=nbytes):
        return jax.device_get(x)
