"""The one traffic generator: drives a sampler as a traffic mix file says.

A mix (``bench/traffic/<mix>.json``) is data:

- ``call``: ``sample`` (one whole edge list per graph) or ``sample_stream``
  (fixed-size chunks, every one handed to the consumer);
- ``chunk_edges``: the chunk size of ``sample_stream``;
- ``loop``: ``closed``, with ``clients`` (1): each client asks for its next
  graph when the last one has arrived.

A graph fails when its call raises, or when any of the program's fallback
counters moved while it was sampled: a host path is not the system under
test.  A failed graph's edges are not delivered work.
"""

from __future__ import annotations

import time
import traceback
from typing import Callable, Dict, List, Tuple

import numpy as np

CALLS = ("sample", "sample_stream")


def validate(mix: dict) -> None:
    if mix.get("call") not in CALLS:
        raise ValueError(f"mix call must be one of {CALLS}, got {mix.get('call')!r}")
    if mix.get("loop") != "closed" or int(mix.get("clients", 0)) != 1:
        raise ValueError("only a closed loop with one client is generated")
    if mix["call"] == "sample_stream" and int(mix.get("chunk_edges", 0)) < 1:
        raise ValueError("sample_stream needs chunk_edges >= 1")


def fallback_counts() -> Dict[str, int]:
    """The program's fallback counters, over every engine that keeps them."""
    from repro.core import balldrop, quilt

    return {
        f"{mod.__name__}.{k}": mod.DISPATCH_COUNTERS[k]
        for mod in (quilt, balldrop)
        for k in quilt.FALLBACK_COUNTERS
    }


def one_graph(target, mix: dict, key, spans) -> dict:
    """Sample one graph as the mix says.  The record keeps the pieces the
    consumer received (one whole edge list, or every chunk)."""
    before = fallback_counts()
    t0 = time.perf_counter()
    t_first = None
    pieces: List[np.ndarray] = []
    error = None
    with spans("graph"):
        try:
            if mix["call"] == "sample":
                pieces.append(target.sample(key))
                t_first = time.perf_counter()
            else:
                for chunk in target.stream(key, int(mix["chunk_edges"])):
                    if t_first is None:
                        t_first = time.perf_counter()
                    pieces.append(chunk)
        except Exception:  # a failed graph is counted, and the run goes on
            error = traceback.format_exc(limit=4)
    t_end = time.perf_counter()
    after = fallback_counts()
    moved = {k: after[k] - before[k] for k in after if after[k] != before[k]}
    return {
        "t0": t0,
        "t_first": t_first if t_first is not None else t_end,
        "t_end": t_end,
        "pieces": pieces,
        "num_edges": sum(int(p.shape[0]) for p in pieces),
        "failed": error is not None or bool(moved),
        "error": error or (f"fallback counters moved: {moved}" if moved else None),
    }


def edges(rec: dict) -> np.ndarray:
    """The (E, 2) edge list a record's consumer received."""
    if not rec["pieces"]:
        return np.zeros((0, 2), np.int64)
    return np.concatenate(rec["pieces"])


def drive(target, mix: dict, key_of: Callable, seconds: float, spans,
          keep: Callable) -> Tuple[List[dict], float, float]:
    """Closed loop, one client: graphs back to back until the first one
    that completes at or after ``seconds``.  ``keep(index, record)`` sees
    every record (with its edges) before the edges are dropped.  Returns
    the records and the window's opening and closing times."""
    records: List[dict] = []
    t_open = time.perf_counter()
    i = 0
    while True:
        rec = one_graph(target, mix, key_of(i), spans)
        keep(i, rec)
        rec["pieces"] = None
        records.append(rec)
        i += 1
        if rec["t_end"] - t_open >= seconds:
            return records, t_open, rec["t_end"]
