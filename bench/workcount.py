"""Work the sampling laws must do, counted from their parameters, and the
chip's published peaks.

The descent kernel writes two int32 configuration ids per candidate slot.
A roofline share divides the bytes the LAW needs (not the bytes the
program happens to move) by the kernel's device time and the HBM peak.
"""

from __future__ import annotations

import json
import math
import pathlib

import numpy as np

from bench import reference

BYTES_PER_SLOT = 8  # two int32 configuration ids
PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peak(device_kind: str, path: pathlib.Path = PEAKS) -> dict:
    """Published peaks of one chip; a kind not in the table is an error."""
    table = json.loads(path.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}")
    return table[device_kind]


def kpgm_expected_distinct(theta: np.ndarray, d: int, slots: int) -> float:
    """Expected distinct cells among ``slots`` draws of a d-level KPGM with
    one 2x2 initiator: a sum over the multinomial classes of cells."""
    t = np.asarray(theta, np.float64).reshape(4) / float(np.sum(theta))
    total = 0.0
    for n0 in range(d + 1):
        for n1 in range(d + 1 - n0):
            for n2 in range(d + 1 - n0 - n1):
                n3 = d - n0 - n1 - n2
                cells = math.factorial(d) // (
                    math.factorial(n0) * math.factorial(n1)
                    * math.factorial(n2) * math.factorial(n3)
                )
                p = t[0] ** n0 * t[1] ** n1 * t[2] ** n2 * t[3] ** n3
                if p > 0.0:
                    total += cells * -math.expm1(slots * math.log1p(-p))
    return total


def kpgm_slots(theta: np.ndarray, d: int, num_edges: int) -> int:
    """Slots the law expects to draw before it has ``num_edges`` distinct
    cells (the smallest N whose expected distinct count reaches it)."""
    lo, hi = num_edges, num_edges
    while kpgm_expected_distinct(theta, d, hi) < num_edges:
        lo, hi = hi, hi * 2
        if hi > 1 << 40:
            raise ValueError("edge count out of reach of this initiator")
    while hi - lo > 1:
        mid = (lo + hi) // 2
        if kpgm_expected_distinct(theta, d, mid) >= num_edges:
            hi = mid
        else:
            lo = mid
    return hi


def candidates_per_graph(cfg: dict) -> int:
    """Candidate slots one graph of the configuration's law needs."""
    if cfg["law"] == "magm_exact_cells":
        d = int(cfg["log2_nodes"])
        thetas = np.broadcast_to(np.asarray(cfg["theta"], np.float32), (d, 2, 2))
        return int(cfg["blocks"]) ** 2 * reference.proposal_budget(thetas)
    if cfg["law"] == "kpgm_first_distinct":
        d = int(cfg["scale"])
        return kpgm_slots(cfg["initiator"], d, int(cfg["edge_factor"]) << d)
    raise KeyError(f"no work count for law {cfg['law']!r}")


def descent_bytes(candidates: int) -> int:
    """HBM bytes the descent must write for ``candidates`` slots."""
    return BYTES_PER_SLOT * int(candidates)
