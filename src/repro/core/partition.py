"""Theorem-2 partition of nodes by attribute-configuration occurrence rank.

Z_i := { j <= i : lambda_j = lambda_i };  D_c := { i : |Z_i| = c }.

Within every D_c the configuration map lambda is injective, and the number of
non-empty sets B = max_i |Z_i| is the minimum achievable by ANY partition with
that injectivity property (pigeon-hole; paper Theorem 2).
"""

from __future__ import annotations

from typing import List, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


def occurrence_ranks_np(lam: np.ndarray) -> np.ndarray:
    """|Z_i| for every node (1-based), vectorised with a stable sort.

    After a stable argsort of lam, equal configurations form contiguous runs in
    original-index order, so the within-run position is exactly |Z_i| - 1.
    """
    lam = np.asarray(lam)
    n = lam.shape[0]
    order = np.argsort(lam, kind="stable")
    sorted_lam = lam[order]
    run_start = np.zeros(n, dtype=np.int64)
    new_run = np.empty(n, dtype=bool)
    new_run[0] = True
    new_run[1:] = sorted_lam[1:] != sorted_lam[:-1]
    run_start = np.maximum.accumulate(np.where(new_run, np.arange(n), 0))
    rank_sorted = np.arange(n) - run_start + 1
    ranks = np.empty(n, dtype=np.int64)
    ranks[order] = rank_sorted
    return ranks


def occurrence_ranks(lam: jax.Array) -> jax.Array:
    """JAX (jit-able, fixed-shape) version of :func:`occurrence_ranks_np`."""
    n = lam.shape[0]
    order = jnp.argsort(lam, stable=True)
    sorted_lam = lam[order]
    new_run = jnp.concatenate(
        [jnp.array([True]), sorted_lam[1:] != sorted_lam[:-1]]
    )
    idx = jnp.arange(n)
    run_start = jax.lax.associative_scan(jnp.maximum, jnp.where(new_run, idx, 0))
    rank_sorted = idx - run_start + 1
    return jnp.zeros(n, dtype=rank_sorted.dtype).at[order].set(rank_sorted)


class Partition(NamedTuple):
    """D_1..D_B as index arrays plus per-set sorted config lookup tables."""

    ranks: np.ndarray  # (n,) |Z_i|
    B: int
    sets: List[np.ndarray]  # D_c: original node indices, c = 1..B
    sorted_configs: List[np.ndarray]  # lambda values of D_c, ascending
    sorted_nodes: List[np.ndarray]  # node ids aligned with sorted_configs


def build_partition(lam: np.ndarray) -> Partition:
    lam = np.asarray(lam)
    ranks = occurrence_ranks_np(lam)
    B = int(ranks.max()) if lam.size else 0
    sets, scfg, snode = [], [], []
    for c in range(1, B + 1):
        members = np.nonzero(ranks == c)[0]
        cfg = lam[members]
        o = np.argsort(cfg)
        sets.append(members)
        scfg.append(cfg[o])
        snode.append(members[o])
    return Partition(ranks=ranks, B=B, sets=sets, sorted_configs=scfg, sorted_nodes=snode)


CFG_SENTINEL = np.int32(2**31 - 1)  # larger than any d<=31 config id


def dense_inverse(part: Partition, d: int) -> np.ndarray:
    """(B, 2^d) int32 map config -> node id per block (-1 when absent).

    The config space of a d-attribute MAGM is exactly the KPGM node space
    2^d, so for moderate d a dense inverse turns the per-candidate block
    lookup into a single gather — the CPU fast path.  O(B * 2^d) memory;
    callers gate on size (core/quilt.py).
    """
    inv = np.full((part.B, 1 << d), -1, dtype=np.int32)
    for b in range(part.B):
        inv[b, part.sorted_configs[b]] = part.sorted_nodes[b]
    return inv


def lookup_nodes(
    sorted_configs: np.ndarray, sorted_nodes: np.ndarray, configs: np.ndarray
) -> np.ndarray:
    """Map sampled configuration ids -> node ids in one D_c; -1 when absent."""
    pos = np.searchsorted(sorted_configs, configs)
    pos_c = np.minimum(pos, max(sorted_configs.size - 1, 0))
    if sorted_configs.size == 0:
        return np.full(configs.shape, -1, dtype=np.int64)
    hit = sorted_configs[pos_c] == configs
    return np.where(hit, sorted_nodes[pos_c], -1)


def is_valid_partition(lam: np.ndarray, sets: List[np.ndarray]) -> bool:
    """Checks the injectivity invariant and coverage (used by property tests)."""
    lam = np.asarray(lam)
    seen = np.zeros(lam.shape[0], dtype=bool)
    for members in sets:
        if np.unique(lam[members]).size != members.size:
            return False  # two nodes in one set share a configuration
        if seen[members].any():
            return False  # not a partition
        seen[members] = True
    return bool(seen.all())


def min_partition_size(lam: np.ndarray) -> int:
    """Pigeon-hole lower bound = max multiplicity of any configuration."""
    if np.asarray(lam).size == 0:
        return 0
    _, counts = np.unique(np.asarray(lam), return_counts=True)
    return int(counts.max())
