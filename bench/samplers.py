"""Build the system under test, and its inputs, from a configuration file.

A configuration names its ``law``; each law has one builder here, which
makes the program's sampler through its public session API
(``repro.api``) and the data the reference needs to check it.  Every
input comes from ``--seed``: a MAGM job's attribute matrix, and the key
of every graph.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import numpy as np

from bench import reference


def base_key(seed: int):
    """PRNG key of a run; seeds past 32 bits fold their high word in."""
    import jax

    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def graph_key(base, index: int):
    import jax

    return jax.random.fold_in(base, index)


def attributes(n: int, d: int, mu: float, blocks: int, seed: int) -> np.ndarray:
    """F ~ Bernoulli(mu)^(n x d) from ``seed``, redrawn from the same
    stream until its largest configuration multiplicity (the number of
    Theorem-2 blocks) is ``blocks``: every seed's round has the same size."""
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 0xA77])
    for _ in range(1000):
        F = (rng.random((n, d)) < mu).astype(np.int8)
        if np.bincount(reference.configurations(F)).max() == blocks:
            return F
    raise ValueError(f"no attribute draw with {blocks} blocks in 1000 tries")


@dataclasses.dataclass
class Target:
    """The sampler under test and what the reference needs to check it."""

    sampler: Any
    sample: Callable  # key -> (E, 2) edges on the host
    stream: Callable  # (key, chunk_edges) -> iterator of edge chunks
    check: Callable  # (key, ft) -> (reference edges, slots drawn)


def build_magm(cfg: dict, seed: int) -> Target:
    from repro.api import MAGMSampler, SamplerConfig
    from repro.core import magm

    d = int(cfg["log2_nodes"])
    theta = np.asarray(cfg["theta"], np.float32)
    F = attributes(1 << d, d, float(cfg["mu"]), int(cfg["blocks"]), seed)
    sampler = MAGMSampler(
        SamplerConfig(
            params=magm.make_params(theta, mu=float(cfg["mu"]), d=d),
            F=F,
            backend=cfg["backend"],
        )
    )
    thetas = np.broadcast_to(theta, (d, 2, 2)).copy()
    return Target(
        sampler=sampler,
        sample=lambda key: sampler.sample(key).edges,
        stream=lambda key, chunk: sampler.sample_stream(key, chunk_edges=chunk),
        check=lambda key, ft: reference.magm_exact(F, thetas, key, ft),
    )


def build_kpgm(cfg: dict, seed: int) -> Target:
    from repro.api import KPGMSampler, SamplerConfig
    from repro.core import kpgm

    d = int(cfg["scale"])
    theta = np.asarray(cfg["initiator"], np.float32)
    edges = int(cfg["edge_factor"]) << d
    sampler = KPGMSampler(
        SamplerConfig(params=kpgm.make_params(theta, d=d), backend=cfg["backend"])
    )
    thetas = np.broadcast_to(theta, (d, 2, 2)).copy()
    return Target(
        sampler=sampler,
        sample=lambda key: sampler.sample(key, num_edges=edges).edges,
        stream=lambda key, chunk: sampler.sample_stream(
            key, chunk_edges=chunk, num_edges=edges
        ),
        check=lambda key, ft: reference.kpgm_first_distinct(
            thetas, edges, key, ft
        ),
    )


BUILDERS = {"magm_exact_cells": build_magm, "kpgm_first_distinct": build_kpgm}


def build(cfg: dict, seed: int) -> Target:
    return BUILDERS[cfg["law"]](cfg, seed)
