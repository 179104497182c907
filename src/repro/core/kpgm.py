"""Stochastic Kronecker Product Graph Model (KPGM), Leskovec et al. (2010).

Edge probability matrix  P = Theta^(1) x Theta^(2) x ... x Theta^(d)
(paper eq. 3) with 2x2 initiator matrices.  Equivalently (paper eq. 6)

    P_ij = prod_k theta^(k)[b_k(i), b_k(j)]

where b_k(i) is the k-th most significant bit of (i-1).  We use 0-based node
ids throughout, so ``P[i, j] = prod_k theta^(k)[bit_k(i), bit_k(j)]``.

Sampling (Algorithm 1 of the paper) is recast as a *batched tensor program*
for TPU (see DESIGN.md section 3): all X candidate edges descend the d levels
simultaneously as a (X, d) uniform tensor compared against per-level cumulative
quadrant probabilities, and the resulting bit-planes are contracted against a
powers-of-two vector to form integer node ids.  No scalar control flow.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dedup

# Most candidates one device round may hold, sized for one TPU v5e (16 GiB
# HBM).  Every round that reads it, compiled for a v5e at n = 2^16 widths
# and this many candidates, needs by compiled.memory_analysis() (temp +
# outputs + arguments): the exact-cell quilt round over 64 graphs 1.189e10 B
# (11.07 GiB, 118 B each), a fused ball-dropping round of 64 samples
# 1.108e10 B, kpgm_sample_many's fused round 3.73e9 B, and the split heavy
# round at its 2^26-slot limit 1.95e9 B — leaving room for the plan and the
# previous round's buffers (tests/test_chip_compile.py holds each under 85%
# of the HBM).  Past the cap a run fails loudly or takes a counted, warned
# fallback.  (The counter-PRNG word bound on slots per GRAPH is separate:
# see kernels.quadrant_descent.PRNG_SLOT_LIMIT.)
DEVICE_MAX_CANDIDATES = 96 << 20


class KPGMParams(NamedTuple):
    """Per-level 2x2 initiator matrices, shape (d, 2, 2), float32 in [0,1]."""

    thetas: jax.Array

    @property
    def d(self) -> int:
        return self.thetas.shape[0]

    @property
    def num_nodes(self) -> int:
        return 1 << self.d


def make_params(theta: np.ndarray, d: int) -> KPGMParams:
    """Replicate one 2x2 initiator at every level (paper section 6 setup)."""
    theta = np.asarray(theta, dtype=np.float32)
    if theta.shape != (2, 2):
        raise ValueError(f"initiator must be 2x2, got {theta.shape}")
    if not ((theta >= 0).all() and (theta <= 1).all()):
        raise ValueError("initiator entries must lie in [0, 1]")
    return KPGMParams(jnp.asarray(np.broadcast_to(theta, (d, 2, 2)).copy()))


def edge_moments(thetas: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Mean m and second-moment term v of |E| (Algorithm 1 lines 3-4).

    m = prod_k sum(theta^(k)),  v = prod_k sum((theta^(k))^2); the number of
    edges is approximately N(m, m - v).
    """
    m = jnp.prod(jnp.sum(thetas, axis=(1, 2)))
    v = jnp.prod(jnp.sum(thetas**2, axis=(1, 2)))
    return m, v


def expected_edges(thetas: jax.Array) -> float:
    return float(edge_moments(thetas)[0])


def sample_num_edges(key: jax.Array, thetas: jax.Array) -> jax.Array:
    """X ~ N(m, m - v) (Algorithm 1 line 5), clipped to >= 0 and rounded.

    Returned as float32 (edge counts can exceed int32 at 20B-edge scale;
    host callers convert with int())."""
    m, v = edge_moments(thetas)
    std = jnp.sqrt(jnp.maximum(m - v, 0.0))
    x = m + std * jax.random.normal(key, ())
    return jnp.maximum(jnp.round(x), 0.0)


def _bucket(x: int) -> int:
    """Smallest 2^k * {4,5,6,7}/4 >= x: geometric batch-size grid (ratio
    <=1.25) so the jitted sampler compiles O(log n) programs while wasting
    <=25%% of generated candidates (vs 2x for pure powers of two)."""
    if x <= 64:
        return 64
    k = (x - 1).bit_length() - 3
    base = 1 << k
    for mult in (4, 5, 6, 7, 8):
        if mult * base >= x:
            return mult * base
    return 8 * base


def _level_cumprobs(thetas: jax.Array) -> jax.Array:
    """(d, 4) cumulative quadrant probabilities, row-major (00, 01, 10, 11)."""
    flat = thetas.reshape(-1, 4)
    flat = flat / jnp.sum(flat, axis=1, keepdims=True)
    return jnp.cumsum(flat, axis=1)


def _descend(u: jax.Array, cum: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(N, d) uniforms + (d, 4) cumulative quadrant probs -> int32 id pairs."""
    d = u.shape[1]
    quad = (
        (u >= cum[None, :, 0]).astype(jnp.int32)
        + (u >= cum[None, :, 1]).astype(jnp.int32)
        + (u >= cum[None, :, 2]).astype(jnp.int32)
    )
    a = quad >> 1  # source bit-plane, (N, d)
    b = quad & 1  # target bit-plane
    pows = (1 << jnp.arange(d - 1, -1, -1)).astype(jnp.int32)
    # integer multiply-add, not a matmul: exact on every backend
    return (
        jnp.sum(a * pows, axis=1, dtype=jnp.int32),
        jnp.sum(b * pows, axis=1, dtype=jnp.int32),
    )


@functools.partial(jax.jit, static_argnames=("num_edges",))
def sample_edge_batch(
    key: jax.Array, thetas: jax.Array, num_edges: int
) -> Tuple[jax.Array, jax.Array]:
    """Sample ``num_edges`` (src, dst) pairs by vectorised quadrant descent.

    Each edge independently follows Algorithm 1 lines 7-16: at level k pick
    quadrant (a, b) with probability proportional to theta^(k)_{ab}.  Returned
    ids are 0-based in [0, 2^d).  Duplicates are possible (the caller
    implements the paper's rejection by dedup + top-up).
    """
    d = thetas.shape[0]
    if d > 31:
        raise ValueError("node ids are int32 on device; require d <= 31")
    cum = _level_cumprobs(thetas)  # (d, 4)
    u = jax.random.uniform(key, (num_edges, d), dtype=jnp.float32)
    return _descend(u, cum)


def _kpgm_sample_host(
    key: jax.Array,
    params: KPGMParams,
    *,
    max_rounds: int = 8,
    oversample: float = 1.05,
    num_edges: Optional[int] = None,
) -> np.ndarray:
    """Host-level orchestration of Algorithm 1 (the reference path): draw
    X ~ N(m, m-v), then draw edge candidates in fixed-shape device batches,
    dedupe on host, and top up until X unique edges are collected (the
    paper's rejection step).  Used by ``repro.api.KPGMSampler`` for
    ``backend="host"`` and for d too large for the device plan."""
    thetas = params.thetas
    d = params.d
    n = params.num_nodes
    key, sub = jax.random.split(key)
    target = int(sample_num_edges(sub, thetas)) if num_edges is None else int(num_edges)
    target = min(target, n * n)
    if target == 0:
        return np.zeros((0, 2), dtype=np.int64)

    # Dedup must preserve ARRIVAL order: np.unique sorts by value, and
    # truncating a sorted list to the target count would bias kept edges
    # toward low node ids (top-left of the adjacency matrix).
    seen: np.ndarray = np.empty((0,), dtype=np.int64)
    for _ in range(max_rounds):
        need = target - seen.size
        if need <= 0:
            break
        key, sub = jax.random.split(key)
        # bucket the batch size to the next power of two: sample_edge_batch
        # is jitted per static size, and per-call recompilation dominated the
        # cold-path wall time (EXPERIMENTS.md Perf, sampler iteration 1:
        # 22.0s cold -> 2.1s once sizes bucket into a handful of programs)
        batch = _bucket(max(int(need * oversample) + 16, 64))
        src, dst = sample_edge_batch(sub, thetas, batch)
        # consume the FULL bucket-rounded batch: the candidates are iid, so
        # the padding beyond need*oversample is free signal — discarding it
        # (the PR-1 behaviour) only bought extra top-up rounds
        flat = np.asarray(src, dtype=np.int64) * n + np.asarray(dst, dtype=np.int64)
        _, first_idx = np.unique(flat, return_index=True)
        in_order = flat[np.sort(first_idx)]
        fresh = in_order[~np.isin(in_order, seen, assume_unique=True)]
        seen = np.concatenate([seen, fresh])
    seen = seen[:target] if seen.size > target else seen
    return np.stack([seen // n, seen % n], axis=1)


def kpgm_sample(
    key: jax.Array,
    params: KPGMParams,
    *,
    max_rounds: int = 8,
    oversample: float = 1.05,
    num_edges: Optional[int] = None,
    backend: str = "auto",
    mesh=None,
) -> np.ndarray:
    """DEPRECATED shim over ``repro.api.KPGMSampler`` — sample a KPGM graph.

    Returns the unique (src, dst) int64 array of shape (E, 2).  Now has the
    same ``backend=``/``mesh=`` surface as the quilting samplers: the
    session layer runs the draw as the trivial B = 1 quilt (identity config
    -> node lookup), so the fused device rounds, on-device top-up and the
    bit-identical ``mesh=`` sharding all apply.  Pinned bit-identical to
    ``KPGMSampler(SamplerConfig(params=params, ...)).sample(key)`` by test.
    Sessions additionally amortize the identity plan across calls — this
    shim rebuilds it every time.
    """
    import warnings

    warnings.warn(
        "kpgm_sample is deprecated; use repro.api.KPGMSampler (see "
        "docs/API.md for the migration table)",
        DeprecationWarning,
        stacklevel=2,
    )
    from repro import api

    sampler = api.KPGMSampler(
        api.SamplerConfig(
            params=params,
            backend=backend,
            mesh=mesh,
            max_rounds=max_rounds,
            oversample=oversample,
        )
    )
    return sampler.sample(key, num_edges=num_edges).edges


@functools.partial(jax.jit, static_argnames=("num_candidates",))
def _many_round(
    key: jax.Array,
    thetas: jax.Array,
    asks: jax.Array,
    targets: jax.Array,
    *,
    num_candidates: int,
):
    """One fused device round for ALL graphs: descent + segmented dedup.

    Fixed-shape outputs (candidate ids + take mask + per-graph counts), so
    the program caches across calls of the same bucketed batch size.  Must be
    called under dedup.call_x64 (packed int64 sort keys)."""
    d = thetas.shape[0]
    cum = _level_cumprobs(thetas)
    u = jax.random.uniform(key, (num_candidates, d), dtype=jnp.float32)
    src, dst = _descend(u, cum)
    cum_asks = jnp.cumsum(asks)
    graph_id = jnp.searchsorted(
        cum_asks, jnp.arange(num_candidates, dtype=asks.dtype), side="right"
    ).astype(jnp.int32)
    take, counts = dedup.segmented_unique_mask(
        graph_id, src, dst, cum_asks, targets, node_bits=d,
        max_ask=num_candidates,
    )
    return src, dst, take, counts


def _host_topup(
    key: jax.Array,
    thetas: jax.Array,
    n: int,
    targets: np.ndarray,
    seen: list,
    max_rounds: int,
    oversample: float,
) -> list:
    """Round-by-round host rejection loop (the PR-1 path), used to finish the
    rare shortfall the single device round leaves behind.

    ``seen`` holds per-graph flat keys (src * n + dst) in arrival order.
    Dedup preserves ARRIVAL order: np.unique sorts by value, and truncating a
    sorted list to the target count would bias kept edges toward low node
    ids."""
    for _ in range(max_rounds):
        needs = np.array([t - s.size for t, s in zip(targets, seen)])
        if needs.max(initial=0) <= 0:
            break
        asks, batch = dedup.plan_asks(needs, oversample)
        key, sub = jax.random.split(key)
        src, dst = sample_edge_batch(sub, thetas, batch)
        flat = np.asarray(src, dtype=np.int64) * n + np.asarray(dst, dtype=np.int64)
        off = 0
        for i, ask in enumerate(np.asarray(asks)):
            if ask == 0:
                continue
            chunk = flat[off : off + int(ask)]
            off += int(ask)
            _, first_idx = np.unique(chunk, return_index=True)
            in_order = chunk[np.sort(first_idx)]
            fresh = in_order[~np.isin(in_order, seen[i], assume_unique=False)]
            seen[i] = np.concatenate([seen[i], fresh])[: targets[i]]
    return seen


def kpgm_sample_many(
    key: jax.Array,
    params: KPGMParams,
    count: int,
    *,
    max_rounds: int = 8,
    oversample: float = 1.1,
    backend: str = "auto",
) -> list:
    """Sample ``count`` independent KPGM graphs with SHARED device batches.

    Algorithm 2 needs B^2 independent KPGM draws; issuing them one
    kpgm_sample at a time pays per-call dispatch + top-up rounds B^2 times.
    Candidates are iid, so one large batch partitioned DISJOINTLY across the
    graphs preserves independence while amortising the device calls
    (EXPERIMENTS.md Perf, sampler iteration 2).

    With ``backend="auto"``/``"device"`` the first (and almost always only)
    round runs fully on-device: one fused dispatch does descent + a single
    sort-based segmented dedup over the packed keys of ALL graphs at once
    (core/dedup.py), replacing the per-graph np.unique/np.isin loop.  The
    residual shortfall (duplicate collisions) is finished by the host loop.
    ``backend="host"`` forces the reference path.
    """
    thetas = params.thetas
    n = params.num_nodes
    d = params.d
    key, sub = jax.random.split(key)
    m, v = edge_moments(thetas)
    std = float(jnp.sqrt(jnp.maximum(m - v, 0.0)))
    draws = np.asarray(
        jax.random.normal(sub, (count,)) * std + float(m)
    )
    targets = np.clip(np.round(draws), 0, min(n * n, 2**62)).astype(np.int64)
    if count == 0:
        return []

    total = int(targets.sum())
    use_device = backend == "device" or (
        backend == "auto"
        and 0 < total
        and total * oversample + 16 * count <= DEVICE_MAX_CANDIDATES
    )

    seen = [np.empty((0,), dtype=np.int64) for _ in range(count)]
    rounds_left = max_rounds
    if use_device and total > 0:
        asks, batch = dedup.plan_asks(targets, oversample)
        key, sub = jax.random.split(key)
        src, dst, take, counts = dedup.call_x64(
            _many_round,
            sub,
            thetas,
            jnp.asarray(asks, jnp.int32),
            jnp.asarray(targets, jnp.int32),
            num_candidates=batch,
        )
        take_h = np.asarray(take)
        flat = (
            np.asarray(src, dtype=np.int64) * n + np.asarray(dst, dtype=np.int64)
        )[take_h]
        # taken edges stay grouped by graph (graph chunks are contiguous and
        # the mask preserves order): split at the per-graph count boundaries
        bounds = np.cumsum(np.asarray(counts, dtype=np.int64))[:-1]
        seen = [s for s in np.split(flat, bounds)]
        rounds_left -= 1
    seen = _host_topup(key, thetas, n, targets, seen, rounds_left, oversample)
    return [np.stack([s // n, s % n], axis=1) for s in seen]


def edge_prob_matrix(thetas: jax.Array) -> jax.Array:
    """Exact dense P = kron(theta_1, ..., theta_d).  Only for small d (tests)."""
    d = thetas.shape[0]
    p = thetas[0]
    for k in range(1, d):
        p = jnp.kron(p, thetas[k])
    del d
    return p


def log_prob_pairs(thetas: jax.Array, src: jax.Array, dst: jax.Array) -> jax.Array:
    """log P_{src,dst} for 0-based id pairs, evaluated via eq. (6).

    One select per level over the (E,) pairs, so the compiled program holds
    no (E, d) intermediate — the exact-cell rounds call it on every
    candidate."""
    d = thetas.shape[0]
    logt = jnp.log(jnp.clip(thetas, 1e-30, 1.0))  # (d, 2, 2)
    total = jnp.zeros(jnp.shape(src), logt.dtype)
    for k in range(d):
        shift = d - 1 - k
        a = ((src >> shift) & 1) == 1
        b = ((dst >> shift) & 1) == 1
        lk = logt[k]
        total = total + jnp.where(
            a,
            jnp.where(b, lk[1, 1], lk[1, 0]),
            jnp.where(b, lk[0, 1], lk[0, 0]),
        )
    return total
