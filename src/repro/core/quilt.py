"""Algorithm 2 — quilting KPGM samples into a MAGM sample — plus the
Section-5 split sampler for unbalanced attribute distributions.

Quilting: partition nodes into D_1..D_B (partition.py), and for every block
pair (k, l) sample a FULL KPGM graph with Algorithm 1, keep only the edges
(x, y) for which some i in D_k has lambda_i = x and some j in D_l has
lambda_j = y, and map them back to node space.  Theorem 3: the union is an
exact MAGM sample.  Expected cost O(B^2 log(n) |E|), and B = O(log n) w.h.p.
for balanced attributes (Theorem 4).

Section-5 split: configurations occurring more than B' times are pulled out
into R "heavy" groups D-hat_1..D-hat_R; all block pairs touching a heavy group
are Erdos-Renyi uniform blocks (every node in a heavy group shares one
configuration, so the edge probability is a single scalar P_{lam'_i, lam'_j}).
The remaining "light" nodes W are quilted with B <= B'.  B' is chosen by
minimising the cost model T(B') = B'^2 log(n)|E| + (|W|+d)R + dR^2.

Sampling pipeline (device-resident, mesh-shardable quilting)
------------------------------------------------------------

``quilt_sample`` runs the whole B^2-block hot path in O(max_rounds) device
dispatches instead of O(B^2) host round-trips, and optionally shards it
across a device mesh:

1. **Plan** — :func:`get_quilt_plan` builds a :class:`QuiltPlan` ONCE per
   (attribute matrix, thetas) pair and caches it: the Theorem-2 partition,
   the padded per-block sorted-config lookup tables (+ the dense config ->
   node inverse used by the CPU fast path), the cumulative quadrant
   probabilities and the |E| moments, all as device arrays.
2. **Layout** — every block-pair graph g gets the SAME number of candidate
   slots per round (dedup.uniform_ask) and derives its variates from the
   counter PRNG (kernels/quadrant_descent.py): slot s's level-k uniform is
   ``counter_u01(counter_seed(round_key), g, s * PRNG_CHANNELS + k)``, so
   graph g's candidate stream depends only on (key, g, absolute slot) —
   never on how graphs are laid out across devices, and never on where the
   round boundaries fell.  This is what makes the sharded and
   single-device paths bit-identical and top-up rounds prefix-stable.
3. **Descent + lookup + dedup** — one fused program per round draws the
   candidates for ALL local block pairs: quadrant descent produces config
   ids (Pallas kernel ``kernels/quadrant_descent.descent_prng`` on TPU, its
   bit-identical jnp twin elsewhere) and maps them to node ids, -1 marking
   a membership miss: inside the kernel, from the graph's two rows of the
   dense inverse in VMEM, where the rows fit (:func:`kernel_lookup`), else
   with an XLA gather (:func:`gather_nodes`); then the sort-based
   segmented dedup (core/dedup.py) over ``(graph_id << 2d) | src << d | dst``
   packed keys returns a fixed-shape take mask + per-graph unique counts.
4. **Mesh sharding** — with ``mesh=``, the B^2 graphs are placed along the
   ``graphs`` logical axis (repro.dist.sharding.graph_shard_axes) and step 3
   runs under ``shard_map``: each device descends + dedups ONLY its chunk of
   graphs (the streams are iid, Theorem 4), with no collective inside the
   round — the final host gather of the sharded outputs is the only
   cross-device step.
5. **On-device top-up** — a duplicate-collision shortfall (typically <0.1%
   of edges) triggers another FIXED-SHAPE device round whose candidate
   stream is [all prior rounds' candidates || fresh draws]: the seen keys
   ride through the segmented dedup again, so arrival-order semantics are
   exact and nothing but the tiny per-graph counts ever leaves the device.
   The PR-1 host rejection loop survives only as a fallback for the
   pathological case of ``max_rounds`` exhausted device rounds.

Public surface
--------------

The engine here (:func:`quilt_run` over a :class:`QuiltPlan`,
:func:`split_run` over a :class:`SplitPlan`) is consumed by the session
facade ``repro.api`` (MAGMSampler / KPGMSampler), which owns its plan,
mesh placement and key stream across samples.  The module-level free
functions :func:`quilt_sample` / :func:`quilt_sample_fast` remain as
deprecated shims pinned bit-identical to the sessions; see docs/API.md.
"""

from __future__ import annotations

import functools
import hashlib
import math
import warnings
from collections import OrderedDict
from typing import List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core import dedup, kpgm, kron, magm, partition, transfer
from repro.dist import chaos
from repro.kernels import ops


class QuiltStats(NamedTuple):
    B: int
    num_kpgm_draws: int
    kpgm_edges_total: int
    kept_edges: int
    heavy_groups: int
    light_nodes: int
    bprime: Optional[int]


# ---------------------------------------------------------------------------
# QuiltPlan: everything quilt_sample needs, built once per attribute matrix
# ---------------------------------------------------------------------------

# dense config->node inverse above this many entries would dominate memory;
# larger plans look nodes up through the O(2^d + n) by-config triple
DENSE_INV_CAP = 1 << 24


class QuiltPlan(NamedTuple):
    """Precomputed device state for quilting one attribute matrix.

    Built (and content-cached) by :func:`get_quilt_plan`: the Theorem-2
    partition, the config -> node lookups of the device rounds (the dense
    inverse and/or the by-config triple, see :func:`device_lookup`), the
    cumulative quadrant probabilities, and the |E| moments — everything
    :func:`quilt_sample` needs besides the key.

    Examples
    --------
    >>> import numpy as np, jax
    >>> from repro.core import magm, quilt
    >>> theta = np.array([[0.3, 0.6], [0.6, 0.9]], dtype=np.float32)
    >>> params = magm.make_params(theta, mu=0.5, d=5)
    >>> F = np.asarray(magm.sample_attributes(jax.random.PRNGKey(0), 24, params.mu))
    >>> plan = quilt.get_quilt_plan(F, params.thetas)
    >>> plan.n, plan.d, plan.num_graphs == plan.B ** 2
    (24, 5, True)
    >>> plan is quilt.get_quilt_plan(F, params.thetas)  # content-cached
    True
    """

    n: int
    d: int
    B: int
    part: partition.Partition  # host-side partition (top-up + stats)
    thetas: jax.Array  # (d, 2, 2)
    cum: jax.Array  # (d, 4) cumulative quadrant probabilities
    inv: Optional[jax.Array]  # (B, 2^d) dense inverse or None
    mean_edges: float  # E|E| of one KPGM draw
    std_edges: float  # sqrt(m - v)
    # conditional-on-F MAGM |E| moments (c^T P c quadratic forms, kron.py)
    # and the ball-dropping proposals-per-edge factor; None past the
    # kron.MOMENT_CAP gate, in which case backend="balldrop" is unavailable
    bd_mean: Optional[float] = None
    bd_std: Optional[float] = None
    bd_cost: Optional[float] = None
    # largest single-cell probability prod_k max(theta^(k)) — sizes the
    # exact-cell proposal budget (see _exact_budget)
    p_max: Optional[float] = None
    # by-config dense lookup: nodes grouped by configuration in occurrence
    # (node-index) order.  cfg_nodes[cfg_offset[x] + b] is the SAME node as
    # partition.dense_inverse[b, x] in O(2^d + n) memory instead of
    # O(B * 2^d) — the ball-dropping rank lookup for skewed mu, where
    # B = c_max makes the dense inverse blow past DENSE_INV_CAP, and the
    # quilt's own lookup once it does
    cfg_offset: Optional[jax.Array] = None  # (2^d,) int32 exclusive prefix
    cfg_count: Optional[jax.Array] = None  # (2^d,) int32 multiplicities
    cfg_nodes: Optional[jax.Array] = None  # (n,) int32 grouped node ids

    @property
    def num_graphs(self) -> int:
        return self.B * self.B

    @property
    def exact_budget(self) -> Optional[int]:
        """Proposals per block-pair graph of the exact-cell round (None
        when no finite budget exists; see :func:`_exact_budget`)."""
        return _exact_budget(self.p_max, self.mean_edges)


PLAN_STATS = tracing.register(
    "quilt.plan", {"partition_builds": 0, "plan_builds": 0, "plan_hits": 0}
)
_PART_CACHE: "OrderedDict" = OrderedDict()
_PLAN_CACHE: "OrderedDict" = OrderedDict()
_KPGM_PLAN_CACHE: "OrderedDict" = OrderedDict()
_CACHE_MAX = 8


def clear_plan_cache() -> None:
    """Clear the content-keyed plan/partition caches of the SHIM path.

    Session objects (``repro.api.MAGMSampler`` / ``KPGMSampler``) own their
    :class:`QuiltPlan` directly (:func:`build_quilt_plan` bypasses these
    caches entirely), so live sessions are unaffected by this call — the
    global cache's only remaining role is amortizing repeated calls of the
    deprecated free-function shims (:func:`quilt_sample`,
    :func:`quilt_sample_fast`).
    """
    _PART_CACHE.clear()
    _PLAN_CACHE.clear()
    _KPGM_PLAN_CACHE.clear()


def _warn_shim(old: str, new: str) -> None:
    warnings.warn(
        f"{old} is deprecated; use {new} (see docs/API.md for the migration"
        " table)",
        DeprecationWarning,
        stacklevel=3,
    )


def _digest(a: np.ndarray):
    a = np.ascontiguousarray(a)
    return (a.shape, a.dtype.str, hashlib.sha1(a.tobytes()).hexdigest())


def _cache_put(cache: OrderedDict, key, value) -> None:
    cache[key] = value
    cache.move_to_end(key)
    while len(cache) > _CACHE_MAX:
        cache.popitem(last=False)


def _partition_state(F: np.ndarray, d: int):
    """Partition + device-lookup structures for one attribute matrix."""
    lam = np.asarray(magm.configs_from_attributes(jnp.asarray(F)))
    part = partition.build_partition(lam)
    PLAN_STATS["partition_builds"] += 1
    inv_np = (
        partition.dense_inverse(part, d)
        if part.B and part.B * (1 << d) <= DENSE_INV_CAP
        else None
    )
    bycfg_np = None
    if part.B and 2 * (1 << d) <= DENSE_INV_CAP:
        # stable sort groups nodes by config in node-index order — exactly
        # the Theorem-2 occurrence-rank order, so entry b of config x's
        # group is block b's node for x (bit-identical to dense_inverse)
        count = np.bincount(lam, minlength=1 << d).astype(np.int32)
        offset = np.zeros(1 << d, dtype=np.int32)
        offset[1:] = np.cumsum(count[:-1])
        nodes = np.argsort(lam, kind="stable").astype(np.int32)
        bycfg_np = (offset, count, nodes)
    return part, inv_np, bycfg_np


@jax.jit
def _plan_constants(th_dev: jax.Array):
    """All theta-only plan scalars/tables fused into ONE compiled dispatch.

    Returns (cum, m, std, p_max).  Eagerly these were ~a dozen tiny op-by-op
    dispatches per plan build (cumprobs, two moment reductions, the sqrt,
    the per-level max-product); serving cold-start builds exactly one plan,
    so folding them into a single jitted call is the cheap half of the
    ``plan_build_*`` win — the partition reuse in :func:`build_quilt_plan`
    is the other.
    """
    cum = kpgm._level_cumprobs(th_dev)
    m, v = kpgm.edge_moments(th_dev)
    std = jnp.sqrt(jnp.maximum(m - v, 0.0))
    p_max = jnp.prod(jnp.max(th_dev, axis=(1, 2)))
    return cum, m, std, p_max


def _assemble_plan(F: np.ndarray, th: np.ndarray, part_state) -> QuiltPlan:
    part, inv_np, bycfg_np = part_state
    n, d = F.shape
    th_dev = jnp.asarray(th)
    cum, m_dev, std_dev, pmax_dev = _plan_constants(th_dev)
    # one transfer for all three host-side scalars, not three blocking gets
    m, std, p_max = (float(x) for x in jax.device_get((m_dev, std_dev, pmax_dev)))
    bd_mean = bd_std = bd_cost = None
    if part.B and (1 << d) <= kron.MOMENT_CAP:
        c = kron.config_multiplicities(part, d)
        bd_mean, bd_std = kron.edge_count_moments(c, th)
        bd_cost = kron.balldrop_cost_factor(float(m), part.B, bd_mean)
    plan = QuiltPlan(
        n=n,
        d=d,
        B=part.B,
        part=part,
        thetas=th_dev,
        cum=cum,
        inv=jnp.asarray(inv_np) if inv_np is not None else None,
        mean_edges=m,
        std_edges=std,
        bd_mean=bd_mean,
        bd_std=bd_std,
        bd_cost=bd_cost,
        p_max=p_max,
        cfg_offset=jnp.asarray(bycfg_np[0]) if bycfg_np else None,
        cfg_count=jnp.asarray(bycfg_np[1]) if bycfg_np else None,
        cfg_nodes=jnp.asarray(bycfg_np[2]) if bycfg_np else None,
    )
    PLAN_STATS["plan_builds"] += 1
    return plan


def build_quilt_plan(
    F: np.ndarray, thetas: jax.Array, *, reuse_partition: bool = True
) -> QuiltPlan:
    """Build a QuiltPlan the caller owns (the session cold-start path).

    The session path (``repro.api``): the caller holds the returned plan for
    its whole lifetime, so the *plan* itself is never cached and
    :func:`clear_plan_cache` cannot evict it out from under a live session.

    The theta-independent partition state (Theorem-2 blocks, padded lookup
    tables, dense/by-config inverses) IS shared through the content-keyed
    ``_PART_CACHE`` by default: it is immutable once built and dominates the
    serving cold start, so two sessions over the same attribute matrix — or
    one session re-created after a parameter refit — pay the O(n + B·2^d)
    partition cost once.  A cache hit leaves ``PLAN_STATS['partition_builds']``
    untouched.  Pass ``reuse_partition=False`` to force a fresh build (and
    skip the SHA-1 content digest entirely, restoring the old contract for
    callers that mutate F arrays in place).
    """
    F = np.asarray(F)
    th = np.asarray(thetas)
    if not reuse_partition:
        return _assemble_plan(F, th, _partition_state(F, F.shape[1]))
    fkey = _digest(F)
    part_state = _PART_CACHE.get(fkey)
    if part_state is None:
        part_state = _partition_state(F, F.shape[1])
        _cache_put(_PART_CACHE, fkey, part_state)
    else:
        _PART_CACHE.move_to_end(fkey)
    return _assemble_plan(F, th, part_state)


def build_kpgm_plan(thetas: jax.Array) -> QuiltPlan:
    """Identity-partition plan: one block mapping config c -> node c.

    Lets a plain KPGM graph (no attribute matrix) run through the exact
    quilting engine — fused device rounds, on-device top-up, ``mesh=``
    sharding with bit-identical results — as the trivial B = 1 quilt whose
    lookup is the identity.  Used by ``repro.api.KPGMSampler``; O(2^d)
    memory, so callers gate on d.

    Unlike :func:`build_quilt_plan`, this IS content-cached (keyed by the
    theta digest): identity plans are fully determined by thetas and
    immutable, so sharing them across sessions — and across the repeated
    ``kpgm_sample`` shim calls that would otherwise rebuild the O(2^d)
    partition every time — is pure win.  Sessions keep their reference, so
    :func:`clear_plan_cache` still cannot pull a plan out from under one.
    """
    th = np.asarray(thetas)
    tkey = _digest(th)
    plan = _KPGM_PLAN_CACHE.get(tkey)
    if plan is not None:
        _KPGM_PLAN_CACHE.move_to_end(tkey)
        return plan
    d = int(th.shape[0])
    lam = np.arange(1 << d, dtype=np.int64)
    F_id = np.asarray(magm.attributes_from_configs(jnp.asarray(lam), d))
    plan = _assemble_plan(F_id, th, _partition_state(F_id, d))
    _cache_put(_KPGM_PLAN_CACHE, tkey, plan)
    return plan


def get_quilt_plan(F: np.ndarray, thetas: jax.Array) -> QuiltPlan:
    """Build (or fetch) the cached QuiltPlan for an (F, thetas) pair.

    Keyed by content: repeated samples over the same attribute matrix reuse
    the cached partition + device tables (no re-partition), and the same F
    under new thetas only re-derives the theta-dependent pieces.  This is
    the shim-path fallback; sessions use :func:`build_quilt_plan` and hold
    the plan themselves.
    """
    F = np.asarray(F)
    th = np.asarray(thetas)
    fkey = _digest(F)
    tkey = _digest(th)
    plan = _PLAN_CACHE.get((fkey, tkey))
    if plan is not None:
        PLAN_STATS["plan_hits"] += 1
        _PLAN_CACHE.move_to_end((fkey, tkey))
        return plan

    cached_part = _PART_CACHE.get(fkey)
    if cached_part is None:
        cached_part = _partition_state(F, F.shape[1])
        _cache_put(_PART_CACHE, fkey, cached_part)
    else:
        # true LRU: a HIT must refresh recency too, or the hottest
        # partition is the first evicted once the cache fills
        _PART_CACHE.move_to_end(fkey)
    plan = _assemble_plan(F, th, cached_part)
    _cache_put(_PLAN_CACHE, (fkey, tkey), plan)
    return plan


# ---------------------------------------------------------------------------
# Device-resident quilting (mesh-shardable)
# ---------------------------------------------------------------------------

# one fused dispatch per round (first round + on-device top-ups) + the final
# gather; tests assert the total stays O(max_rounds), independent of B^2, and
# that host_topup_rounds stays 0 on the default backend.  mesh_degrades
# counts dispatch-time device losses recovered by rebuilding the mesh over
# the survivors; degraded_fallbacks counts max_rounds-exhausted runs that
# fell through to the host top-up loop; exact_fallbacks counts runs that
# wanted the exact-cell round but took the drawn-target rounds; and
# host_fallbacks counts backend="auto" runs that left the device for the
# host loop.  Every one of them also warns — degradation is observable,
# never silent.
DISPATCH_COUNTERS = tracing.register(
    "quilt.dispatch",
    {
        "device_rounds": 0,
        "device_topup_rounds": 0,
        "host_topup_rounds": 0,
        "mesh_degrades": 0,
        "degraded_fallbacks": 0,
        "exact_fallbacks": 0,
        "host_fallbacks": 0,
    },
)

# candidate slots, kept edges and device-to-host bytes (core/transfer.py)
ENGINE_COUNTERS = transfer.ENGINE_COUNTERS

# the counters above that must stay 0 for a run to have stayed on its
# device path (the chip smoke and the tests read them)
FALLBACK_COUNTERS = (
    "host_topup_rounds",
    "mesh_degrades",
    "degraded_fallbacks",
    "exact_fallbacks",
    "host_fallbacks",
)


def fallback(counters: dict, name: str, message: str) -> None:
    """Count a departure from the device path and say so."""
    counters[name] += 1
    warnings.warn(message, RuntimeWarning, stacklevel=3)


def device_lookup(plan: "QuiltPlan"):
    """The config -> node gather tables of the device rounds: ``(inv,)``,
    the dense (B, 2^d) inverse, when it was built, else the by-config
    triple ``(cfg_offset, cfg_count, cfg_nodes)``; None when the plan has
    neither (no device lookup exists at this size)."""
    if plan.inv is not None:
        return (plan.inv,)
    if plan.cfg_offset is not None:
        return (plan.cfg_offset, plan.cfg_count, plan.cfg_nodes)
    return None


def gather_nodes(tables, kb, lb, scfg, dcfg, d: int):
    """Node ids of the (block, config) pairs, -1 where the block does not
    hold the config — one XLA gather per side.

    With the by-config triple, rank k of config x is x's k-th node in
    node-index order (a hit iff ``k < c_x``): the Theorem-2 occurrence
    rank, so both table forms give the same ids bit for bit."""
    if len(tables) == 1:
        flat = tables[0].reshape(-1)
        return flat[(kb << d) | scfg], flat[(lb << d) | dcfg]
    cfg_offset, cfg_count, cfg_nodes = tables

    def one(rank, cfg):
        c = cfg_count[cfg]
        idx = cfg_offset[cfg] + jnp.minimum(rank, jnp.maximum(c - 1, 0))
        return jnp.where(rank < c, cfg_nodes[idx], jnp.int32(-1))

    return one(kb, scfg), one(lb, dcfg)


def kernel_lookup(tables, use_kernel: bool) -> bool:
    """Whether a round looks its configs up inside the descent kernel: it
    runs the Pallas kernel, the plan has the dense inverse, and the
    inverse's rows hold at most ``ops.KERNEL_LOOKUP_MAX_ROW`` entries.
    Every other round gathers with :func:`gather_nodes`."""
    return (
        use_kernel
        and len(tables) == 1
        and tables[0].shape[1] <= ops.KERNEL_LOOKUP_MAX_ROW
    )


def graph_major(gids: jax.Array, a_tot: int):
    """(local graph index, global graph id) of every candidate of a
    graph-major round of ``a_tot`` slots per graph — broadcasts, not
    gathers."""
    gc = gids.shape[0]
    local = jnp.broadcast_to(
        jnp.arange(gc, dtype=jnp.int32)[:, None], (gc, a_tot)
    ).reshape(-1)
    gid = jnp.broadcast_to(
        gids.astype(jnp.int32)[:, None], (gc, a_tot)
    ).reshape(-1)
    return local, gid


def slots_fit(a_tot: int) -> bool:
    """Whether ``a_tot`` slots per graph fit the counter word
    ``slot * PRNG_CHANNELS + channel`` in uint32 (kept apart from the
    device-memory cap ``kpgm.DEVICE_MAX_CANDIDATES``)."""
    return int(a_tot) <= ops.PRNG_SLOT_LIMIT


def _pad_inputs(gtot: int, g_pad: int, targets: np.ndarray):
    """(gids, targets) padded to ``g_pad`` as device arrays; padding rows
    carry gid 0 / target 0, so they never emit.  Transfers are explicit
    (``device_put``) so the hot path stays clean under
    ``jax.transfer_guard("disallow")``."""
    gids = np.zeros(g_pad, dtype=np.int32)
    gids[:gtot] = np.arange(gtot, dtype=np.int32)
    tpad = np.zeros(g_pad, dtype=np.int32)
    tpad[:gtot] = targets
    return jax.device_put(gids), jax.device_put(tpad)


def _exact_budget(p_max: Optional[float], mean_edges: float) -> Optional[int]:
    """Fixed per-graph proposal count G for the exact-cell mode.

    Quadrant descent proposes cell c with probability pi_c = p_c / S
    (S = sum of cell probabilities = ``mean_edges``), so after G iid
    proposals the cell is occupied with q_c = 1 - (1 - pi_c)^G.  The
    smallest G with q_c >= p_c for EVERY cell (so acceptance thinning
    alpha_c = p_c / q_c <= 1 can hit the exact Bernoulli(p_c) marginal) is
    log(1 - p) / log(1 - p / S) at p = p_max — the ratio is increasing in
    p.  Returns None when no usable finite budget exists.
    """
    if p_max is None or mean_edges <= 0.0:
        return None
    # cells with p within float-eps of 1 would need an unbounded budget;
    # clipping concedes a <=1e-6 relative bias for those cells only
    p = min(float(p_max), 1.0 - 1e-6)
    S = max(float(mean_edges), p)
    if p <= 0.0:
        return 1
    ratio = p / S
    if ratio >= 1.0:
        return 1
    g = math.log1p(-p) / math.log1p(-ratio)
    if not math.isfinite(g) or g > float(kpgm.DEVICE_MAX_CANDIDATES):
        return None
    g = max(int(math.ceil(g)), 1)
    return g if slots_fit(g) else None


def _accept_u01(salt: jax.Array, gid: jax.Array, cell: jax.Array) -> jax.Array:
    """Deterministic uniform in [0, 1) per (salt, graph, cell): a
    splitmix64-style finalizer over the packed ids.

    Every duplicate candidate of one cell hashes identically, so the
    acceptance test of the exact-cell mode keeps or kills the CELL as a
    unit; keyed by the global graph id + a salt derived from the round key,
    it is layout-invariant under mesh sharding.  Needs x64 (call under
    dedup.call_x64).
    """
    x = (
        salt
        ^ (gid.astype(jnp.uint64) * jnp.uint64(0x9E3779B97F4A7C15))
        ^ (cell.astype(jnp.uint64) * jnp.uint64(0xC2B2AE3D27D4EB4F))
    )
    x = (x ^ (x >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
    x = x ^ (x >> jnp.uint64(31))
    return (x >> jnp.uint64(40)).astype(jnp.float32) * jnp.float32(2.0**-24)


def _exact_cell_valid(
    rkey: jax.Array,
    gid: jax.Array,
    scfg: jax.Array,
    dcfg: jax.Array,
    thetas: jax.Array,
    budget: int,
    log_extra: float = 0.0,
    cell: Optional[jax.Array] = None,
) -> jax.Array:
    """Per-candidate accept mask making cell inclusion exactly Bernoulli(p).

    ``q = 1 - (1 - pi)^G`` is the cell's occupancy probability under this
    round's G proposals (pi = p / S / exp(log_extra); ``log_extra`` adds the
    ball-dropping rank factor log B^2), and the cell survives with
    probability alpha = p / q, decided by the shared per-cell hash — so the
    marginal is q * alpha = p exactly.  Composes into ``valid=`` of
    dedup.segmented_unique_mask: a rejected cell never emits, an accepted
    one emits its arrival-order first occurrence.
    """
    d = thetas.shape[0]
    logp = kpgm.log_prob_pairs(thetas, scfg, dcfg)
    log_s = jnp.sum(jnp.log(jnp.sum(thetas, axis=(1, 2))))
    logpi = (logp - log_s - log_extra).astype(jnp.float32)
    pi = jnp.exp(logpi)
    q = -jnp.expm1(jnp.float32(budget) * jnp.log1p(-pi))
    alpha = jnp.minimum(
        jnp.exp(logp.astype(jnp.float32) - jnp.log(q)), 1.0
    )
    salt = jax.random.bits(
        jax.random.fold_in(rkey, 0x5EED), (), jnp.uint64
    )
    if cell is None:
        # quilt: the dedup unit IS the config cell.  Ball dropping passes
        # the packed NODE pair instead (many node pairs share one config
        # pair but must draw independent accept bits).
        cell = scfg.astype(jnp.int64) * jnp.int64(1 << d) + dcfg.astype(
            jnp.int64
        )
    return _accept_u01(salt, gid, cell) < alpha


def _degrade_layout(mesh, exc: "chaos.DeviceLoss", gtot: int, counters=None):
    """Recover from a dispatch-time device loss: survivors mesh + layout.

    Returns ``(mesh, axes, g_pad)`` for the degraded mesh.  Re-raises the
    original fault when recovery is impossible (no mesh to shrink, or no
    surviving device).  The re-run is bit-identical on the smaller mesh —
    per-graph ``fold_in`` keys and shared slot counts mean no per-graph
    stream ever depended on the device layout (Theorem 4 invariance), and
    a changed pad size only adds zero-target rows that emit nothing.
    """
    if mesh is None:
        raise exc
    from repro.dist import sharding as _dist_sharding
    from repro.launch import mesh as _launch_mesh

    try:
        new_mesh = _launch_mesh.degrade_sampler_mesh(mesh, exc.device)
    except ValueError:
        raise exc from None
    layout = _dist_sharding.graph_layout(new_mesh, gtot)
    (DISPATCH_COUNTERS if counters is None else counters)["mesh_degrades"] += 1
    warnings.warn(
        f"device {exc.device} lost mid-dispatch: rebuilt the sampler mesh "
        f"over {layout.nshards} surviving device(s) and re-running the "
        "round (layout invariance keeps the edges bit-identical)",
        RuntimeWarning,
        stacklevel=3,
    )
    return new_mesh, layout.axes, layout.padded


def _round_body(
    rkey: jax.Array,
    gids: jax.Array,
    targets: jax.Array,
    cum: jax.Array,
    thetas: jax.Array,
    tables,
    *,
    rounds: Tuple[int, ...],
    num_blocks: int,
    use_kernel: bool,
    exact: bool = False,
):
    """Per-shard fused quilting round over a chunk of block-pair graphs.

    ``gids``/``targets`` are this shard's GLOBAL graph ids and edge targets
    (zero-target padding rows emit nothing).  Candidates come from the
    counter PRNG (kernels/quadrant_descent.py): graph g's slot-s level-k
    uniform is ``counter_u01(counter_seed(rkey), g, s * PRNG_CHANNELS + k)``
    — a pure function of the round key, the GLOBAL graph id and the
    candidate's absolute position in the graph's concatenated stream.
    ``rounds`` therefore only sets the total slot count ``sum(rounds)``: a
    top-up round re-derives the earlier rounds' variates as an exact prefix
    (that is how the seen keys ride through the segmented dedup with exact
    arrival-order semantics), and any sharding of the graph axis is
    bit-identical by construction (no per-device state enters the hash).

    Returns fixed-shape (scfg, dcfg, snode, dnode, take, counts); call under
    dedup.call_x64.  ``use_kernel`` picks the descent: the Pallas kernel
    (which derives the variates in-kernel — no HBM uniforms operand) or its
    jnp twin; the two are bit-identical by shared integer math.  ``tables``
    (:func:`device_lookup`) maps configs to nodes: inside the kernel, from
    the graph's two rows of the dense inverse held in VMEM, where
    :func:`kernel_lookup` says the rows fit; else with an XLA gather
    (:func:`gather_nodes`).  Both give the same ids bit for bit.  No
    collectives: with shard_map, the caller's gather of the outputs is the
    only cross-device step.

    ``exact=True`` is the exact-cell mode (single round, plan-constant
    budget): instead of ranking first-N-distinct cells against a drawn
    target, every proposed cell passes the per-cell acceptance thinning of
    :func:`_exact_cell_valid`, making cell inclusion exactly Bernoulli(p) —
    the fix for the high-Q collision deficit the MAGFIT recovery suite
    surfaced.  ``targets`` then only carries the (never-binding) budget cap
    and the zero rows that mute mesh padding.
    """
    d = cum.shape[0]
    gc = gids.shape[0]
    a_tot = int(sum(rounds))
    seed = ops.counter_seed(rkey)
    local, gid = graph_major(gids, a_tot)
    if kernel_lookup(tables, use_kernel):
        scfg, dcfg, snode, dnode = ops.descent_prng_pallas(
            seed, gids, cum, a_tot=a_tot, num_blocks=num_blocks,
            inv=tables[0],
        )
    else:
        if use_kernel:
            scfg, dcfg = ops.descent_prng_pallas(seed, gids, cum, a_tot=a_tot)
        else:
            slot = jnp.arange(gc * a_tot, dtype=jnp.int32) - local * a_tot
            u = ops.descent_uniforms(seed[0, 0], seed[0, 1], gid, slot, d)
            scfg, dcfg = kpgm._descend(u, cum)
        # graph ids beyond B^2 are batched samples (repro.api sample_batch):
        # sample s's block pair g' lives at gid = s * B^2 + g', so the block
        # decode reduces mod B^2 (a no-op for the single-sample gid < B^2
        # case); the kernel's lookup decodes the same way
        block = gid % (num_blocks * num_blocks)
        kb = block // num_blocks
        lb = block % num_blocks
        snode, dnode = gather_nodes(tables, kb, lb, scfg, dcfg, d)
    cum_asks = jnp.arange(1, gc + 1, dtype=jnp.int32) * a_tot
    valid = None
    if exact:
        # fold the occurrence-lookup misses in too: counts then equal the
        # realized per-graph edge totals (QuiltRun.targets in exact mode)
        valid = (
            (snode >= 0)
            & (dnode >= 0)
            & _exact_cell_valid(rkey, gid, scfg, dcfg, thetas, rounds[0])
        )
    take, counts = dedup.segmented_unique_mask(
        local, scfg, dcfg, cum_asks, targets, node_bits=d, valid=valid,
        max_ask=a_tot,
    )
    return scfg, dcfg, snode, dnode, take, counts


@functools.lru_cache(maxsize=64)
def _compiled_round(
    mesh,
    axes: Tuple[str, ...],
    rounds: Tuple[int, ...],
    num_blocks: int,
    use_kernel: bool,
    num_tables: int,
    exact: bool = False,
):
    """Jit (and, with a mesh, shard_map) one round program.

    Cached so repeated samples of the same shape reuse the compiled program;
    keyed by the mesh object, the resolved graph axes and the static sizes.
    In the exact-cell mode every static here is a plan constant, so warm
    sessions never recompile across keys (the recompile-budget sanitizer
    pins this).
    """
    body = functools.partial(
        _round_body,
        rounds=rounds,
        num_blocks=num_blocks,
        use_kernel=use_kernel,
        exact=exact,
    )
    if mesh is not None:
        spec = jax.sharding.PartitionSpec(axes)
        rep = jax.sharding.PartitionSpec()
        body = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(rep, spec, spec, rep, rep, (rep,) * num_tables),
            out_specs=(spec,) * 6,
            check_vma=False,
        )
    return jax.jit(body)


class DeviceBatchUnavailable(RuntimeError):
    """Raised by :func:`quilt_run` when ``num_samples > 1`` resolves to the
    host backend (no fused multi-sample path exists there); callers fall
    back to a per-sample loop."""


class QuiltRun(NamedTuple):
    """One executed quilting run: fixed-shape device buffers + emission.

    The engine result shared by every public surface: ``edges()`` is the
    classic concatenated array, ``iter_chunks()`` the streaming emission
    (``repro.api.MAGMSampler.sample_stream``), ``edges_per_sample()`` the
    fused-batch split.  ``tail`` holds ``(graph_id, (E, 2))`` pieces from
    the pathological host top-up fallback, appended after the device edges
    in insertion order; ``host_edges``/``host_stats`` are set instead of the
    device fields when the run took the host backend.

    ``sampler`` records which engine produced the run: ``"quilt"`` (B^2
    block-pair graphs per sample) or ``"balldrop"`` (one node-pair stream
    per sample, core/balldrop.py); the per-sample splits and stats key off
    it to know how many dedup graphs one sample spans.
    """

    plan: QuiltPlan
    num_samples: int
    targets: np.ndarray  # (num_samples * B^2,)
    counts: np.ndarray  # (num_samples * B^2,) per-graph unique counts
    snode: Optional[jax.Array]  # (g_pad * slots,) candidate node ids
    dnode: Optional[jax.Array]
    keep: Optional[np.ndarray]  # host bool: taken AND both lookups hit
    slots_per_graph: int
    tail: Tuple[Tuple[int, np.ndarray], ...]
    host_edges: Optional[np.ndarray]
    host_stats: Optional[QuiltStats]
    sampler: str = "quilt"
    # snode/dnode already fetched whole by the engine (quilt_run's keep
    # mask, balldrop's host top-up): emission's fetches of them move nothing
    nodes_fetched: bool = False

    @property
    def graphs_per_sample(self) -> int:
        """Dedup graphs one sample spans (B^2 block pairs, or one
        node-pair stream for the ball-dropping backend)."""
        return 1 if self.sampler == "balldrop" else self.plan.num_graphs

    def kept_edges(self) -> int:
        if self.host_edges is not None:
            return int(self.host_edges.shape[0])
        kept = int(self.keep.sum()) if self.keep is not None else 0
        return kept + sum(int(p.shape[0]) for _, p in self.tail)

    @tracing.traced("quilt.emit")
    def edges(self) -> np.ndarray:
        """Concatenated (E, 2) int64 edge array (all samples, sample-major)."""
        if self.host_edges is not None:
            return self.host_edges
        if self.num_samples != 1 and self.tail:
            # tail pieces land after ALL device edges; only the per-sample
            # split reassembles a sample-major order for fused batches
            return np.concatenate(self.edges_per_sample(), axis=0)
        pieces: List[np.ndarray] = []
        if self.keep is not None and self.keep.any():
            sn = transfer.to_host(self.snode, moves=not self.nodes_fetched)
            dn = transfer.to_host(self.dnode, moves=not self.nodes_fetched)
            pieces.append(
                np.stack(
                    [sn[self.keep], dn[self.keep]], axis=1
                ).astype(np.int64)
            )
            ENGINE_COUNTERS["kept_edges"] += pieces[0].shape[0]
        pieces.extend(p for _, p in self.tail)
        pieces = [p for p in pieces if p.size]
        if not pieces:
            return np.zeros((0, 2), dtype=np.int64)
        return np.concatenate(pieces, axis=0)

    def iter_chunks(self, chunk_edges: int):
        """Yield fixed-size deduped edge chunks without materializing the
        full edge list (the last chunk may be shorter)."""
        if self.num_samples != 1:
            raise ValueError("iter_chunks streams single-sample runs only")
        if self.host_edges is not None:
            return dedup.rechunk_edges([self.host_edges], chunk_edges)
        if self.keep is None:
            return dedup.rechunk_edges(
                [p for _, p in self.tail], chunk_edges
            )
        return dedup.iter_edge_chunks(
            self.snode,
            self.dnode,
            self.keep,
            chunk_edges,
            tail=[p for _, p in self.tail],
        )

    @tracing.traced("quilt.emit")
    def edges_per_sample(self) -> List[np.ndarray]:
        """Split the kept edges of a fused batch back into per-sample
        (E_s, 2) arrays (candidate order is sample-major, so each sample's
        edges are contiguous)."""
        G = self.graphs_per_sample
        S = self.num_samples
        if self.host_edges is not None:
            return [self.host_edges]
        per: List[List[np.ndarray]] = [[] for _ in range(S)]
        if self.keep is not None and self.keep.any():
            sn = transfer.to_host(self.snode, moves=not self.nodes_fetched)
            dn = transfer.to_host(self.dnode, moves=not self.nodes_fetched)
            idx = np.flatnonzero(self.keep)
            samp = (idx // max(self.slots_per_graph, 1)) // G
            dev = np.stack([sn[idx], dn[idx]], axis=1).astype(np.int64)
            ENGINE_COUNTERS["kept_edges"] += dev.shape[0]
            bounds = np.searchsorted(samp, np.arange(1, S))
            for s, piece in enumerate(np.split(dev, bounds)):
                per[s].append(piece)
        for g, piece in self.tail:
            per[g // G].append(piece)
        return [
            np.concatenate(p, axis=0)
            if p and sum(x.size for x in p)
            else np.zeros((0, 2), dtype=np.int64)
            for p in per
        ]

    def stats(self, kept: Optional[int] = None) -> QuiltStats:
        if self.host_stats is not None:
            return self.host_stats
        return QuiltStats(
            B=self.plan.B,
            # the ball-dropping backend never draws whole KPGM graphs
            num_kpgm_draws=0 if self.sampler == "balldrop" else self.plan.num_graphs,
            kpgm_edges_total=int(self.counts.sum()),
            kept_edges=self.kept_edges() if kept is None else int(kept),
            heavy_groups=0,
            light_nodes=self.plan.n,
            bprime=None,
        )

    def stats_per_sample(
        self, kept_sizes: List[int]
    ) -> List[QuiltStats]:
        G = self.graphs_per_sample
        csum = self.counts.reshape(self.num_samples, G).sum(axis=1)
        return [
            QuiltStats(
                B=self.plan.B,
                num_kpgm_draws=0 if self.sampler == "balldrop" else G,
                kpgm_edges_total=int(csum[s]),
                kept_edges=int(kept_sizes[s]),
                heavy_groups=0,
                light_nodes=self.plan.n,
                bprime=None,
            )
            for s in range(self.num_samples)
        ]


@tracing.traced("quilt.run")
def quilt_run(
    key: jax.Array,
    plan: QuiltPlan,
    *,
    num_samples: int = 1,
    targets: Optional[np.ndarray] = None,
    max_rounds: int = 8,
    oversample: float = 1.05,
    backend: str = "auto",
    use_kernel: Optional[bool] = None,
    mesh=None,
    exact_cells: Optional[bool] = None,
) -> QuiltRun:
    """Execute the quilting engine for a prebuilt plan; returns a QuiltRun.

    The session-facing core of :func:`quilt_sample` (which wraps it behind
    the deprecated free-function signature).  ``num_samples > 1`` fuses a
    whole batch of independent MAGM samples into the SAME per-round device
    dispatches — sample s's block pair g' is graph ``s * B^2 + g'`` of the
    segmented dedup — and raises :class:`DeviceBatchUnavailable` if the
    backend decision resolves to host.  ``targets`` overrides the per-graph
    Normal(m, m - v) edge-count draw (the key is split identically either
    way, so the candidate streams don't depend on the override).

    ``exact_cells`` selects the exact-cell mode (default: on exactly when
    no ``targets`` override is given): ONE fixed-shape round of
    :func:`_exact_budget` proposals per graph with per-cell acceptance
    thinning, so each cell appears with exactly its Bernoulli probability
    instead of the first-N-distinct law ``1 - (1 - p/S)^N`` whose high-Q
    deficit the MAGFIT recovery suite surfaced.  The round shape is a plan
    constant — warm sessions re-dispatch one cached program for every key
    (zero recompiles).  Runs that cannot take it (explicit targets, host
    backend, budget past DEVICE_MAX_CANDIDATES) fall back to the legacy
    ranked rounds, counted in ``DISPATCH_COUNTERS["exact_fallbacks"]``;
    ``exact_cells=False`` forces the legacy path (the KPGM sessions do, to
    keep their drawn-target contract).  ``QuiltRun.targets`` equals the
    realized counts in exact mode.

    ``backend="balldrop"`` dispatches to the ball-dropping engine
    (core/balldrop.py, arXiv:1202.6001): same plan, same QuiltRun surface,
    but one node-pair candidate stream per sample (targets are per SAMPLE
    there, not per block pair).
    """
    if backend == "balldrop":
        from repro.core import balldrop  # lazy: balldrop imports this module

        return balldrop.balldrop_run(
            key,
            plan,
            num_samples=num_samples,
            targets=targets,
            max_rounds=max_rounds,
            oversample=oversample,
            use_kernel=use_kernel,
            mesh=mesh,
            exact_cells=exact_cells,
        )
    S = int(num_samples)
    G = plan.num_graphs
    gtot = S * G
    ncfg = 1 << plan.d
    targets_given = targets is not None

    if use_kernel is None:
        use_kernel = not ops.INTERPRET
    lookup = device_lookup(plan)
    if backend == "device" and lookup is None:
        raise ValueError(
            "backend='device' needs a device lookup, and this plan has none "
            f"(d={plan.d}: 2 * 2^d is over DENSE_INV_CAP={DENSE_INV_CAP})"
        )

    exact = (not targets_given) if exact_cells is None else bool(exact_cells)
    exact = (
        exact
        and not targets_given
        and backend in ("auto", "device")
        and lookup is not None
        and gtot > 0
    )
    budget = plan.exact_budget if exact else None
    if exact and (
        budget is None or gtot * budget > kpgm.DEVICE_MAX_CANDIDATES
    ):
        fallback(
            DISPATCH_COUNTERS,
            "exact_fallbacks",
            f"exact-cell round over the device budget ({gtot} graphs x "
            f"{budget} proposals > DEVICE_MAX_CANDIDATES="
            f"{kpgm.DEVICE_MAX_CANDIDATES}, or no finite budget): taking "
            "the drawn-target rounds instead",
        )
        exact = False

    key, sub = jax.random.split(key)
    if exact:
        targets = np.full(gtot, budget, dtype=np.int64)
        ask0 = budget
    elif targets is None:
        draws = (
            transfer.to_host(jax.random.normal(sub, (gtot,)))
            * plan.std_edges
            + plan.mean_edges
        )
        targets = np.clip(
            np.round(draws), 0, min(ncfg * ncfg, 2**62)
        ).astype(np.int64)
        ask0 = dedup.uniform_ask(targets, oversample)
    else:
        targets = np.clip(
            np.asarray(targets, dtype=np.int64).reshape(gtot),
            0,
            min(ncfg * ncfg, 2**62),
        )
        ask0 = dedup.uniform_ask(targets, oversample)
    total = int(targets.sum())

    from repro.dist import sharding as _dist_sharding

    layout = _dist_sharding.graph_layout(mesh, gtot)
    axes, g_pad = layout.axes, layout.padded
    if not axes:
        mesh = None  # no usable graph axis: run the unsharded program
    # the backend decision must be LAYOUT-INVARIANT (gtot, not g_pad; no
    # nshards factor) or mesh and no-mesh runs could pick different
    # samplers near the cap and break the bit-identity contract; meshes
    # with spare aggregate memory can force backend="device" instead
    use_device = exact or backend == "device" or (
        backend == "auto"
        and lookup is not None
        and gtot * ask0 <= kpgm.DEVICE_MAX_CANDIDATES
        and slots_fit(ask0)
    )
    if not use_device:
        if S > 1:
            raise DeviceBatchUnavailable(
                "fused sample_batch needs the device backend "
                f"(backend={backend!r}, candidates={gtot * ask0})"
            )
        if targets_given:
            # the host reference path draws its own per-block X ~ N(m, m-v)
            # and cannot honor an explicit target; callers (KPGMSampler)
            # catch this and run their own target-honoring host loop
            raise DeviceBatchUnavailable(
                "targets override needs the device backend "
                f"(backend={backend!r}, candidates={gtot * ask0})"
            )
        if backend == "auto":
            fallback(
                DISPATCH_COUNTERS,
                "host_fallbacks",
                f"backend='auto' is sampling on the host: {gtot} graphs x "
                f"{ask0} slots is over DEVICE_MAX_CANDIDATES="
                f"{kpgm.DEVICE_MAX_CANDIDATES} or the counter-PRNG slot "
                "limit, or the plan has no device lookup",
            )
        edges, st = _quilt_sample_host(
            key, plan, max_rounds=max_rounds, oversample=oversample
        )
        return QuiltRun(
            plan, 1, targets, np.zeros(gtot, np.int64), None, None, None,
            0, (), edges, st,
        )

    tail: List[Tuple[int, np.ndarray]] = []
    counts = np.zeros(gtot, dtype=np.int64)
    seen_cfg: Optional[List[np.ndarray]] = None
    outs = None
    shortfall = targets.copy()
    key, rkey = jax.random.split(key)
    a_tot = 0

    if total > 0:
        gids_j, tpad_j = _pad_inputs(gtot, g_pad, targets)
        tables = lookup
        rounds: Tuple[int, ...] = ()
        for r in range(1 if exact else max_rounds):
            chaos.maybe_fail("quilt.round")
            ask = budget if exact else dedup.uniform_ask(shortfall, oversample)
            if ask == 0:
                break
            if rounds and (
                gtot * (sum(rounds) + ask) > kpgm.DEVICE_MAX_CANDIDATES
                or not slots_fit(sum(rounds) + ask)
            ):
                # the cumulative stream would outgrow the device budget
                # (near-saturated targets): let the host fallback finish the
                # residual instead of OOMing.  Like the backend decision,
                # this guard is layout-invariant (gtot * total, no nshards),
                # so every mesh breaks at the same round with the same state.
                break
            # each dispatch re-processes [prior rounds || fresh draws] as one
            # longer per-graph stream: the seen keys are carried through the
            # segmented dedup on-device, nothing returns to the host but the
            # per-graph counts
            rounds = rounds + (ask,)
            with tracing.span(
                "quilt.round", round=r, ask=ask, slots=gtot * sum(rounds)
            ):
                while True:
                    try:
                        chaos.maybe_fail("quilt.dispatch")
                        fn = _compiled_round(
                            mesh, axes, rounds, plan.B, use_kernel,
                            len(tables), exact,
                        )
                        outs = dedup.call_x64(
                            fn, rkey, gids_j, tpad_j, plan.cum, plan.thetas,
                            tables,
                        )
                        break
                    except chaos.DeviceLoss as exc:
                        # the device is gone — retrying the same program
                        # fails identically, so rebuild over the survivors
                        # and re-run the round (bit-exact, see
                        # _degrade_layout)
                        mesh, axes, g_pad = _degrade_layout(mesh, exc, gtot)
                        gids_j, tpad_j = _pad_inputs(gtot, g_pad, targets)
            DISPATCH_COUNTERS[
                "device_rounds" if r == 0 else "device_topup_rounds"
            ] += 1
            with tracing.span("quilt.round_wait", round=r):
                # the wait for the round, apart from the copy of its counts
                outs[5].block_until_ready()
            counts = transfer.to_host(outs[5]).astype(np.int64)[:gtot]
            # exact mode has no shortfall concept: the thinning already
            # realized each cell's Bernoulli draw, counts ARE the result
            shortfall = np.zeros_like(targets) if exact else targets - counts
            if shortfall.max(initial=0) <= 0:
                break
        a_tot = sum(rounds)
        ENGINE_COUNTERS["candidate_slots"] += gtot * a_tot
        if kernel_lookup(tables, use_kernel):
            ENGINE_COUNTERS["kernel_lookup_slots"] += gtot * a_tot

    keep = None
    snode = dnode = None
    if outs is not None:
        scfg, dcfg, snode, dnode, take, _ = outs
        with tracing.span("quilt.mask"):
            take_h = transfer.to_host(take)
            keep = (
                take_h
                & (transfer.to_host(snode) >= 0)
                & (transfer.to_host(dnode) >= 0)
            )
        if shortfall.max(initial=0) > 0:
            # pathological: max_rounds device rounds still short — fall back
            # to the PR-1 host rejection loop for the residual
            fallback(
                DISPATCH_COUNTERS,
                "degraded_fallbacks",
                f"device rounds exhausted (max_rounds={max_rounds}, "
                f"{a_tot} slots/graph) with {int(shortfall.sum())} edges "
                "still short: finishing the residual with the host "
                "rejection loop (raise max_rounds or oversample to stay "
                "device-resident)",
            )
            flat_taken = (
                transfer.to_host(scfg)[take_h].astype(np.int64) * ncfg
                + transfer.to_host(dcfg)[take_h].astype(np.int64)
            )
            full_counts = transfer.to_host(outs[5], moves=False).astype(
                np.int64
            )
            seen_cfg = list(
                np.split(flat_taken, np.cumsum(full_counts)[:-1])
            )[:gtot]

    if seen_cfg is not None:
        counts = _host_quilt_topup(
            key, plan, targets, counts, seen_cfg, tail, max_rounds, oversample
        )

    if exact:
        # the realized per-graph cell counts are the only meaningful
        # "targets" of an exact run
        targets = counts.copy()
    return QuiltRun(
        plan, S, targets, counts, snode, dnode, keep, a_tot, tuple(tail),
        None, None, nodes_fetched=outs is not None,
    )


def quilt_sample(
    key: jax.Array,
    params: magm.MAGMParams,
    F: np.ndarray,
    *,
    max_rounds: int = 8,
    oversample: float = 1.05,
    backend: str = "auto",
    use_kernel: Optional[bool] = None,
    mesh=None,
    return_stats: bool = False,
    exact_cells: Optional[bool] = None,
) -> np.ndarray | Tuple[np.ndarray, QuiltStats]:
    """DEPRECATED shim over ``repro.api.MAGMSampler`` — sample one MAGM graph.

    Delegates to the session engine (:func:`quilt_run`) through the global
    plan cache, and is pinned bit-identical to
    ``MAGMSampler(SamplerConfig(params=params, F=F, ...)).sample(key)`` by
    test.  New code should hold a session: repeated ``.sample()`` calls
    amortize the partition/plan build and the per-call content digest this
    shim pays every time.  See docs/API.md for the migration table.

    ``F`` is the (n, d) attribute matrix (sample with magm.sample_attributes
    or supply observed attributes).  ``backend``/``use_kernel``/``mesh``
    behave exactly as on :class:`repro.api.SamplerConfig`: the default
    backend runs the device-resident pipeline, ``mesh=`` shards the B^2
    block-pair streams bit-identically across any device count.
    """
    _warn_shim("quilt_sample", "repro.api.MAGMSampler.sample")
    F = np.asarray(F)
    if F.size == 0:
        out = np.zeros((0, 2), dtype=np.int64)
        if return_stats:
            return out, QuiltStats(0, 0, 0, 0, 0, 0, None)
        return out
    run = quilt_run(
        key,
        get_quilt_plan(F, params.thetas),
        max_rounds=max_rounds,
        oversample=oversample,
        backend=backend,
        use_kernel=use_kernel,
        mesh=mesh,
        exact_cells=exact_cells,
    )
    out = run.edges()
    # Blocks are disjoint in node space (each (i, j) pair belongs to exactly
    # one (|Z_i|, |Z_j|) block), so no cross-block dedup is needed.
    if return_stats:
        return out, run.stats(out.shape[0])
    return out


def _host_quilt_topup(
    key: jax.Array,
    plan: QuiltPlan,
    targets: np.ndarray,
    counts: np.ndarray,
    seen_cfg: List[np.ndarray],
    tail: List[Tuple[int, np.ndarray]],
    max_rounds: int,
    oversample: float,
) -> np.ndarray:
    """Finish the duplicate-collision shortfall of the device round.

    Per top-up round: ONE small device batch shared across the short graphs,
    then host-side arrival-order dedup + block lookup (the shortfall is a few
    edges, so the O(B) python loop here is off the hot path).  Appends
    ``(graph_id, (E, 2))`` pieces to ``tail`` in arrival order."""
    ncfg = 1 << plan.d
    part = plan.part
    for _ in range(max_rounds):
        needs = targets - counts
        if needs.max(initial=0) <= 0:
            break
        asks, batch = dedup.plan_asks(needs, oversample)
        key, sub = jax.random.split(key)
        s2, d2 = kpgm.sample_edge_batch(sub, plan.thetas, batch)
        DISPATCH_COUNTERS["host_topup_rounds"] += 1
        flat = np.asarray(s2, dtype=np.int64) * ncfg + np.asarray(
            d2, dtype=np.int64
        )
        off = 0
        for g, ask in enumerate(np.asarray(asks)):
            if ask == 0:
                continue
            chunk = flat[off : off + int(ask)]
            off += int(ask)
            _, first_idx = np.unique(chunk, return_index=True)
            in_order = chunk[np.sort(first_idx)]
            fresh = in_order[~np.isin(in_order, seen_cfg[g])]
            fresh = fresh[: int(needs[g])]
            if fresh.size == 0:
                continue
            seen_cfg[g] = np.concatenate([seen_cfg[g], fresh])
            counts[g] += fresh.size
            blk = g % (plan.B * plan.B)  # sample-major gid for fused batches
            k, l = blk // plan.B, blk % plan.B
            sn = partition.lookup_nodes(
                part.sorted_configs[k], part.sorted_nodes[k], fresh // ncfg
            )
            dn = partition.lookup_nodes(
                part.sorted_configs[l], part.sorted_nodes[l], fresh % ncfg
            )
            keep = (sn >= 0) & (dn >= 0)
            if keep.any():
                tail.append(
                    (g, np.stack([sn[keep], dn[keep]], axis=1))
                )
    return counts


def _quilt_sample_host(
    key: jax.Array,
    plan: QuiltPlan,
    *,
    max_rounds: int,
    oversample: float,
) -> Tuple[np.ndarray, QuiltStats]:
    """PR-1 reference path: kpgm_sample_many + per-block host lookup.

    The rejection knobs come from the caller's config (quilt_run), so the
    host backend obeys the same ``max_rounds``/``oversample`` as the device
    pipeline — note this changed the host-path candidate stream vs PR 3,
    which ran kpgm_sample_many at its own oversample=1.1 default."""
    part = plan.part
    kp = kpgm.KPGMParams(plan.thetas)
    edges = []
    draws = part.B * part.B
    kpgm_total = 0
    key, sub = jax.random.split(key)
    graphs = kpgm.kpgm_sample_many(
        sub, kp, draws, max_rounds=max_rounds, oversample=oversample
    )
    for k in range(part.B):
        for l in range(part.B):
            e = graphs[k * part.B + l]
            kpgm_total += e.shape[0]
            if e.shape[0] == 0:
                continue
            src = partition.lookup_nodes(
                part.sorted_configs[k], part.sorted_nodes[k], e[:, 0]
            )
            dst = partition.lookup_nodes(
                part.sorted_configs[l], part.sorted_nodes[l], e[:, 1]
            )
            keep = (src >= 0) & (dst >= 0)
            if keep.any():
                edges.append(np.stack([src[keep], dst[keep]], axis=1))

    out = (
        np.concatenate(edges, axis=0)
        if edges
        else np.zeros((0, 2), dtype=np.int64)
    )
    return out, QuiltStats(
        B=part.B,
        num_kpgm_draws=draws,
        kpgm_edges_total=kpgm_total,
        kept_edges=out.shape[0],
        heavy_groups=0,
        light_nodes=plan.n,
        bprime=None,
    )


# ---------------------------------------------------------------------------
# Section 5: split sampler for unbalanced mu
# ---------------------------------------------------------------------------


def _er_block(
    rng: np.random.Generator, ns: int, nt: int, p: float
) -> np.ndarray:
    """Erdos-Renyi directed block: each of the ns*nt cells is an edge w.p. p.

    Distributionally equivalent to the paper's geometric skip-sampling: draw
    the edge COUNT ~ Binomial(ns*nt, p), then place that many distinct cells
    uniformly (the single-block case of :func:`_sample_cells`, which the
    batched R^2 heavy path uses directly).
    """
    cells = ns * nt
    if cells == 0 or p <= 0.0:
        return np.zeros((0, 2), dtype=np.int64)
    count = rng.binomial(cells, min(p, 1.0))
    if count == 0:
        return np.zeros((0, 2), dtype=np.int64)
    flat = _sample_cells(
        rng, np.array([count], np.int64), np.array([cells], np.int64)
    )
    return np.stack([flat // nt, flat % nt], axis=1).astype(np.int64)


def choose_bprime(
    counts: np.ndarray, n: int, d: int, expected_e: float
) -> Tuple[int, float]:
    """Minimise T(B') = B'^2 log(n) |E| + (|W| + d) R + d R^2 over candidate B'.

    ``counts`` are the multiplicities of the distinct configurations.  The
    cost is a step function of B' that only changes at the distinct
    multiplicity values, so the candidates are those values plus B' = 0
    (every configuration heavy, empty light part) — without the 0 candidate
    an all-heavy optimum below ``min(counts)`` could never be chosen.  Empty
    ``counts`` (no nodes / no configurations) degenerates to (0, 0.0).
    """
    counts = np.sort(np.asarray(counts, dtype=np.int64).reshape(-1))
    if counts.size == 0:
        return 0, 0.0
    log_n = max(np.log2(max(n, 2)), 1.0)
    cands = np.concatenate([[0], np.unique(counts)])
    best_bp, best_t = int(counts.max()), float("inf")
    for bp in cands:
        heavy = counts > bp
        r = int(heavy.sum())
        w = int(counts[~heavy].sum())
        t = float(bp) ** 2 * log_n * max(expected_e, 1.0) + (w + d) * r + d * r * r
        if t < best_t:
            best_t, best_bp = t, int(bp)
    return best_bp, best_t


class SplitPlan(NamedTuple):
    """Precomputed state for the Section-5 split sampler.

    Everything that depends only on (F, thetas, bprime): the heavy/light
    split, the per-pair scalar edge probabilities (bilinear form), and the
    light-subgraph QuiltPlan.  Sessions (``repro.api.MAGMSampler`` with
    ``split=True``) build this ONCE and amortize it across samples — the
    probability matrices alone were previously recomputed on every
    ``quilt_sample_fast`` call.

    The ``blk_*`` tail is the device-resident heavy path: every heavy ER
    unit — R^2 heavy-heavy blocks plus 2 |W| R one-node strip cells per
    direction — is a "uniform block" of ``rows x cols`` cells sharing one
    scalar p.  One fixed-shape round of ``heavy_budget`` weighted proposals
    (block ~ w_m = rows * cols * p_m, cell uniform within the block) +
    per-cell exact-Bernoulli thinning (``blk_alpha``) + the segmented
    node-pair dedup realizes all of them in a single jitted dispatch,
    replacing the host numpy binomial.  ``heavy_budget`` is None when the
    exact budget is unaffordable (host fallback) and 0 when there is no
    heavy mass at all.
    """

    n: int
    d: int
    bprime: int
    W: np.ndarray  # light node ids
    heavy_cfgs: np.ndarray  # (R,) heavy configuration ids
    sizes: np.ndarray  # (R,) heavy group sizes
    offs: np.ndarray  # (R,) offsets into cat
    cat: np.ndarray  # concatenated heavy group node ids
    p_hh: np.ndarray  # (R, R) heavy-heavy edge probabilities
    p_wh: np.ndarray  # (|W|, R) light-source strip probabilities
    p_hw: np.ndarray  # (R, |W|) heavy-source strip probabilities
    light_plan: Optional[QuiltPlan]  # quilt plan of F[W] (None if W empty)
    pool: Optional[jax.Array] = None  # (|cat| + |W|,) int32 node id pool
    blk_rows: Optional[jax.Array] = None  # (M,) int32 rows per block
    blk_cols: Optional[jax.Array] = None  # (M,) int32 cols per block
    blk_src_base: Optional[jax.Array] = None  # (M,) int32 pool offset (rows)
    blk_dst_base: Optional[jax.Array] = None  # (M,) int32 pool offset (cols)
    blk_alpha: Optional[jax.Array] = None  # (M,) f32 per-cell accept prob
    blk_cumw: Optional[jax.Array] = None  # (M,) f64 normalized cum weights
    heavy_budget: Optional[int] = None  # proposals G; None -> host fallback
    heavy_mean: float = 0.0  # S_h = expected heavy-part edges

    @property
    def R(self) -> int:
        return int(self.heavy_cfgs.size)


def build_split_plan(
    F: np.ndarray,
    params: magm.MAGMParams,
    bprime: Optional[int] = None,
    *,
    use_cache: bool = False,
) -> SplitPlan:
    """Derive the Section-5 split for (F, params); ``bprime=None`` minimises
    the paper's cost model T(B') via :func:`choose_bprime`.

    ``use_cache=True`` routes the light-subgraph plan through the global
    content-keyed cache (the shim path); sessions leave it False and own
    the plan."""
    F = np.asarray(F)
    n, d = F.shape
    lam = np.asarray(magm.configs_from_attributes(jnp.asarray(F)))
    uniq, counts = np.unique(lam, return_counts=True)
    if bprime is None:
        bprime, _ = choose_bprime(
            counts, n, d, magm.expected_edges(params, n)
        )

    heavy_cfgs = uniq[counts > bprime]
    node_is_heavy = np.isin(lam, heavy_cfgs)
    W = np.nonzero(~node_is_heavy)[0]  # light nodes
    heavy_groups = [np.nonzero(lam == c)[0] for c in heavy_cfgs]
    R = len(heavy_groups)

    sizes = np.array([g.size for g in heavy_groups], dtype=np.int64)
    offs = (
        np.concatenate([[0], np.cumsum(sizes)[:-1]])
        if R
        else np.zeros(0, dtype=np.int64)
    )
    cat = (
        np.concatenate(heavy_groups) if R else np.zeros(0, dtype=np.int64)
    )
    p_hh = np.zeros((0, 0))
    p_wh = np.zeros((W.size, 0))
    p_hw = np.zeros((0, W.size))
    if R:
        heavy_attr = jnp.asarray(
            magm.attributes_from_configs(jnp.asarray(heavy_cfgs), d)
        )
        p_hh = np.minimum(
            np.exp(
                np.asarray(
                    magm.log_edge_prob(heavy_attr, heavy_attr, params.thetas)
                )
            ),
            1.0,
        )
        if W.size:
            FW = jnp.asarray(F[W])
            p_wh = np.minimum(
                np.exp(
                    np.asarray(
                        magm.log_edge_prob(FW, heavy_attr, params.thetas)
                    )
                ),
                1.0,
            )
            p_hw = np.minimum(
                np.exp(
                    np.asarray(
                        magm.log_edge_prob(heavy_attr, FW, params.thetas)
                    )
                ),
                1.0,
            )

    light_plan = None
    if W.size:
        light_plan = (
            get_quilt_plan(F[W], params.thetas)
            if use_cache
            else build_quilt_plan(F[W], params.thetas)
        )
    return SplitPlan(
        n=n, d=d, bprime=int(bprime), W=W, heavy_cfgs=heavy_cfgs,
        sizes=sizes, offs=offs, cat=cat, p_hh=p_hh, p_wh=p_wh, p_hw=p_hw,
        light_plan=light_plan,
        **_heavy_device_state(n, W, sizes, offs, cat, p_hh, p_wh, p_hw),
    )


def _heavy_device_state(n, W, sizes, offs, cat, p_hh, p_wh, p_hw) -> dict:
    """Device-resident decode state for the heavy ER part of a SplitPlan.

    Flattens every heavy unit into one list of M uniform blocks over a
    shared node-id ``pool = [cat ‖ W]``: heavy-heavy block (a, b) spans
    ``sizes[a] x sizes[b]`` cells at pool offsets ``(offs[a], offs[b])``;
    light->heavy strip cell (i, b) is a ``1 x sizes[b]`` block whose single
    source row is pool slot ``|cat| + i`` (and transposed for
    heavy->light).  Proposal weights ``w_m = rows * cols * p_m`` make the
    per-CELL proposal law exactly ``p_m / S_h`` — a plan constant — so the
    exact-cell acceptance ``alpha_m = p_m / (1 - (1 - p_m/S_h)^G)`` is
    precomputed per block, and the sampling round needs no probability
    math at all.  All arrays are device-put at build time (the warm path
    ships nothing under ``transfer_guard("disallow")``); ``blk_cumw`` is
    f64 (placed under ``jax.enable_x64``) because block selection by
    searchsorted over up to ~1e5 blocks needs more than f32's 2^-24 grid.
    """
    R = int(sizes.size)
    if R == 0:
        return {}
    C = int(cat.size)
    s64 = sizes.astype(np.int64)
    rows = [np.repeat(s64, R)]
    cols = [np.tile(s64, R)]
    src_base = [np.repeat(offs, R)]
    dst_base = [np.tile(offs, R)]
    probs = [p_hh.reshape(-1).astype(np.float64)]
    if W.size:
        wi = np.arange(W.size, dtype=np.int64)
        ones = np.ones(W.size * R, dtype=np.int64)
        # light -> heavy: one (1 x sizes[b]) block per (i, b), row-major
        rows.append(ones)
        cols.append(np.tile(s64, W.size))
        src_base.append(C + np.repeat(wi, R))
        dst_base.append(np.tile(offs, W.size))
        probs.append(p_wh.reshape(-1).astype(np.float64))
        # heavy -> light: one (sizes[b] x 1) block per (i, b)
        rows.append(np.tile(s64, W.size))
        cols.append(ones)
        src_base.append(np.tile(offs, W.size))
        dst_base.append(C + np.repeat(wi, R))
        probs.append(p_hw.T.reshape(-1).astype(np.float64))
    rows = np.concatenate(rows)
    cols = np.concatenate(cols)
    src_base = np.concatenate(src_base)
    dst_base = np.concatenate(dst_base)
    probs = np.concatenate(probs)
    w = rows.astype(np.float64) * cols.astype(np.float64) * probs
    s_h = float(w.sum())
    if s_h <= 0.0:
        return {"heavy_budget": 0, "heavy_mean": 0.0}
    budget = _exact_budget(float(probs.max()), s_h)
    if budget is None or budget > kpgm.DEVICE_MAX_CANDIDATES:
        return {"heavy_mean": s_h}  # heavy_budget None: host fallback
    pi = np.minimum(probs / s_h, 1.0 - 1e-12)
    q = -np.expm1(float(budget) * np.log1p(-pi))
    alpha = np.where(q > 0.0, np.minimum(probs / q, 1.0), 0.0)
    cumw = np.cumsum(w) / s_h
    cumw[-1] = 1.0
    pool = np.concatenate([cat, W]).astype(np.int32)
    with jax.enable_x64(True):
        state = {
            "pool": jax.device_put(pool),
            "blk_rows": jax.device_put(rows.astype(np.int32)),
            "blk_cols": jax.device_put(cols.astype(np.int32)),
            "blk_src_base": jax.device_put(src_base.astype(np.int32)),
            "blk_dst_base": jax.device_put(dst_base.astype(np.int32)),
            "blk_alpha": jax.device_put(alpha.astype(np.float32)),
            "blk_cumw": jax.device_put(cumw),
        }
    state["heavy_budget"] = int(budget)
    state["heavy_mean"] = s_h
    return state


def rng_from_key(key: jax.Array) -> np.random.Generator:
    """Deterministic numpy Generator derived from a JAX PRNG key.

    The Section-5 split sampler's heavy ER blocks are device-resident now
    (:func:`_split_heavy_body`); this router remains for the two paths that
    still draw them with numpy — the deprecated ``quilt_sample_fast(seed=)``
    alias (which pins the old host binomial stream) and the
    ``heavy_budget is None`` fallback when the exact proposal budget would
    exceed ``DEVICE_MAX_CANDIDATES``.  Deriving the generator from the SAME
    key that drives the quilted light part keeps the one-key contract.

    Raw ``PRNGKey`` uint32 arrays are canonicalized to typed keys up front,
    so both representations of the same key run the identical fold + data
    extraction path and yield the identical generator (pinned by test) —
    rather than relying on ``jax.random.key_data`` happening to accept raw
    arrays in the installed jax version."""
    arr = jnp.asarray(key)
    if not jnp.issubdtype(arr.dtype, jax.dtypes.prng_key):
        key = jax.random.wrap_key_data(arr.astype(jnp.uint32))
    # jitted so the fold constant is baked into one compiled program: an
    # eager fold_in ships a fresh uint32 scalar host->device on EVERY call
    # (caught by the transfer-guard sanitizer on the split hot path)
    data = _fold_key_data(key)
    entropy = [int(x) for x in np.asarray(data, dtype=np.uint32).ravel()]
    return np.random.default_rng(entropy)


@jax.jit
def _fold_key_data(key: jax.Array) -> jax.Array:
    return jax.random.key_data(jax.random.fold_in(key, 0x5EED))


def _node_bits(n: int) -> int:
    """Bits needed to pack a node id of [0, n) (same as balldrop's)."""
    return max(int(n - 1).bit_length(), 1) if n > 1 else 1


def _split_heavy_body(
    hkey: jax.Array,
    pool: jax.Array,
    blk_rows: jax.Array,
    blk_cols: jax.Array,
    blk_src_base: jax.Array,
    blk_dst_base: jax.Array,
    blk_alpha: jax.Array,
    blk_cumw: jax.Array,
    *,
    budget: int,
    node_bits: int,
):
    """One fixed-shape device round realizing ALL heavy ER units at once.

    Proposal s picks block m ~ blk_cumw by a 48-bit counter uniform (two
    hash channels — f32's 24 bits would quantize the block law over ~1e5
    strip cells), then a uniform cell within the block from two more
    channels.  The per-cell proposal probability is exactly
    ``p_m / heavy_mean`` by the ``rows * cols * p`` weighting, so the
    precomputed ``blk_alpha`` thinning makes every CELL (= node pair; the
    accept hash is keyed by the packed pair) exactly Bernoulli(p_m), and
    the segmented node-pair dedup emits each accepted cell once — the same
    exact-cell contract as the quilt/balldrop engines.  Call under
    ``dedup.call_x64`` (uint64/f64 inside).
    """
    seed = ops.counter_seed(hkey)
    s0, s1 = seed[0, 0], seed[0, 1]
    gid0 = jnp.int32(0)
    base = jnp.arange(budget, dtype=jnp.uint32) * jnp.uint32(
        ops.PRNG_CHANNELS
    )
    hi = ops.counter_hash(s0, s1, gid0, base).astype(jnp.uint64)
    lo = ops.counter_hash(s0, s1, gid0, base + jnp.uint32(1)).astype(
        jnp.uint64
    )
    u_blk = (hi >> jnp.uint64(8)).astype(jnp.float64) * (2.0**-24) + (
        lo >> jnp.uint64(8)
    ).astype(jnp.float64) * (2.0**-48)
    m = jnp.clip(
        jnp.searchsorted(blk_cumw, u_blk, side="right"),
        0,
        blk_cumw.shape[0] - 1,
    ).astype(jnp.int32)
    rows = blk_rows[m]
    cols = blk_cols[m]
    u_r = ops.counter_u01(s0, s1, gid0, base + jnp.uint32(2))
    u_c = ops.counter_u01(s0, s1, gid0, base + jnp.uint32(3))
    r = jnp.minimum(
        (u_r * rows.astype(jnp.float32)).astype(jnp.int32), rows - 1
    )
    c = jnp.minimum(
        (u_c * cols.astype(jnp.float32)).astype(jnp.int32), cols - 1
    )
    src = pool[blk_src_base[m] + r]
    dst = pool[blk_dst_base[m] + c]
    # heavy/light node sets are disjoint and blocks tile disjoint pair
    # rectangles, so the packed node pair uniquely identifies the cell —
    # duplicates of one cell share one accept bit (cell-as-a-unit thinning)
    pair = src.astype(jnp.int64) * jnp.int64(1 << node_bits) + dst.astype(
        jnp.int64
    )
    salt = jax.random.bits(
        jax.random.fold_in(hkey, 0x5EED), (), jnp.uint64
    )
    accept = _accept_u01(salt, gid0, pair) < blk_alpha[m]
    local = jnp.zeros(budget, dtype=jnp.int32)
    cum_asks = jnp.array([budget], dtype=jnp.int32)
    targets = jnp.array([budget], dtype=jnp.int64)
    take, _ = dedup.segmented_unique_mask(
        local, src, dst, cum_asks, targets,
        node_bits=node_bits, max_ask=budget, valid=accept,
    )
    return src, dst, take


@functools.lru_cache(maxsize=32)
def _compiled_split_heavy(jit_budget: int, jit_node_bits: int):
    """Jit one heavy-round program per (budget, node_bits) — both plan
    constants, so warm split sessions never recompile (sanitizer-pinned).

    The parameter names are deliberately NOT ``budget``/``node_bits``: the
    lint call graph follows straight-line name aliases into ``jax.jit``
    arguments, and those generic names alias to unrelated host-side
    assignments elsewhere in this module."""
    return jax.jit(
        functools.partial(
            _split_heavy_body, budget=jit_budget, node_bits=jit_node_bits
        )
    )


def split_run(
    key: jax.Array,
    sp: SplitPlan,
    rng: Optional[np.random.Generator] = None,
    *,
    max_rounds: int = 8,
    oversample: float = 1.05,
    backend: str = "auto",
    use_kernel: Optional[bool] = None,
    mesh=None,
) -> Tuple[np.ndarray, QuiltStats]:
    """Execute the Section-5 split sampler for a prebuilt :class:`SplitPlan`.

    Quilts the light-light subgraph through :func:`quilt_run` and realizes
    the heavy blocks / strips (the ball-dropping regime of Moreno et al.,
    arXiv:1202.6001) in ONE jitted device round (:func:`_split_heavy_body`)
    keyed by a sibling split of ``key`` — the whole sampler is
    device-resident and zero-transfer when warm.  ``rng`` is the legacy
    escape hatch: passing a numpy Generator draws the heavy part with the
    old host binomial + distinct-cell placement (the deprecated
    ``quilt_sample_fast(seed=)`` alias pins that stream), and the device
    path falls back to it (derived via :func:`rng_from_key`) when
    ``sp.heavy_budget`` is None (exact budget past DEVICE_MAX_CANDIDATES).
    """
    W = sp.W
    R = sp.R
    pieces = []
    stats_b = 0
    draws = kp_total = 0
    key, hkey = jax.random.split(key)

    # (1) light x light: quilt the W-subgraph (configs unchanged; B <= B').
    if W.size:
        key, sub = jax.random.split(key)
        run = quilt_run(
            sub, sp.light_plan, max_rounds=max_rounds,
            oversample=oversample, backend=backend, use_kernel=use_kernel,
            mesh=mesh,
        )
        ew = run.edges()
        st = run.stats(ew.shape[0])
        stats_b, draws, kp_total = st.B, st.num_kpgm_draws, st.kpgm_edges_total
        if ew.size:
            pieces.append(np.stack([W[ew[:, 0]], W[ew[:, 1]]], axis=1))

    device_heavy = R > 0 and rng is None and sp.heavy_budget is not None
    if device_heavy:
        # (2+3) every heavy block and strip in one fixed-shape dispatch
        if sp.heavy_budget > 0:
            fn = _compiled_split_heavy(
                sp.heavy_budget, _node_bits(sp.n)
            )
            src, dst, take = dedup.call_x64(
                fn, hkey, sp.pool, sp.blk_rows, sp.blk_cols,
                sp.blk_src_base, sp.blk_dst_base, sp.blk_alpha,
                sp.blk_cumw,
            )
            keep = transfer.to_host(take)
            if keep.any():
                sn = transfer.to_host(src)[keep]
                dn = transfer.to_host(dst)[keep]
                pieces.append(
                    np.stack([sn, dn], axis=1).astype(np.int64)
                )
    elif R:
        if rng is None:
            fallback(
                DISPATCH_COUNTERS,
                "host_fallbacks",
                "split heavy part on the host: its exact proposal budget is "
                f"over DEVICE_MAX_CANDIDATES={kpgm.DEVICE_MAX_CANDIDATES}",
            )
            rng = rng_from_key(key)
        sizes, offs, cat = sp.sizes, sp.offs, sp.cat
        # (2) heavy x heavy blocks (including the diagonal): scalar-p ER
        # blocks, all R^2 at once — one batched binomial for the counts and
        # one _sample_cells call for every block's distinct flat cell ids.
        cells = sizes[:, None] * sizes[None, :]
        counts_hh = rng.binomial(cells, sp.p_hh).reshape(-1)
        cell_ids = _sample_cells(rng, counts_hh, cells.reshape(-1))
        if cell_ids.size:
            rep = np.repeat(np.arange(R * R), counts_hh)
            a, b = rep // R, rep % R
            rr, cc = cell_ids // sizes[b], cell_ids % sizes[b]
            pieces.append(
                np.stack([cat[offs[a] + rr], cat[offs[b] + cc]], axis=1)
            )

        # (3) light x heavy and heavy x light strips: per light node i the
        # probability against group b is the scalar P_{lam_i, lam'_b}; both
        # directions batch the |W| x R binomials and share one _sample_cells.
        if W.size:
            sizes_rep = np.tile(sizes, W.size)
            for p, flip in ((sp.p_wh, False), (sp.p_hw.T, True)):
                counts_s = rng.binomial(
                    sizes[None, :], p
                ).reshape(-1)  # row-major over (light i, group b)
                cols = _sample_cells(rng, counts_s, sizes_rep)
                if not cols.size:
                    continue
                rep = np.repeat(np.arange(W.size * R), counts_s)
                i, b = rep // R, rep % R
                light = W[i]
                heavy = cat[offs[b] + cols]
                pieces.append(
                    np.stack(
                        [heavy, light] if flip else [light, heavy], axis=1
                    )
                )

    out = (
        dedup.dedup_edges(np.concatenate(pieces, axis=0))
        if pieces
        else np.zeros((0, 2), dtype=np.int64)
    )
    return out, QuiltStats(
        B=stats_b,
        num_kpgm_draws=draws,
        kpgm_edges_total=kp_total,
        kept_edges=out.shape[0],
        heavy_groups=R,
        light_nodes=int(W.size),
        bprime=int(sp.bprime),
    )


_SEED_UNSET = object()


def quilt_sample_fast(
    key: jax.Array,
    params: magm.MAGMParams,
    F: np.ndarray,
    *,
    bprime: Optional[int] = None,
    seed=_SEED_UNSET,
    mesh=None,
    backend: str = "auto",
    use_kernel: Optional[bool] = None,
    return_stats: bool = False,
) -> np.ndarray | Tuple[np.ndarray, QuiltStats]:
    """DEPRECATED shim over ``repro.api.MAGMSampler`` (``split=True``) —
    Section-5 sampler: quilt the light nodes, ER-sample the heavy blocks.

    Configurations occurring more than ``bprime`` times become R "heavy"
    groups whose block pairs are scalar-p Erdos-Renyi draws; the remaining
    light nodes are quilted (``mesh`` shards that part across devices).
    ``bprime=None`` minimises the paper's cost model T(B') via
    :func:`choose_bprime`.

    The whole draw is keyed by ``key`` alone and the heavy ER part runs
    device-resident (:func:`_split_heavy_body`), matching every other
    sampler.  ``seed=`` survives one release as a deprecated alias that
    pins the old host numpy binomial stream.  Pinned bit-identical by test
    to ``MAGMSampler(SamplerConfig(..., split=True)).sample(key)``.
    """
    _warn_shim(
        "quilt_sample_fast", "repro.api.MAGMSampler (SamplerConfig split=True)"
    )
    if seed is _SEED_UNSET:
        rng = None
    else:
        warnings.warn(
            "quilt_sample_fast(seed=...) is deprecated: omit it and the "
            "numpy stream derives from `key` (rng_from_key)",
            DeprecationWarning,
            stacklevel=2,
        )
        rng = np.random.default_rng(seed)
    sp = build_split_plan(F, params, bprime, use_cache=True)
    out, st = split_run(
        key, sp, rng, mesh=mesh, backend=backend, use_kernel=use_kernel
    )
    if return_stats:
        return out, st
    return out


_RESAMPLE_ROUNDS = 32
_DENSE_CHUNK_CELLS = 1 << 22  # cap the (rows, G) key matrix at ~32 MB


def _sample_cells(
    rng: np.random.Generator, counts: np.ndarray, sizes: np.ndarray
) -> np.ndarray:
    """For each row i, draw counts[i] DISTINCT integers in [0, sizes[i]).

    The generalisation of the old fixed-group ``_sample_cols`` to per-row
    ranges, so ALL R^2 heavy blocks (whose cell spaces differ) share one
    vectorised call.  counts are clipped to sizes; rows stay in order and
    zero-count rows contribute nothing.

    - DENSE rows (counts[i] > sizes[i] / 2) take the first counts[i] entries
      of a random-key argsort with out-of-range columns pushed to the end —
      an exact uniform draw without replacement, batched + chunked.
    - SPARSE rows draw with replacement, then only the colliding slots are
      redrawn, globally across all rows per round (duplicates are found with
      one sort over row-tagged keys); pathological rows fall back to an exact
      ``rng.choice(..., replace=False)``.
    """
    counts = np.asarray(counts, dtype=np.int64)
    sizes = np.asarray(sizes, dtype=np.int64)
    pos_mask = counts > 0
    pos = np.minimum(counts[pos_mask], sizes[pos_mask])
    sz = sizes[pos_mask]
    tot = int(pos.sum())
    if tot == 0:
        return np.empty(0, dtype=np.int64)
    seg_id = np.repeat(np.arange(pos.size, dtype=np.int64), pos)
    cols = np.empty(tot, dtype=np.int64)

    dense_seg = pos > sz // 2
    dense_slot = dense_seg[seg_id]
    if dense_seg.any():
        lens = pos[dense_seg]
        szs = sz[dense_seg]
        gmax = int(szs.max())
        picks = []
        rows_per_chunk = max(1, _DENSE_CHUNK_CELLS // max(gmax, 1))
        for lo in range(0, lens.size, rows_per_chunk):
            chunk_len = lens[lo : lo + rows_per_chunk]
            chunk_sz = szs[lo : lo + rows_per_chunk]
            keys = rng.random((chunk_len.size, gmax))
            keys[np.arange(gmax)[None, :] >= chunk_sz[:, None]] = 2.0
            order = np.argsort(keys, axis=1)
            mask = np.arange(gmax)[None, :] < chunk_len[:, None]
            picks.append(order[mask])  # row-major: chunk rows stay in order
        cols[dense_slot] = np.concatenate(picks)

    sparse_slot = ~dense_slot
    ns = int(sparse_slot.sum())
    if ns:
        sid = seg_id[sparse_slot]
        smax = int(sz.max())
        sub = rng.integers(0, sz[sid])
        dup = np.zeros(ns, dtype=bool)
        for _ in range(_RESAMPLE_ROUNDS):
            key = sid * smax + sub
            order = np.argsort(key, kind="stable")
            sk = key[order]
            dup[:] = False
            dup[order[1:]] = sk[1:] == sk[:-1]
            n_dup = int(dup.sum())
            if not n_dup:
                break
            sub[dup] = rng.integers(0, sz[sid[dup]])
        else:  # pathological rows: exact fallback, loops only over offenders
            for s in np.unique(sid[dup]):
                m = sid == s
                sub[m] = rng.choice(int(sz[s]), size=int(m.sum()), replace=False)
        cols[sparse_slot] = sub
    return cols


def _sample_cols(
    rng: np.random.Generator, counts: np.ndarray, group: np.ndarray
) -> np.ndarray:
    """For each row i, draw counts[i] distinct members of ``group`` (the
    fixed-group special case of :func:`_sample_cells`)."""
    counts = np.asarray(counts)
    cells = _sample_cells(
        rng, counts, np.full(counts.shape, group.size, dtype=np.int64)
    )
    return group[cells]


def naive_reference_sample(
    key: jax.Array, params: magm.MAGMParams, F: np.ndarray
) -> np.ndarray:
    """O(n^2) exact sampler (the paper's baseline); small n only."""
    Q = magm.edge_prob_matrix(jnp.asarray(np.asarray(F)), params.thetas)
    u = jax.random.uniform(key, Q.shape)
    adj = np.asarray(u < Q)
    src, dst = np.nonzero(adj)
    return np.stack([src, dst], axis=1).astype(np.int64)
