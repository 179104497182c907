"""Ball-dropping MAGM sampler (Moreno et al., arXiv:1202.6001) as a third
backend over the quilting plan.

Quilting (core/quilt.py) draws B^2 whole KPGM graphs and filters them down
to the realized attribute matrix.  Ball dropping inverts the loop: draw the
graph's EDGE COUNT up front, then place that many balls directly.  The
adaptation to the Theorem-2 partition machinery is what makes one ball
placement exact here:

1. **Target** — |E| conditional on F is a sum of independent
   Bernoulli(Q_ij), so one draw N ~ round(Normal(c^T P c, sqrt(Var))) with
   the Kronecker quadratic forms of core/kron.py (precomputed on the
   :class:`~repro.core.quilt.QuiltPlan` as ``bd_mean``/``bd_std``).
2. **Proposal** — each ball is a plain quadrant descent (config pair
   (x, y) with probability P_xy / m — the KPGM kernel path) plus two
   uniform ranks (k, l) in [0, B)^2.
3. **Rejection** — the ranks are mapped through the SAME per-block lookup
   tables the quilt uses: block k contains configuration x iff its
   multiplicity c_x >= k + 1, so the lookup hits with probability
   c_x c_y / B^2 and an accepted ball lands on node pair (i, j) with
   probability proportional to c_x c_y P_xy / (c_x c_y) = Q_ij exactly —
   a lookup MISS is the rejection step, for free.
4. **Dedup** — accepted balls stream through the segmented sort-based
   dedup of core/dedup.py over NODE pairs (``valid=`` masks the misses),
   with the same fixed-shape top-up rounds: round r's candidates are
   [all prior rounds || fresh draws], so arrival-order semantics are exact
   and only per-sample counts leave the device.

The result is returned as a :class:`~repro.core.quilt.QuiltRun`
(``sampler="balldrop"``, one dedup graph per sample), so sessions,
``sample_stream``, ``sample_batch`` and bit-identical ``mesh=`` sharding
are inherited unchanged from the quilting pipeline — here the mesh shards
SAMPLES (each sample's stream is keyed by ``fold_in(fold_in(round_key, r),
sample)``), which is layout-invariant for the same reason the quilt's
block-pair sharding is.
"""

from __future__ import annotations

import functools
import math
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core import dedup, kpgm, kron, partition, quilt, transfer
from repro.dist import chaos
from repro.kernels import ops

__all__ = ["balldrop_run", "DISPATCH_COUNTERS"]

# fused dispatches of the ball-dropping rounds (analogous to
# quilt.DISPATCH_COUNTERS; kept separate so the quilt's O(max_rounds)
# dispatch-count tests are unaffected by balldrop runs)
DISPATCH_COUNTERS = tracing.register(
    "balldrop.dispatch",
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


def _bd_round_body(
    rkey: jax.Array,
    gids: jax.Array,
    targets: jax.Array,
    cum: jax.Array,
    thetas: jax.Array,
    tables,
    *,
    rounds: Tuple[int, ...],
    num_blocks: int,
    node_bits: int,
    use_kernel: bool,
    exact: bool = False,
):
    """Per-shard fused ball-dropping round over a chunk of samples.

    Mirrors ``quilt._round_body`` with two twists: every candidate carries
    its own uniform block ranks (kb, lb) ~ U[0, B)^2 (the two reserved
    rank channels of the same counter-PRNG stream as the descent
    uniforms — ``ops.rank_pair``), and the
    segmented dedup runs over NODE pairs with the lookup misses masked out
    via ``valid=`` — a miss is the rejection step, so only accepted balls
    rank against the per-sample target.  Returns (snode, dnode, take,
    counts); call under dedup.call_x64.

    ``use_kernel`` picks the Pallas descent kernel (``ranks=True`` emits
    the rank channels too) or its bit-identical jnp twin.  ``tables``
    (``quilt.device_lookup``) selects the rank lookup: ``(inv,)`` for the
    dense-inverse gather, or the ``(cfg_offset, cfg_count, cfg_nodes)``
    by-config triple — the heavy-config short-circuit, where rank kb hits
    config x iff ``kb < c_x`` and indexes straight into x's node group
    (bit-identical to the dense inverse via the stable occurrence-rank
    order, but O(2^d + n) memory instead of O(B * 2^d), the win for skewed
    mu where B = c_max is large).

    ``exact=True`` composes the per-NODE-pair acceptance thinning of
    ``quilt._exact_cell_valid`` into the valid mask (pi = p_xy / (S B^2)
    per proposal via ``log_extra = 2 log B``), making node-pair inclusion
    exactly Bernoulli(Q_ij) in one plan-constant round.
    """
    d = cum.shape[0]
    gc = gids.shape[0]
    a_tot = int(sum(rounds))
    seed = ops.counter_seed(rkey)
    local, gid = quilt.graph_major(gids, a_tot)
    if use_kernel:
        scfg, dcfg, kb, lb = ops.descent_prng_pallas(
            seed, gids, cum, a_tot=a_tot, num_blocks=num_blocks, ranks=True,
        )
    else:
        slot = jnp.arange(gc * a_tot, dtype=jnp.int32) - local * a_tot
        u = ops.descent_uniforms(seed[0, 0], seed[0, 1], gid, slot, d)
        kb, lb = ops.rank_pair(
            seed[0, 0], seed[0, 1], gid, slot, num_blocks
        )
        scfg, dcfg = kpgm._descend(u, cum)
    snode, dnode = quilt.gather_nodes(tables, kb, lb, scfg, dcfg, d)
    valid = (snode >= 0) & (dnode >= 0)
    if exact:
        pair = snode.astype(jnp.int64) * jnp.int64(
            1 << node_bits
        ) + dnode.astype(jnp.int64)
        valid = valid & quilt._exact_cell_valid(
            rkey,
            gid,
            scfg,
            dcfg,
            thetas,
            rounds[0],
            log_extra=2.0 * math.log(float(num_blocks)),
            cell=pair,
        )
    cum_asks = jnp.arange(1, gc + 1, dtype=jnp.int32) * a_tot
    take, counts = dedup.segmented_unique_mask(
        local, snode, dnode, cum_asks, targets,
        node_bits=node_bits, valid=valid, max_ask=a_tot,
    )
    return snode, dnode, take, counts


@functools.lru_cache(maxsize=64)
def _compiled_bd_round(
    mesh,
    axes: Tuple[str, ...],
    rounds: Tuple[int, ...],
    num_blocks: int,
    node_bits: int,
    use_kernel: bool,
    num_tables: int,
    exact: bool = False,
):
    """Jit (and, with a mesh, shard_map over the sample axis) one round."""
    body = functools.partial(
        _bd_round_body,
        rounds=rounds,
        num_blocks=num_blocks,
        node_bits=node_bits,
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
            out_specs=(spec,) * 4,
            check_vma=False,
        )
    return jax.jit(body)


def _node_bits(n: int) -> int:
    return max(int(n - 1).bit_length(), 1) if n > 1 else 1


def _propose_host(key, plan, ask: int):
    """One host-side proposal batch: (snode, dnode) with -1 marking misses.

    The distributional twin of the device round's proposal step (descent +
    uniform ranks + per-block lookup), used by the host fallback and the
    top-up; the per-block lookup loops over the B sorted tables instead of
    the dense inverse.
    """
    part = plan.part
    B = plan.B
    uk, kk = jax.random.split(key)
    scfg, dcfg = kpgm.sample_edge_batch(uk, plan.thetas, ask)
    kl = np.asarray(
        jax.random.randint(kk, (ask, 2), 0, B, dtype=jnp.int32)
    )
    scfg = np.asarray(scfg, dtype=np.int64)
    dcfg = np.asarray(dcfg, dtype=np.int64)
    sn = np.full(ask, -1, dtype=np.int64)
    dn = np.full(ask, -1, dtype=np.int64)
    for b in range(B):
        m = kl[:, 0] == b
        if m.any():
            sn[m] = partition.lookup_nodes(
                part.sorted_configs[b], part.sorted_nodes[b], scfg[m]
            )
        m = kl[:, 1] == b
        if m.any():
            dn[m] = partition.lookup_nodes(
                part.sorted_configs[b], part.sorted_nodes[b], dcfg[m]
            )
    return sn, dn


def _balldrop_sample_host(
    key: jax.Array,
    plan: quilt.QuiltPlan,
    *,
    target: int,
    max_rounds: int,
    oversample: float,
) -> np.ndarray:
    """Host fallback: the same rejection process as the device rounds, with
    numpy arrival-order dedup (honors an explicit target, unlike the quilt
    host reference path)."""
    n = plan.n
    target = min(int(target), n * n)
    if target <= 0 or plan.B == 0:
        return np.zeros((0, 2), dtype=np.int64)
    seen = np.empty((0,), dtype=np.int64)
    for _ in range(max_rounds):
        need = target - seen.size
        if need <= 0:
            break
        ask = dedup.bucket_size(
            int(need * oversample * plan.bd_cost) + 16
        )
        ask = min(ask, kpgm.DEVICE_MAX_CANDIDATES)
        key, sub = jax.random.split(key)
        sn, dn = _propose_host(sub, plan, ask)
        ok = (sn >= 0) & (dn >= 0)
        flat = sn[ok] * n + dn[ok]
        _, first_idx = np.unique(flat, return_index=True)
        in_order = flat[np.sort(first_idx)]
        fresh = in_order[~np.isin(in_order, seen, assume_unique=True)]
        seen = np.concatenate([seen, fresh])
    seen = seen[:target]
    return np.stack([seen // n, seen % n], axis=1)


def _host_balldrop_topup(
    key: jax.Array,
    plan: quilt.QuiltPlan,
    targets: np.ndarray,
    counts: np.ndarray,
    seen_pairs: List[np.ndarray],
    tail: List[Tuple[int, np.ndarray]],
    max_rounds: int,
    oversample: float,
) -> np.ndarray:
    """Finish a collision shortfall the device rounds left behind: shared
    proposal batches, host arrival-order dedup against the node pairs taken
    on device, (sample_id, (E, 2)) pieces appended to ``tail``."""
    n = plan.n
    for _ in range(max_rounds):
        needs = targets - counts
        if needs.max(initial=0) <= 0:
            break
        asks, batch = dedup.plan_asks(needs, oversample * plan.bd_cost)
        key, sub = jax.random.split(key)
        sn, dn = _propose_host(sub, plan, batch)
        DISPATCH_COUNTERS["host_topup_rounds"] += 1
        ok = (sn >= 0) & (dn >= 0)
        flat_all = np.where(ok, sn * n + dn, -1)
        off = 0
        for g, ask in enumerate(np.asarray(asks)):
            if ask == 0:
                continue
            chunk = flat_all[off : off + int(ask)]
            off += int(ask)
            chunk = chunk[chunk >= 0]
            _, first_idx = np.unique(chunk, return_index=True)
            in_order = chunk[np.sort(first_idx)]
            fresh = in_order[~np.isin(in_order, seen_pairs[g])]
            fresh = fresh[: int(needs[g])]
            if fresh.size == 0:
                continue
            seen_pairs[g] = np.concatenate([seen_pairs[g], fresh])
            counts[g] += fresh.size
            tail.append(
                (g, np.stack([fresh // n, fresh % n], axis=1))
            )
    return counts


def balldrop_run(
    key: jax.Array,
    plan: quilt.QuiltPlan,
    *,
    num_samples: int = 1,
    targets: Optional[np.ndarray] = None,
    max_rounds: int = 8,
    oversample: float = 1.05,
    use_kernel: Optional[bool] = None,
    mesh=None,
    exact_cells: Optional[bool] = None,
) -> quilt.QuiltRun:
    """Execute the ball-dropping engine for a prebuilt QuiltPlan.

    The ``backend="balldrop"`` arm of :func:`repro.core.quilt.quilt_run`:
    same signature contract, but ``targets`` is per SAMPLE (one node-pair
    stream each) instead of per block pair, defaulting to independent
    N(bd_mean, bd_std) draws.  Raises :class:`ValueError` when the plan was
    built past the ``kron.MOMENT_CAP`` gate (no ball-dropping moments), and
    :class:`quilt.DeviceBatchUnavailable` for fused batches over the device
    candidate budget.

    ``exact_cells`` behaves as on :func:`quilt.quilt_run`: defaulting to on
    when no explicit ``targets`` is given, one plan-constant round of
    ``quilt._exact_budget(p_max, mean_edges * B^2)`` proposals per sample
    with per-node-pair acceptance thinning makes edge inclusion exactly
    Bernoulli(Q_ij) — no drawn target, no top-up, zero warm recompiles.
    Ineligible runs (explicit targets, budget past the device cap) take
    the legacy drawn-target rounds and bump
    ``DISPATCH_COUNTERS["exact_fallbacks"]``.
    """
    S = int(num_samples)
    n = plan.n
    if plan.bd_cost is None:
        raise ValueError(
            "backend='balldrop' needs the plan's ball-dropping moments; "
            f"this plan was built without them (2^d > {kron.MOMENT_CAP}"
            " configurations, or an empty partition)"
        )
    targets_given = targets is not None

    if use_kernel is None:
        use_kernel = not ops.INTERPRET
    # rank lookup: dense inverse (one gather) when it exists, else the
    # by-config short-circuit (O(2^d + n) memory)
    lookup = quilt.device_lookup(plan)

    exact = (not targets_given) if exact_cells is None else bool(exact_cells)
    exact = exact and not targets_given and plan.B > 0 and S > 0
    budget = None
    if exact:
        # each proposal hits a GIVEN node pair with pi = p_xy / (S B^2):
        # the descent picks the config cell, the two uniform ranks pick the
        # pair's occurrence ranks
        budget = quilt._exact_budget(
            plan.p_max, plan.mean_edges * float(plan.B) ** 2
        )
        if budget is None or S * budget > kpgm.DEVICE_MAX_CANDIDATES:
            quilt.fallback(
                DISPATCH_COUNTERS,
                "exact_fallbacks",
                f"exact-cell ball-dropping round over the device budget ({S}"
                f" samples x {budget} proposals > DEVICE_MAX_CANDIDATES="
                f"{kpgm.DEVICE_MAX_CANDIDATES}, or no finite budget): "
                "taking the drawn-target rounds instead",
            )
            exact = False
            budget = None

    key, sub = jax.random.split(key)
    if exact:
        targets = np.full(S, budget, dtype=np.int64)
    elif targets is None:
        draws = (
            transfer.to_host(jax.random.normal(sub, (S,))) * plan.bd_std
            + plan.bd_mean
        )
        targets = np.clip(np.round(draws), 0, n * n).astype(np.int64)
    else:
        targets = np.clip(
            np.asarray(targets, dtype=np.int64).reshape(S), 0, n * n
        )
    total = int(targets.sum())

    from repro.dist import sharding as _dist_sharding

    layout = _dist_sharding.graph_layout(mesh, S)
    axes, s_pad = layout.axes, layout.padded
    if not axes:
        mesh = None
    ask0 = (
        budget if exact
        else dedup.uniform_ask(targets, oversample * plan.bd_cost)
    )
    # layout-invariant device decision, like quilt_run's (S, not s_pad)
    use_device = lookup is not None and (
        exact
        or (S * ask0 <= kpgm.DEVICE_MAX_CANDIDATES and quilt.slots_fit(ask0))
    )
    if not use_device:
        if S > 1:
            raise quilt.DeviceBatchUnavailable(
                "fused balldrop sample_batch over the device budget "
                f"(candidates={S * ask0})"
            )
        quilt.fallback(
            DISPATCH_COUNTERS,
            "host_fallbacks",
            f"ball dropping on the host: {S * ask0} candidates is over "
            f"DEVICE_MAX_CANDIDATES={kpgm.DEVICE_MAX_CANDIDATES} or the "
            "counter-PRNG slot limit, or the plan has no device lookup",
        )
        edges = _balldrop_sample_host(
            key,
            plan,
            target=int(targets[0]),
            max_rounds=max_rounds,
            oversample=oversample,
        )
        st = quilt.QuiltStats(
            B=plan.B,
            num_kpgm_draws=0,
            kpgm_edges_total=int(edges.shape[0]),
            kept_edges=int(edges.shape[0]),
            heavy_groups=0,
            light_nodes=plan.n,
            bprime=None,
        )
        return quilt.QuiltRun(
            plan, 1, targets, np.zeros(S, np.int64), (), 0, (), edges, st,
            sampler="balldrop",
        )

    tail: List[Tuple[int, np.ndarray]] = []
    counts = np.zeros(S, dtype=np.int64)
    shortfall = targets.copy()
    outs = None
    key, rkey = jax.random.split(key)
    a_tot = 0
    nb = _node_bits(n)

    if total > 0:
        gids_j, tpad_j = quilt._pad_inputs(S, s_pad, targets)
        tables = lookup
        rounds: Tuple[int, ...] = ()
        for r in range(1 if exact else max_rounds):
            chaos.maybe_fail("quilt.round")
            ask = (
                budget if exact
                else dedup.uniform_ask(shortfall, oversample * plan.bd_cost)
            )
            if ask == 0:
                break
            if rounds and (
                S * (sum(rounds) + ask) > kpgm.DEVICE_MAX_CANDIDATES
                or not quilt.slots_fit(sum(rounds) + ask)
            ):
                # cumulative stream would outgrow the device budget: let
                # the host top-up finish the residual (layout-invariant,
                # like quilt_run's guard)
                break
            rounds = rounds + (ask,)
            with tracing.span(
                "quilt.round", round=r, ask=ask, slots=S * sum(rounds)
            ):
                while True:
                    try:
                        chaos.maybe_fail("quilt.dispatch")
                        fn = _compiled_bd_round(
                            mesh, axes, rounds, plan.B, nb, use_kernel,
                            len(tables), exact,
                        )
                        outs = dedup.call_x64(
                            fn, rkey, gids_j, tpad_j, plan.cum, plan.thetas,
                            tables,
                        )
                        break
                    except chaos.DeviceLoss as exc:
                        # same degrade-and-rerun recovery as quilt_run: the
                        # per-sample streams are layout-invariant too
                        mesh, axes, s_pad = quilt._degrade_layout(
                            mesh, exc, S, DISPATCH_COUNTERS
                        )
                        gids_j, tpad_j = quilt._pad_inputs(S, s_pad, targets)
            DISPATCH_COUNTERS[
                "device_rounds" if r == 0 else "device_topup_rounds"
            ] += 1
            with tracing.span("quilt.round_wait", round=r):
                outs[3].block_until_ready()
            counts = transfer.to_host(outs[3]).astype(np.int64)[:S]
            shortfall = np.zeros_like(targets) if exact else targets - counts
            if shortfall.max(initial=0) <= 0:
                break
        a_tot = sum(rounds)
        transfer.ENGINE_COUNTERS["candidate_slots"] += S * a_tot

    parts = ()
    fetched = False
    if outs is not None:
        snode, dnode, take, _ = outs
        # the dedup's valid mask already excludes lookup misses, so taken
        # rows are accepted balls: keep == take (and counts == keep sums)
        with tracing.span("quilt.mask"):
            keep = transfer.to_host(take)
        parts = (quilt.RoundBuffers(0, snode, dnode, keep),)
        if shortfall.max(initial=0) > 0:
            quilt.fallback(
                DISPATCH_COUNTERS,
                "degraded_fallbacks",
                f"device rounds exhausted (max_rounds={max_rounds}, "
                f"{a_tot} slots/sample) with {int(shortfall.sum())} edges "
                "still short: finishing the residual with the host "
                "ball-dropping loop (raise max_rounds or oversample to "
                "stay device-resident)",
            )
            flat_taken = (
                transfer.to_host(snode)[keep].astype(np.int64) * n
                + transfer.to_host(dnode)[keep].astype(np.int64)
            )
            fetched = True
            full_counts = transfer.to_host(outs[3], moves=False).astype(
                np.int64
            )
            seen_pairs = list(
                np.split(flat_taken, np.cumsum(full_counts)[:-1])
            )[:S]
            counts = _host_balldrop_topup(
                key, plan, targets, counts, seen_pairs, tail,
                max_rounds, oversample,
            )

    if exact:
        targets = counts.copy()
    return quilt.QuiltRun(
        plan, S, targets, counts, parts, a_tot, tuple(tail),
        None, None, sampler="balldrop", nodes_fetched=fetched,
    )
