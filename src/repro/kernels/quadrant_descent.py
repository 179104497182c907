"""Pallas TPU kernel for Algorithm 1's quadrant descent (KPGM edge sampling).

Each candidate edge descends d levels of the Kronecker hierarchy; at level k
it picks quadrant (a, b) in {0,1}^2 with probability theta^(k)_{ab}.  The
batched formulation (DESIGN.md section 3.1) turns the whole batch into one
dense tensor program:

    u     : (N, d)  uniforms
    cum   : (d, 4)  per-level cumulative quadrant probabilities
    quad  : (N, d)  = sum_{t<3} [u >= cum[:, t]]       (VPU compares)
    src   : (N,)    = sum_k (quad >> 1)_k * 2^(d-1-k)  (bit contraction)
    dst   : (N,)    = sum_k (quad &  1)_k * 2^(d-1-k)

The kernel tiles the edge axis: each grid step loads a (TILE, d) block of
uniforms into VMEM plus the (d, 4) table, and writes (TILE, 1) int32 id
blocks.  Arithmetic intensity is ~O(d) flops / 4d bytes per edge — the kernel
is HBM-bandwidth-bound, which is why the fused formulation (no intermediate
quad / bit-plane tensors round-tripping to HBM) matters.

The engines' kernel is :func:`descent_prng`: it needs no uniforms operand,
generating its variates in-kernel from a counter-based hash of
``(round_key, graph, slot, channel)`` (`counter_hash`), so the only HBM
traffic is the int32 config ids it writes.  Its layout is lane-dense: a
grid step owns ``rows x 128`` consecutive slots of one graph, the graph id
and seed are scalar-prefetched, and the (d, 4) table sits in SMEM.  The
hash is plain uint32 arithmetic, so the same kernel body lowers in CPU
interpret mode AND on TPU, and the jnp twins (``core/quilt.py`` /
``core/balldrop.py`` with ``use_kernel=False``) reproduce it bit-for-bit.
Given the plan's dense config -> node inverse with rows of at most
``KERNEL_LOOKUP_MAX_ROW`` entries, the kernel also looks the configs up:
each grid step holds its graph's source and target block rows in VMEM and
gathers from them along lanes (``_row_lookup``).  Other rounds (larger
rows, the by-config tables, ball dropping's per-candidate blocks) gather
through the plan's tables in XLA.  A TPU-native variant using
``pltpu.prng_seed`` / ``pltpu.prng_random_bits`` sits behind the
``tpu_native`` flag (no CPU
lowering exists for those primitives; see docs/API.md for the flag +
counter-derivation contract).  ``tests/test_chip_compile.py`` compiles the
kernels for a v5e.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Edge-axis tile: multiple of 8 (f32 sublane) and large enough to amortise
# grid overhead; (512, d<=31) uniforms = <64KB, comfortably VMEM-resident.
TILE = 512


def _kernel(u_ref, cum_ref, src_ref, dst_ref, *, d: int):
    u = u_ref[...]  # (TILE, d) f32
    cum = cum_ref[...]  # (d, 4) f32
    # quadrant index per (edge, level): number of cum thresholds below u.
    quad = (
        (u >= cum[None, :, 0]).astype(jnp.int32)
        + (u >= cum[None, :, 1]).astype(jnp.int32)
        + (u >= cum[None, :, 2]).astype(jnp.int32)
    )
    a = quad >> 1
    b = quad & 1
    # powers of two via in-kernel iota (a jnp.arange would be a captured
    # constant, which pallas_call forbids)
    k = jax.lax.broadcasted_iota(jnp.int32, (1, d), 1)
    pows = jnp.int32(1) << (jnp.int32(d - 1) - k)
    src_ref[...] = jnp.sum(a * pows, axis=1, keepdims=True, dtype=jnp.int32)
    dst_ref[...] = jnp.sum(b * pows, axis=1, keepdims=True, dtype=jnp.int32)


def _quilt_kernel(
    u_ref,
    cum_ref,
    kb_ref,
    lb_ref,
    tcfg_ref,
    tnode_ref,
    scfg_ref,
    dcfg_ref,
    snode_ref,
    dnode_ref,
    *,
    d: int,
    table_width: int,
    steps: int,
):
    """Fused quadrant descent + per-block sorted-config lookup.

    One grid step descends a (TILE, d) block of uniforms AND binary-searches
    the resulting config ids in the (B, L) sorted lookup tables of their
    assigned source/target blocks, emitting node ids (-1 on membership miss).
    Membership filtering therefore never leaves the device: the quilting loop
    consumes (src_node, dst_node, valid) directly instead of round-tripping
    B^2 config arrays through the host `searchsorted` path.
    """
    u = u_ref[...]  # (TILE, d) f32
    cum = cum_ref[...]  # (d, 4) f32
    quad = (
        (u >= cum[None, :, 0]).astype(jnp.int32)
        + (u >= cum[None, :, 1]).astype(jnp.int32)
        + (u >= cum[None, :, 2]).astype(jnp.int32)
    )
    a = quad >> 1
    b = quad & 1
    k = jax.lax.broadcasted_iota(jnp.int32, (1, d), 1)
    pows = jnp.int32(1) << (jnp.int32(d - 1) - k)
    # pin the accumulator: under the x64 context jnp.sum would widen to int64
    scfg = jnp.sum(a * pows, axis=1, keepdims=True, dtype=jnp.int32)
    dcfg = jnp.sum(b * pows, axis=1, keepdims=True, dtype=jnp.int32)

    flat_cfg = tcfg_ref[...].reshape(-1)  # (B * L,)
    flat_node = tnode_ref[...].reshape(-1)
    length = jnp.int32(table_width)

    def lower_bound(row, target):
        """Vectorised per-candidate binary search in each candidate's block
        row; `steps` iterations bound any window of width <= table_width."""
        lo = jnp.zeros_like(target)
        hi = jnp.full_like(target, length)
        for _ in range(steps):
            mid = (lo + hi) >> 1
            probe = flat_cfg[row * length + jnp.minimum(mid, length - 1)]
            active = lo < hi
            go_right = active & (probe < target)
            lo = jnp.where(go_right, mid + 1, lo)
            hi = jnp.where(active & ~go_right, mid, hi)
        pos = jnp.minimum(lo, length - 1)
        hit = flat_cfg[row * length + pos] == target
        return jnp.where(hit, flat_node[row * length + pos], -1)

    snode_ref[...] = lower_bound(kb_ref[...], scfg)
    dnode_ref[...] = lower_bound(lb_ref[...], dcfg)
    scfg_ref[...] = scfg
    dcfg_ref[...] = dcfg


@functools.partial(jax.jit, static_argnames=("interpret",))
def quilt_descent_lookup(
    uniforms: jax.Array,
    cumprobs: jax.Array,
    kb: jax.Array,
    lb: jax.Array,
    table_cfg: jax.Array,
    table_node: jax.Array,
    *,
    interpret: bool = True,
):
    """Fused Algorithm-1 descent + block-membership lookup.

    Args:
      uniforms:   (N, d) f32, N a multiple of TILE (ops.py pads).
      cumprobs:   (d, 4) cumulative quadrant probabilities.
      kb, lb:     (N, 1) int32 source/target block ids per candidate.
      table_cfg:  (B, L) int32 per-block configs, each row ascending, padded
                  with INT32_MAX sentinels (partition.CFG_SENTINEL).
      table_node: (B, L) int32 node ids aligned with table_cfg, padding -1.

    Returns (src_cfg, dst_cfg, src_node, dst_node), each (N,) int32 with
    node = -1 when the config is not a member of the block.  Like the other
    kernels this validates on CPU with interpret=True; on TPU the (B, L)
    tables stay VMEM-resident across the whole edge-axis grid.
    """
    n, d = uniforms.shape
    if n % TILE:
        raise ValueError(f"N={n} must be a multiple of TILE={TILE}")
    bsz, width = table_cfg.shape
    steps = max(width - 1, 1).bit_length() + 1
    grid = (n // TILE,)
    out = pl.pallas_call(
        functools.partial(
            _quilt_kernel, d=d, table_width=width, steps=steps
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((TILE, d), lambda i: (i, 0)),
            pl.BlockSpec((d, 4), lambda i: (0, 0)),
            pl.BlockSpec((TILE, 1), lambda i: (i, 0)),
            pl.BlockSpec((TILE, 1), lambda i: (i, 0)),
            pl.BlockSpec((bsz, width), lambda i: (0, 0)),
            pl.BlockSpec((bsz, width), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((TILE, 1), lambda i: (i, 0)) for _ in range(4)
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.int32) for _ in range(4)
        ],
        interpret=interpret,
    )(uniforms, cumprobs, kb, lb, table_cfg, table_node)
    scfg, dcfg, snode, dnode = out
    return scfg[:, 0], dcfg[:, 0], snode[:, 0], dnode[:, 0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def quadrant_descent(
    uniforms: jax.Array, cumprobs: jax.Array, *, interpret: bool = True
):
    """(N, d) uniforms + (d, 4) cumulative probs -> (src, dst) int32 ids.

    N must be a multiple of TILE (ops.py pads).  ``interpret=True`` runs the
    kernel body on CPU for validation; on TPU pass interpret=False.
    """
    n, d = uniforms.shape
    if n % TILE:
        raise ValueError(f"N={n} must be a multiple of TILE={TILE}")
    grid = (n // TILE,)
    src, dst = pl.pallas_call(
        functools.partial(_kernel, d=d),
        grid=grid,
        in_specs=[
            pl.BlockSpec((TILE, d), lambda i: (i, 0)),
            pl.BlockSpec((d, 4), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((TILE, 1), lambda i: (i, 0)),
            pl.BlockSpec((TILE, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.int32),
            jax.ShapeDtypeStruct((n, 1), jnp.int32),
        ],
        interpret=interpret,
    )(uniforms, cumprobs)
    return src[:, 0], dst[:, 0]


# ---------------------------------------------------------------------------
# counter-based in-kernel PRNG
# ---------------------------------------------------------------------------

# Channel slots reserved per candidate: channels 0..d-1 carry the descent
# uniforms (d <= 31 everywhere: int32 config ids), the LAST TWO channels
# carry the ball-dropping block ranks.  The packed word
# ``slot * 64 + channel`` must fit in uint32, so a graph may hold at most
# PRNG_SLOT_LIMIT = 2^26 slots; the engines check it where they size a round
# (quilt.check_slots) and :func:`descent_prng` re-checks its padded layout.
PRNG_CHANNELS = 64
PRNG_SLOT_LIMIT = (1 << 32) // PRNG_CHANNELS
_RANK0 = PRNG_CHANNELS - 2

# lowbias32-style avalanche multipliers (hash-prospector family) plus the
# word/graph stream-separation multipliers (golden-ratio, murmur3 c2)
_MIX_A = 0x7FEB352D
_MIX_B = 0x846CA68B
_WORD_C = 0x9E3779B9
_GID_C = 0x85EBCA6B


def _mix32(x: jax.Array) -> jax.Array:
    """lowbias32 finalizer: an invertible uint32 avalanche round.

    Pure uint32 jnp arithmetic (multiply wraps mod 2^32, ``>>`` on an
    unsigned dtype is a logical shift), so the SAME expression runs inside
    a Pallas kernel body, in interpret mode, and on the jnp fallback paths
    — bit-identical everywhere, no x64 requirement.
    """
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(_MIX_A)
    x = x ^ (x >> jnp.uint32(15))
    x = x * jnp.uint32(_MIX_B)
    x = x ^ (x >> jnp.uint32(16))
    return x


def counter_hash(
    s0: jax.Array, s1: jax.Array, gid: jax.Array, word: jax.Array
) -> jax.Array:
    """uint32 hash of the counter ``(seed words, graph id, word)``.

    The counter-derivation contract (docs/API.md): ``word`` packs the
    intra-graph position as ``slot * PRNG_CHANNELS + channel`` where
    ``slot`` is the candidate's absolute index in the graph's concatenated
    candidate stream — NOT its index within the current round — so a
    top-up round re-deriving slots ``[0, a_tot)`` reproduces the earlier
    rounds' variates as an exact prefix, and any sharding of the graph
    axis sees identical per-graph streams (mesh-layout invariance by
    construction: the seed is replicated, ``gid`` is the GLOBAL graph id).
    Two avalanche rounds with the seed/graph words injected between them
    decorrelate neighbouring counters to chi-square-clean uniformity
    (tests/test_counter_prng.py).
    """
    x = word.astype(jnp.uint32) * jnp.uint32(_WORD_C) + s0.astype(jnp.uint32)
    x = _mix32(x)
    x = x ^ (gid.astype(jnp.uint32) * jnp.uint32(_GID_C) + s1.astype(jnp.uint32))
    return _mix32(x)


def _u01(bits: jax.Array) -> jax.Array:
    """f32 uniform in [0, 1) from the top 24 bits of a uint32 word.

    The 24-bit value goes through int32 on its way to f32: Mosaic has no
    uint32 -> float32 cast, and below 2^24 both conversions are exact, so
    the kernels and the jnp twin agree bit for bit."""
    return (bits >> jnp.uint32(8)).astype(jnp.int32).astype(
        jnp.float32
    ) * jnp.float32(2.0**-24)


def counter_u01(
    s0: jax.Array, s1: jax.Array, gid: jax.Array, word: jax.Array
) -> jax.Array:
    """f32 uniform in [0, 1) from the top 24 bits of :func:`counter_hash`
    (24 bits = full f32 mantissa precision, exact float conversion)."""
    return _u01(counter_hash(s0, s1, gid, word))


def counter_rank(
    s0: jax.Array,
    s1: jax.Array,
    gid: jax.Array,
    word: jax.Array,
    num_blocks: int,
) -> jax.Array:
    """int32 rank in [0, num_blocks) from 31 hash bits (modulo bias is
    <= num_blocks * 2^-31 per bucket — B never exceeds n <= 2^25).  The
    31-bit value is non-negative as int32, so the remainder is taken in
    int32, which both Mosaic and XLA lower."""
    bits = (counter_hash(s0, s1, gid, word) >> jnp.uint32(1)).astype(jnp.int32)
    return jax.lax.rem(bits, jnp.int32(num_blocks))


def counter_seed(key: jax.Array) -> jax.Array:
    """(1, 2) int32 seed words for the counter hash from a JAX PRNG key
    (typed or raw uint32).  Traceable — derived in-jit, so warm calls ship
    no host scalars (transfer-guard clean)."""
    arr = jnp.asarray(key)
    if jnp.issubdtype(arr.dtype, jax.dtypes.prng_key):
        arr = jax.random.key_data(arr)
    words = arr.astype(jnp.uint32).reshape(-1)[-2:]
    return words.astype(jnp.int32).reshape(1, 2)


def descent_uniforms(
    s0: jax.Array, s1: jax.Array, gid: jax.Array, slot: jax.Array, d: int
) -> jax.Array:
    """(N, d) f32 descent uniforms for channels 0..d-1 of each slot — the
    jnp twin of the in-kernel derivation (bit-identical by shared math)."""
    word = slot.astype(jnp.uint32).reshape(-1, 1) * jnp.uint32(
        PRNG_CHANNELS
    ) + jnp.arange(d, dtype=jnp.uint32)[None, :]
    return counter_u01(s0, s1, gid.reshape(-1, 1), word)


def rank_pair(
    s0: jax.Array,
    s1: jax.Array,
    gid: jax.Array,
    slot: jax.Array,
    num_blocks: int,
):
    """(kb, lb) block ranks from the two reserved rank channels — the jnp
    twin of the in-kernel ``ranks=True`` derivation."""
    base = slot.astype(jnp.uint32) * jnp.uint32(PRNG_CHANNELS)
    kb = counter_rank(s0, s1, gid, base + jnp.uint32(_RANK0), num_blocks)
    lb = counter_rank(s0, s1, gid, base + jnp.uint32(_RANK0 + 1), num_blocks)
    return kb, lb


# Lane-dense layout of the counter-PRNG descent kernel: a grid step owns
# ``rows x 128`` consecutive slots of ONE graph, so every live value is a
# full (8k, 128) int32 tile and the graph id is a scalar read.
LANES = 128
MAX_ROWS = 32


def _rows_for(a_tot: int) -> int:
    """Sublane rows per grid step: up to MAX_ROWS, rounded to 8."""
    need = -(-max(int(a_tot), 1) // LANES)
    return min(MAX_ROWS, need + (-need) % 8)


def _row_lookup(tab_ref, cfg: jax.Array) -> jax.Array:
    """Node id of each config of the ``cfg`` tile in the (1, R, 128) table
    row ``tab_ref`` (entry ``x`` at row ``x >> 7``, lane ``x & 127``).

    Mosaic gathers only within a tile of the same shape, along lanes, so
    every 128-entry row slice is broadcast to the tile's shape, gathered at
    each config's lane, and kept where the config's row is that slice's.
    The loop walks the table a whole (8, 128) tile at a time, so every
    load is aligned."""
    hi = cfg >> 7
    lane = cfg & (LANES - 1)

    def tile(t, out):
        block = tab_ref[0, pl.ds(pl.multiple_of(t * 8, 8), 8), :]
        for s in range(8):
            row = jnp.broadcast_to(block[s : s + 1, :], cfg.shape)
            hit = jnp.take_along_axis(row, lane, axis=1)
            out = jnp.where(hi == t * 8 + s, hit, out)
        return out

    return jax.lax.fori_loop(
        0, tab_ref.shape[1] // 8, tile, jnp.full(cfg.shape, -1, jnp.int32)
    )


def _descent_kernel(
    gids_ref, seed_ref, cum_ref, *refs, d: int, rows: int,
    num_blocks: int, ranks: bool, native: bool, lookup: bool,
):
    """Counter-PRNG quadrant descent for one (graph, slot-tile) grid step.

    ``gids_ref`` and ``seed_ref`` are scalar-prefetched (SMEM), ``cum_ref``
    is the (d, 4) cumulative quadrant table in SMEM.  Level k's uniform is
    channel k of the slot's counter word, and the config ids accumulate a
    bit per level — the same integers the jnp twin gets from
    :func:`descent_uniforms` + ``kpgm._descend``.  With ``ranks=True`` the
    two reserved rank channels are emitted too (ball dropping).  With
    ``lookup=True`` the first two of ``refs`` are the inverse's rows of the
    graph's source and target blocks in VMEM, and the node ids of both
    configs are emitted too (:func:`_row_lookup`).

    ``native=True`` draws every channel from the TPU's hardware PRNG
    (``pltpu.prng_random_bits``, seeded per grid step) instead of the
    counter hash: a deployment-speed option with the same law but NOT the
    same bits, and no interpret-mode lowering.
    """
    if lookup:
        stab_ref, dtab_ref, *out_refs = refs
    else:
        out_refs = refs
    g = pl.program_id(0)
    j = pl.program_id(1)
    gid = gids_ref[g]
    s0 = seed_ref[0]
    s1 = seed_ref[1]
    shape = (rows, LANES)
    slot = (
        j * (rows * LANES)
        + jax.lax.broadcasted_iota(jnp.int32, shape, 0) * LANES
        + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    )
    base = slot.astype(jnp.uint32) * jnp.uint32(PRNG_CHANNELS)
    if native:
        from jax.experimental.pallas import tpu as pltpu  # TPU-only

        pltpu.prng_seed(s0 + g * pl.num_programs(1) + j, s1 ^ gid)

    def channel_bits(channel: int) -> jax.Array:
        if native:
            return jax.lax.bitcast_convert_type(
                pltpu.prng_random_bits(shape), jnp.uint32
            )
        return counter_hash(s0, s1, gid, base + jnp.uint32(channel))

    def rank(channel: int) -> jax.Array:
        bits = (channel_bits(channel) >> jnp.uint32(1)).astype(jnp.int32)
        return jax.lax.rem(bits, jnp.int32(num_blocks))

    scfg = jnp.zeros(shape, jnp.int32)
    dcfg = jnp.zeros(shape, jnp.int32)
    for k in range(d):
        u = _u01(channel_bits(k))
        quad = (
            (u >= cum_ref[k, 0]).astype(jnp.int32)
            + (u >= cum_ref[k, 1]).astype(jnp.int32)
            + (u >= cum_ref[k, 2]).astype(jnp.int32)
        )
        scfg = (scfg << 1) | (quad >> 1)
        dcfg = (dcfg << 1) | (quad & 1)
    out_refs[0][...] = scfg
    out_refs[1][...] = dcfg
    if ranks:
        out_refs[2][...] = rank(_RANK0)
        out_refs[3][...] = rank(_RANK0 + 1)
    if lookup:
        out_refs[2][...] = _row_lookup(stab_ref, scfg)
        out_refs[3][...] = _row_lookup(dtab_ref, dcfg)


# Longest inverse row the descent kernel looks configs up in: the lookup
# costs a few vector ops per 128-entry row slice per tile, so its time grows
# with the row, while an XLA gather costs the same per element whatever
# the table (PERF.md, section 6, gives the crossover measured on a v5e).
KERNEL_LOOKUP_MAX_ROW = 1 << 16


def _table_rows(inv: jax.Array) -> jax.Array:
    """The (B, W) inverse as (B, R, 128) rows of whole (8, 128) tiles,
    padded with -1 (configs never reach the padding)."""
    bsz, width = inv.shape
    pad = -width % (8 * LANES)
    inv = jnp.pad(inv.astype(jnp.int32), ((0, 0), (0, pad)),
                  constant_values=-1)
    return inv.reshape(bsz, -1, LANES)


@functools.partial(
    jax.jit,
    static_argnames=("a_tot", "num_blocks", "ranks", "interpret", "native"),
)
def descent_prng(
    seed: jax.Array,
    gids: jax.Array,
    cumprobs: jax.Array,
    *,
    a_tot: int,
    num_blocks: int = 1,
    ranks: bool = False,
    interpret: bool = True,
    native: bool = False,
    inv: Optional[jax.Array] = None,
):
    """Counter-PRNG quadrant descent over ``gids.size * a_tot`` candidates.

    Args:
      seed:       (1, 2) int32 counter seed words (:func:`counter_seed`).
      gids:       (gc,) or (gc, 1) int32 GLOBAL graph ids of this shard.
      cumprobs:   (d, 4) cumulative quadrant probabilities.
      a_tot:      static slots per graph (cumulative over top-up rounds).
      num_blocks: B — rank range of the ``ranks=True`` channels, and the
                  block count of ``inv``.
      inv:        optional (B, W) int32 dense config -> node inverse, -1
                  where a block lacks the config, W at most
                  :data:`KERNEL_LOOKUP_MAX_ROW`.

    Returns ``(src_cfg, dst_cfg)`` — plus ``(kb, lb)`` when ``ranks``, or
    ``(src_node, dst_node)`` when ``inv`` is given — each (gc * a_tot,)
    int32 in graph-major order, bit-identical to the jnp twin built from
    :func:`descent_uniforms` / :func:`rank_pair` and the quilt's gather.
    With ``inv``, graph g's block pair is ``(block // B, block % B)`` for
    ``block = gid % B^2``, and the kernel looks the configs up in those
    two rows of ``inv``, held in VMEM for all of the graph's grid steps.
    Each graph's slots are padded to whole ``rows x 128`` tiles inside the
    kernel and the padding is sliced off here.
    """
    gc = int(gids.shape[0])
    d = int(cumprobs.shape[0])
    rows = _rows_for(a_tot)
    tile = rows * LANES
    bpg = -(-a_tot // tile)
    a_pad = bpg * tile
    if a_pad > PRNG_SLOT_LIMIT:
        raise ValueError(
            f"{a_pad} slots per graph overflow the uint32 counter word "
            f"(limit {PRNG_SLOT_LIMIT})"
        )
    if inv is not None and (ranks or inv.shape[1] > KERNEL_LOOKUP_MAX_ROW):
        raise ValueError(
            "the in-kernel lookup takes no rank channels and rows of at most "
            f"KERNEL_LOOKUP_MAX_ROW={KERNEL_LOOKUP_MAX_ROW} entries "
            f"(got {inv.shape[1]})"
        )
    nout = 4 if ranks or inv is not None else 2
    # traced with x64 off: the engines call this under dedup.call_x64, and
    # Mosaic refuses the 64-bit block indices x64 would give the index maps
    with jax.enable_x64(False):
        out = _descent_call(
            gids.reshape(gc).astype(jnp.int32),
            seed.reshape(2).astype(jnp.int32),
            cumprobs.astype(jnp.float32),
            None if inv is None else _table_rows(inv),
            gc=gc, d=d, rows=rows, bpg=bpg, nout=nout,
            num_blocks=num_blocks, ranks=ranks, interpret=interpret,
            native=native,
        )
    return tuple(o.reshape(gc, a_pad)[:, :a_tot].reshape(-1) for o in out)


def _descent_call(
    gids, seed, cumprobs, table, *, gc, d, rows, bpg, nout, num_blocks,
    ranks, interpret, native,
):
    from jax.experimental.pallas import tpu as pltpu

    a_pad = bpg * rows * LANES
    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)]
    operands = [cumprobs]
    if table is not None:
        # one block row per side, chosen by the graph id: consecutive grid
        # steps of a graph keep the block index, so each row is fetched
        # once per graph
        pairs = num_blocks * num_blocks
        row = (1, table.shape[1], LANES)
        in_specs += [
            pl.BlockSpec(
                row, lambda g, j, gids, _: (gids[g] % pairs // num_blocks, 0, 0)
            ),
            pl.BlockSpec(
                row, lambda g, j, gids, _: (gids[g] % pairs % num_blocks, 0, 0)
            ),
        ]
        operands += [table, table]
    return pl.pallas_call(
        functools.partial(
            _descent_kernel, d=d, rows=rows, num_blocks=num_blocks,
            ranks=ranks, native=native, lookup=table is not None,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(gc, bpg),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec(
                    (rows, LANES), lambda g, j, *_: (g * bpg + j, 0)
                )
                for _ in range(nout)
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((gc * a_pad // LANES, LANES), jnp.int32)
            for _ in range(nout)
        ],
        interpret=interpret,
    )(gids, seed, *operands)


@functools.partial(
    jax.jit, static_argnames=("num_slots", "interpret", "tpu_native")
)
def quadrant_descent_prng(
    seed: jax.Array,
    cumprobs: jax.Array,
    *,
    num_slots: int,
    interpret: bool = True,
    tpu_native: bool = False,
):
    """Counter-PRNG quadrant descent: (1, 2) seed words + (d, 4) cumulative
    probs -> (src, dst) int32 ids for ``num_slots`` candidates (a multiple
    of TILE; ops.py pads).  Candidate ``s`` draws its level-``k`` uniform
    from ``counter_u01(seed, gid=0, s * PRNG_CHANNELS + k)`` — the single
    graph 0 of :func:`descent_prng`."""
    if num_slots % TILE:
        raise ValueError(f"N={num_slots} must be a multiple of TILE={TILE}")
    if tpu_native and interpret:
        raise ValueError(
            "tpu_native=True uses pltpu.prng_random_bits, which has no CPU "
            "interpret lowering — run on a real TPU backend or use the "
            "portable counter-hash kernel (tpu_native=False)"
        )
    return descent_prng(
        seed, jnp.zeros((1,), jnp.int32), cumprobs,
        a_tot=num_slots, interpret=interpret, native=tpu_native,
    )
