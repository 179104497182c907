"""Plain reference of the two sampling laws the benchmark's cells run.

Written from the documented contract (docs/API.md, "Counter PRNG") and the
paper's laws, in straightforward ``jax.numpy`` and NumPy.  It imports
nothing of the program and takes nothing the program made: it rebuilds the
Theorem-2 lookup from the attribute matrix, the quadrant table from the
initiator, and every random bit from the graph key.

- ``magm_exact``: the MAGM exact-cell law.  Block pair ``g = kb * B + lb``
  proposes ``G`` cells by quadrant descent on the counter stream; a cell
  is kept when both blocks hold a node with its configurations and its
  per-cell hash falls under ``alpha = p / (1 - (1 - p / S) ** G)``.  Edges
  come out block pair by block pair, each in first-proposal order.
- ``kpgm_first_distinct``: Algorithm 1 with a fixed edge count ``T``: the
  first ``T`` distinct cells of the counter stream of graph 0.

``ft`` is the float type of every floating-point step.  The configuration
states float32; the control computes the same law in bfloat16.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

CHANNELS = 64
MIX_A = 0x7FEB352D
MIX_B = 0x846CA68B
WORD_C = 0x9E3779B9
GID_C = 0x85EBCA6B
ACCEPT_SALT = 0x5EED
ACCEPT_C1 = 0x9E3779B97F4A7C15
ACCEPT_C2 = 0xC2B2AE3D27D4EB4F
# rows of one device block of the reference (bounds its memory)
BLOCK_ROWS = 1 << 24
# slots of graph 0's stream drawn per step of the KPGM reference
KPGM_ROWS = 1 << 21

def _mix32(x):
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(MIX_A)
    x = x ^ (x >> jnp.uint32(15))
    x = x * jnp.uint32(MIX_B)
    return x ^ (x >> jnp.uint32(16))


def counter_bits(s0, s1, gid, word):
    """uint32 word of the counter stream at (seed, graph, slot * 64 + channel)."""
    x = _mix32(word * jnp.uint32(WORD_C) + s0)
    return _mix32(x ^ (gid * jnp.uint32(GID_C) + s1))


def seed_words(rkey) -> np.ndarray:
    """The last two uint32 words of a raw PRNG key."""
    return np.asarray(jax.random.key_data(rkey) if jnp.issubdtype(
        jnp.asarray(rkey).dtype, jax.dtypes.prng_key) else rkey
    ).astype(np.uint32).reshape(-1)[-2:]


def round_key(key):
    """The key of the sampling round: the graph key split twice (the first
    split draws the edge-count target, the second the round)."""
    key, _ = jax.random.split(key)
    _, rkey = jax.random.split(key)
    return rkey


def quadrant_table(theta: np.ndarray, ft):
    """(d, 4) cumulative quadrant probabilities of each level, in ``ft``."""
    flat = jnp.asarray(theta, ft).reshape(-1, 4)
    flat = flat / jnp.sum(flat, axis=1, keepdims=True)
    return jnp.cumsum(flat, axis=1)


def _descend(s0, s1, gid, slot, cum, d: int, ft):
    """Config pair of each (graph, slot): level k takes channel k."""
    word0 = slot * jnp.uint32(CHANNELS)
    src = jnp.zeros(jnp.broadcast_shapes(gid.shape, slot.shape), jnp.int32)
    dst = jnp.zeros_like(src)
    for k in range(d):
        bits = counter_bits(s0, s1, gid, word0 + jnp.uint32(k))
        u = ((bits >> jnp.uint32(8)).astype(jnp.int32).astype(jnp.float32)
             * jnp.float32(2.0**-24)).astype(ft)
        quad = ((u >= cum[k, 0]).astype(jnp.int32)
                + (u >= cum[k, 1]).astype(jnp.int32)
                + (u >= cum[k, 2]).astype(jnp.int32))
        src = (src << 1) | (quad >> 1)
        dst = (dst << 1) | (quad & 1)
    return src, dst


# ---------------------------------------------------------------------------
# MAGM exact-cell law
# ---------------------------------------------------------------------------


def configurations(F: np.ndarray) -> np.ndarray:
    """Integer configuration of each node; attribute 0 is the top bit."""
    d = F.shape[1]
    return (F.astype(np.int64) << np.arange(d - 1, -1, -1)).sum(axis=1)


def block_lookup(F: np.ndarray):
    """Theorem-2 blocks as a by-configuration table: block b holds, for
    every configuration x, the b-th node (in index order) whose
    configuration is x.  Returns (offset, count, nodes, B)."""
    lam = configurations(F)
    count = np.bincount(lam, minlength=1 << F.shape[1])
    offset = np.concatenate([[0], np.cumsum(count)[:-1]])
    nodes = np.argsort(lam, kind="stable")
    return offset, count, nodes, int(count.max())


def proposal_budget(theta: np.ndarray, ft=jnp.float32) -> int:
    """G: the fewest proposals with ``1 - (1 - p/S)^G >= p`` for the most
    probable cell p, S the sum of all cell probabilities."""
    th = jnp.asarray(theta, ft)
    p_max = float(jnp.prod(jnp.max(th, axis=(1, 2))))
    s = float(jnp.prod(jnp.sum(th, axis=(1, 2))))
    p = min(p_max, 1.0 - 1e-6)
    s = max(s, p)
    if p <= 0.0 or p / s >= 1.0:
        return 1
    return max(int(math.ceil(math.log1p(-p) / math.log1p(-p / s))), 1)


def _lookup(offset, count, nodes, rank, cfg):
    """Node of block ``rank`` with configuration ``cfg``, or -1."""
    c = count[cfg]
    idx = offset[cfg] + jnp.minimum(rank, jnp.maximum(c - 1, 0))
    return jnp.where(rank < c, nodes[idx], -1)


@functools.partial(jax.jit, static_argnames=("d", "B", "G", "ft"))
def _magm_valid(seed, salt, gids, cum, theta, offset, count, nodes, *,
                d, B, G, ft):
    """Which proposals of the graphs ``gids`` (G slots each) are edges
    (both blocks hold the configurations, and the cell is accepted), as
    (graphs, G) uint8."""
    gid = gids.astype(jnp.uint32)[:, None]
    slot = jnp.arange(G, dtype=jnp.uint32)[None, :]
    scfg, dcfg = _descend(seed[0], seed[1], gid, slot, cum, d, ft)
    snode = _lookup(offset, count, nodes, (gids // B)[:, None], scfg)
    dnode = _lookup(offset, count, nodes, (gids % B)[:, None], dcfg)
    # acceptance: alpha = p / q with q = 1 - (1 - p/S)^G
    logt = jnp.log(jnp.clip(theta.astype(ft), 1e-30, 1.0))
    logp = jnp.zeros(scfg.shape, ft)
    for k in range(d):
        a = ((scfg >> (d - 1 - k)) & 1) == 1
        b = ((dcfg >> (d - 1 - k)) & 1) == 1
        lk = logt[k]
        logp = logp + jnp.where(
            a, jnp.where(b, lk[1, 1], lk[1, 0]), jnp.where(b, lk[0, 1], lk[0, 0])
        )
    log_s = jnp.sum(jnp.log(jnp.sum(theta.astype(ft), axis=(1, 2))))
    pi = jnp.exp(logp - log_s)
    q = -jnp.expm1(jnp.asarray(G, ft) * jnp.log1p(-pi))
    alpha = jnp.minimum(jnp.exp(logp - jnp.log(q)), 1.0)
    cell = (scfg.astype(jnp.uint64) << jnp.uint64(d)) | dcfg.astype(jnp.uint64)
    x = (salt ^ (gid.astype(jnp.uint64) * jnp.uint64(ACCEPT_C1))
         ^ (cell * jnp.uint64(ACCEPT_C2)))
    x = (x ^ (x >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
    x = x ^ (x >> jnp.uint64(31))
    u = ((x >> jnp.uint64(40)).astype(jnp.float32)
         * jnp.float32(2.0**-24)).astype(ft)
    valid = (snode >= 0) & (dnode >= 0) & (u < alpha)
    return valid.astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("d", "B", "ft"))
def _magm_rows(seed, gid, slot, cum, offset, count, nodes, *, d, B, ft):
    """(src cfg, dst cfg, src node, dst node) of the given proposals."""
    gid = gid.astype(jnp.uint32)
    scfg, dcfg = _descend(seed[0], seed[1], gid, slot.astype(jnp.uint32),
                          cum, d, ft)
    g = gid.astype(jnp.int32)
    return (scfg, dcfg, _lookup(offset, count, nodes, g // B, scfg),
            _lookup(offset, count, nodes, g % B, dcfg))


def _pow2_at_least(x: int) -> int:
    return 1 << max(int(x) - 1, 1).bit_length()


def magm_exact(F: np.ndarray, theta: np.ndarray, key, ft=jnp.float32):
    """Edges of one graph of the MAGM exact-cell law, in emission order.

    Returns ``(edges, slots)``: an (E, 2) int64 array and the number of
    proposals the law draws (B^2 * G)."""
    d = F.shape[1]
    offset, count, nodes, B = block_lookup(F)
    G = proposal_budget(theta, ft)
    rkey = round_key(key)
    seed = jnp.asarray(seed_words(rkey))
    cum = quadrant_table(theta, ft)
    tables = (jnp.asarray(offset, jnp.int32), jnp.asarray(count, jnp.int32),
              jnp.asarray(nodes, jnp.int32))
    per = max(BLOCK_ROWS // G, 1)
    pieces = []
    with jax.enable_x64(True):
        salt = jax.random.bits(
            jax.random.fold_in(rkey, ACCEPT_SALT), (), jnp.uint64
        )
        for g0 in range(0, B * B, per):
            gids = np.arange(g0, g0 + per, dtype=np.int32)
            gids = jnp.asarray(np.minimum(gids, B * B - 1))
            n_real = min(per, B * B - g0)
            valid = _magm_valid(
                seed, salt, gids, cum, jnp.asarray(theta), *tables,
                d=d, B=B, G=G, ft=ft,
            )
            rows = np.flatnonzero(np.asarray(valid)[:n_real])
            if rows.size == 0:
                continue
            cap = _pow2_at_least(rows.size)
            gid = np.zeros(cap, np.int32)
            slot = np.zeros(cap, np.int32)
            gid[: rows.size] = g0 + rows // G
            slot[: rows.size] = rows % G
            sc, dc, sn, dn = (
                np.asarray(a)[: rows.size]
                for a in _magm_rows(seed, jnp.asarray(gid), jnp.asarray(slot),
                                    cum, *tables, d=d, B=B, ft=ft)
            )
            graph = rows.astype(np.int64) // G
            cell = (graph << (2 * d)) | (sc.astype(np.int64) << d) | dc
            _, first = np.unique(cell, return_index=True)
            first.sort()
            pieces.append(np.stack([sn[first], dn[first]], axis=1))
    edges = (np.concatenate(pieces).astype(np.int64) if pieces
             else np.zeros((0, 2), np.int64))
    return edges, B * B * G


# ---------------------------------------------------------------------------
# KPGM: the first T distinct cells
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("d", "rows", "ft"))
def _kpgm_block(seed, start, cum, *, d, rows, ft):
    slot = start.astype(jnp.uint32) + jnp.arange(rows, dtype=jnp.uint32)
    return _descend(seed[0], seed[1], jnp.uint32(0), slot, cum, d, ft)


def kpgm_first_distinct(theta: np.ndarray, num_edges: int, key,
                        ft=jnp.float32):
    """The first ``num_edges`` distinct cells of graph 0's counter stream.

    Returns ``(edges, slots)``: an (E, 2) int64 array in stream order and
    the number of slots the law had to draw to find them."""
    d = theta.shape[0]
    seed = jnp.asarray(seed_words(round_key(key)))
    cum = quadrant_table(theta, ft)
    rows = KPGM_ROWS
    cells = []
    drawn = 0
    first = np.zeros(0, np.int64)
    while first.size < num_edges and drawn < (1 << 26):
        src, dst = _kpgm_block(seed, jnp.int32(drawn), cum, d=d, rows=rows,
                               ft=ft)
        cells.append((np.asarray(src).astype(np.int64) << d)
                     | np.asarray(dst).astype(np.int64))
        drawn += rows
        if drawn < num_edges:
            continue  # fewer slots than edges cannot hold enough cells
        flat = np.concatenate(cells)
        _, first = np.unique(flat, return_index=True)
    first.sort()
    first = first[:num_edges]
    picked = flat[first]
    edges = np.stack([picked >> d, picked & ((1 << d) - 1)], axis=1)
    slots = int(first[-1]) + 1 if first.size else 0
    return edges, slots


# ---------------------------------------------------------------------------
# comparison
# ---------------------------------------------------------------------------

# Both laws are deterministic functions of the graph key, and the reference
# computes them in the configuration's float32, so every checked graph must
# agree edge for edge and in order.
LIMITS = {"edges_off": 0, "order_off": 0}


def compare(edges: np.ndarray, ref: np.ndarray) -> dict:
    """``edges_off``: size of the multiset difference of the two edge
    lists (a missing, extra, altered or repeated edge each counts);
    ``order_off``: positions at which the two sequences differ, plus the
    difference of their lengths."""
    a = np.asarray(edges, np.int64).reshape(-1, 2)
    b = np.asarray(ref, np.int64).reshape(-1, 2)
    ka = (a[:, 0] << 32) | (a[:, 1] & 0xFFFFFFFF)
    kb = (b[:, 0] << 32) | (b[:, 1] & 0xFFFFFFFF)
    keys, inv = np.unique(np.concatenate([ka, kb]), return_inverse=True)
    ca = np.bincount(inv[: ka.size], minlength=keys.size)
    cb = np.bincount(inv[ka.size:], minlength=keys.size)
    m = min(ka.size, kb.size)
    return {
        "edges_off": int(np.abs(ca - cb).sum()),
        "order_off": int((ka[:m] != kb[:m]).sum()) + abs(ka.size - kb.size),
    }
