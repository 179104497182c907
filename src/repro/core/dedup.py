"""Device-resident segmented dedup for batched KPGM rejection sampling.

Algorithm 1 dedupes candidate edges against the edges already accepted;
Algorithm 2 needs that for all B^2 block-pair graphs at once.  The PR-1 host
path paid one ``np.unique`` + ``np.isin`` per graph per top-up round — O(B^2)
host<->device round-trips.  This module replaces it with ONE jitted
sort-based segmented dedup over all graphs:

    key_i = (graph_id_i << 2d) | (src_i << d) | (dst_i << arrival_bits'...)

Concretely every candidate is packed into a single int64

    graph_id << (2*node_bits + arrival_bits)
        | src << (node_bits + arrival_bits)
        | dst << arrival_bits
        | arrival

so ONE single-operand sort groups duplicates while the low ``arrival`` bits
keep a strict total order (no stable-sort needed) AND carry the permutation.
A second, cheap int32 sort on ``(arrival << 1) | is_first`` restores arrival
order — sorts are ~4x cheaper than the equivalent scatter on CPU XLA, and
single-operand sorts are ~5x cheaper than multi-operand ones.

Arrival order matters: Algorithm 1 keeps the FIRST ``target`` distinct edges
of the candidate stream (truncating a value-sorted list would bias kept edges
toward low node ids).  The returned ``take`` mask marks, per graph, the first
``min(target_g, uniques_g)`` distinct candidates in stream order; outputs are
fixed-shape (mask + per-graph counts), so the compiled program is cached
across calls of the same bucketed batch size.

When the packed key does not fit in 63 bits (large d and many graphs) the
same computation runs on a 4-operand lexicographic ``lax.sort`` — slower but
correct for any d <= 31.

int64 keys require the x64 context: callers wrap jitted entry points with
:func:`call_x64` (all dtypes inside are pinned, so enabling x64 only widens
the packed keys, nothing else).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core.transfer import ENGINE_COUNTERS, to_host

__all__ = [
    "bucket_size",
    "plan_asks",
    "uniform_ask",
    "dedup_edges",
    "rechunk_edges",
    "iter_edge_chunks",
    "segmented_unique_mask",
    "segmented_unique",
    "call_x64",
    "host_unique_reference",
]

def bucket_size(x: int, tile: int = 1) -> int:
    """Round ``x`` up to the geometric grid {8..15} * 2^k (ratio <= 1.125),
    then to a multiple of ``tile``.

    Candidate-batch shapes must be bucketed or every call recompiles the
    round program; the fine grid wastes <= 12.5%% of generated candidates.
    """
    x = max(int(x), 1)
    if x <= 16:
        b = 16
    else:
        k = x.bit_length() - 4  # so that 8 * 2^k <= x < 16 * 2^k
        base = 1 << k
        b = 16 * base
        for mult in range(8, 16):
            if mult * base >= x:
                b = mult * base
                break
    return b + (-b) % max(int(tile), 1)


def plan_asks(
    needs: np.ndarray, oversample: float, tile: int = 1
) -> Tuple[np.ndarray, int]:
    """Split one bucketed candidate batch across the graphs that need edges.

    Every graph with ``needs[g] > 0`` gets ~``needs[g] * oversample + 16``
    slots; the whole bucket is then consumed (the remainder is spread over the
    needing graphs instead of discarded, so fewer top-up rounds are needed).
    Returns ``(asks, N)`` with ``asks.sum() == N`` and N a bucket multiple of
    ``tile``.
    """
    needs = np.maximum(np.asarray(needs, dtype=np.int64), 0)
    raw = np.where(needs > 0, (needs * oversample).astype(np.int64) + 16, 0)
    total = int(raw.sum())
    if total == 0:
        return np.zeros_like(needs), 0
    n = bucket_size(total, tile)
    asks = raw * n // total
    idx = np.nonzero(needs > 0)[0]
    deficit = int(n - asks.sum())
    q, r = divmod(deficit, idx.size)
    asks[idx] += q
    asks[idx[:r]] += 1
    return asks, n


def uniform_ask(needs: np.ndarray, oversample: float, tile: int = 1) -> int:
    """One SHARED per-graph slot count covering the largest shortfall.

    The mesh-sharded quilting round gives every graph the same number of
    candidate slots, so (a) all shards of a ``shard_map`` run the identical
    program shape and (b) each graph's candidate stream depends only on its
    own folded key and this count — never on how graphs are laid out across
    devices.  Returns ``bucket_size(max(needs) * oversample + 16)`` (0 when
    nothing is needed); per-graph margins are therefore at least as generous
    as :func:`plan_asks` gives the neediest graph.
    """
    needs = np.maximum(np.asarray(needs, dtype=np.int64), 0)
    top = int(needs.max(initial=0))
    if top == 0:
        return 0
    return bucket_size(int(top * oversample) + 16, tile)


def dedup_edges(edges: np.ndarray) -> np.ndarray:
    """First-occurrence unique rows of an ``(E, 2)`` edge array.

    Host-side convenience mirroring the arrival-order semantics of the device
    dedup (:func:`segmented_unique_mask`): the FIRST copy of each ``(src,
    dst)`` pair is kept, in stream order.  Node ids must fit in 31 bits.

    Examples
    --------
    >>> import numpy as np
    >>> from repro.core.dedup import dedup_edges
    >>> dedup_edges(np.array([[3, 1], [0, 2], [3, 1], [0, 0]]))
    array([[3, 1],
           [0, 2],
           [0, 0]])
    >>> dedup_edges(np.empty((0, 2))).shape
    (0, 2)
    """
    edges = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    if edges.shape[0] == 0:
        return edges
    key = (edges[:, 0] << 32) | edges[:, 1]
    _, first_idx = np.unique(key, return_index=True)
    return edges[np.sort(first_idx)]


def rechunk_edges(pieces, chunk_edges: int):
    """Re-chunk a stream of ``(E_i, 2)`` edge pieces into fixed-size chunks.

    Yields ``(chunk_edges, 2)`` int64 arrays; only the final chunk may be
    shorter.  Empty pieces are skipped; at most one chunk is buffered, so
    the full edge list is never materialized.

    Examples
    --------
    >>> import numpy as np
    >>> from repro.core.dedup import rechunk_edges
    >>> pieces = [np.arange(6).reshape(3, 2), np.arange(4).reshape(2, 2)]
    >>> [c.shape for c in rechunk_edges(pieces, 2)]
    [(2, 2), (2, 2), (1, 2)]
    >>> np.concatenate(list(rechunk_edges(pieces, 4)), axis=0).shape
    (5, 2)
    """
    chunk_edges = int(chunk_edges)
    if chunk_edges <= 0:
        raise ValueError(f"chunk_edges must be positive, got {chunk_edges}")
    buf: list = []
    have = 0
    for piece in pieces:
        with tracing.span("stream.rechunk"):
            p = np.asarray(piece, dtype=np.int64).reshape(-1, 2)
        while p.shape[0]:
            take = min(chunk_edges - have, p.shape[0])
            buf.append(p[:take])
            have += take
            p = p[take:]
            if have == chunk_edges:
                with tracing.span("stream.rechunk"):
                    chunk = np.concatenate(buf, axis=0)
                yield chunk
                buf, have = [], 0
    if have:
        with tracing.span("stream.rechunk"):
            chunk = np.concatenate(buf, axis=0)
        yield chunk


def iter_edge_chunks(
    src, dst, keep: np.ndarray, chunk_edges: int, tail=()
):
    """Stream the kept ``(src, dst)`` rows of a candidate buffer in chunks.

    The chunked-emission hook of the device quilting pipeline
    (``repro.api.MAGMSampler.sample_stream``): ``src``/``dst`` are the
    fixed-shape per-round candidate buffers (device or host arrays) and
    ``keep`` the host-side boolean take mask.  The buffers are walked in
    windows — each window is sliced on device and only its kept rows reach
    the host — so at no point does the full ``(E, 2)`` edge list
    materialize.  ``tail`` pieces (host top-up edges) are appended after the
    device edges, matching the concatenated-array emission order exactly.
    Yields ``(chunk_edges, 2)`` int64 arrays (final chunk may be shorter).
    """

    def pieces():
        window = max(int(chunk_edges), 1 << 15)
        for (base, s_blk), (_, d_blk) in zip(
            _row_blocks(src), _row_blocks(dst)
        ):
            for off in range(0, s_blk.shape[0], window):
                with tracing.span("stream.window"):
                    hi = min(off + window, s_blk.shape[0])
                    k = keep[base + off : base + hi]
                    if not k.any():
                        continue
                    s = to_host(s_blk[off : off + window])[k]
                    d = to_host(d_blk[off : off + window])[k]
                    piece = np.stack([s, d], axis=1)
                    ENGINE_COUNTERS["kept_edges"] += piece.shape[0]
                yield piece
        for t in tail:
            yield t

    return rechunk_edges(pieces(), chunk_edges)


def _row_blocks(x):
    """``(row offset, single-device array)`` blocks of a 1-D candidate
    buffer in row order: the buffer itself, or — when it is sharded over
    the ``graphs`` axis — each device's shard, so no window slices across
    devices."""
    shards = getattr(x, "addressable_shards", None)
    if not shards or len(shards) == 1:
        return [(0, x)]
    blocks = {}
    for shard in shards:
        blocks.setdefault(shard.index[0].start or 0, shard.data)
    return sorted(blocks.items(), key=lambda b: b[0])


def _packed_bits(node_bits: int, num_graphs: int, n: int) -> Tuple[int, int, bool]:
    glog = max(int(num_graphs - 1).bit_length(), 1) if num_graphs > 1 else 1
    abits = max(int(n - 1).bit_length(), 1) if n > 1 else 1
    fits = glog + 2 * node_bits + abits <= 63
    return glog, abits, fits


def segmented_unique_mask(
    graph_id: jax.Array,
    src: jax.Array,
    dst: jax.Array,
    cum_asks: jax.Array,
    targets: jax.Array,
    *,
    node_bits: int,
    max_ask: int,
    valid: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Per-graph first-occurrence mask with arrival-order target capping.

    Traceable (call under jit + x64).  ``graph_id`` must be non-decreasing —
    candidates are laid out in contiguous per-graph chunks whose inclusive
    ends are ``cum_asks`` (so chunk g is ``[cum_asks[g-1], cum_asks[g])``).
    Returns ``(take, counts)``: ``take[i]`` marks candidate i as one of the
    first ``targets[g]`` distinct ``(src, dst)`` pairs of its graph in stream
    order, and ``counts[g] = take[graph_id == g].sum()``.

    ``valid`` (optional bool mask) excludes rejected candidates — e.g. the
    ball-dropping backend's per-block lookup misses — from both the distinct
    ranking and the output: invalid rows are remapped to an out-of-range
    sentinel pair before packing (one extra bit per node id, so their
    ``src``/``dst`` values, -1 included, never collide with real edges) and
    are never fresh, so the per-graph target is filled by valid pairs only.

    ``max_ask`` (static) bounds every chunk's length.  The packed key
    carries the candidate's position WITHIN its graph, not its global
    arrival index — the graph bits already order the chunks — so it needs
    ``log2(max_ask)`` position bits, which keeps large rounds on the single
    int64 sort (the 4-operand fallback costs far more to compile for a
    TPU).  ``max_ask = len(src)`` is always a valid bound.
    """
    n = src.shape[0]
    num_graphs = targets.shape[0]
    if valid is not None:
        # sentinel > any real node id; needs node_bits + 1 per id to pack
        sentinel = jnp.int32(1) << node_bits
        src = jnp.where(valid, src.astype(jnp.int32), sentinel)
        dst = jnp.where(valid, dst.astype(jnp.int32), sentinel)
        node_bits = node_bits + 1
    arrival = jnp.arange(n, dtype=jnp.int64)
    offs_ex = jnp.concatenate([jnp.zeros((1,), cum_asks.dtype), cum_asks[:-1]])
    _, abits, fits = _packed_bits(node_bits, num_graphs, max_ask)
    pos = arrival - offs_ex[graph_id].astype(jnp.int64)

    if fits:
        key = (
            (graph_id.astype(jnp.int64) << (2 * node_bits + abits))
            | (src.astype(jnp.int64) << (node_bits + abits))
            | (dst.astype(jnp.int64) << abits)
            | pos
        )
        ks = jnp.sort(key)
        edge = ks >> abits  # (graph, src, dst) with the position stripped
        first = jnp.concatenate(
            [jnp.ones((1,), bool), edge[1:] != edge[:-1]]
        )
        pos_sorted = (ks & ((jnp.int64(1) << abits) - 1)).astype(jnp.int32)
        g_sorted = (ks >> (2 * node_bits + abits)).astype(jnp.int32)
        arr_sorted = pos_sorted + offs_ex[g_sorted].astype(jnp.int32)
    else:
        gs, ss, ds, arr_s = jax.lax.sort(
            (
                graph_id.astype(jnp.int32),
                src.astype(jnp.int32),
                dst.astype(jnp.int32),
                arrival.astype(jnp.int32),
            ),
            num_keys=4,
        )
        first = jnp.concatenate(
            [
                jnp.ones((1,), bool),
                (gs[1:] != gs[:-1]) | (ss[1:] != ss[:-1]) | (ds[1:] != ds[:-1]),
            ]
        )
        arr_sorted = arr_s

    # second 1-operand sort un-permutes the flags back to arrival order
    # (arrival values are unique, so this is an exact inverse permutation)
    restore = jnp.sort((arr_sorted.astype(jnp.int32) << 1) | first)
    fresh = (restore & 1) > 0
    if valid is not None:
        fresh = fresh & valid

    c = jnp.cumsum(fresh.astype(jnp.int32))
    ends = jnp.maximum(cum_asks - 1, 0)
    base = jnp.where(offs_ex > 0, c[jnp.maximum(offs_ex - 1, 0)], 0)
    rank = c - base[graph_id]  # 1-based rank among fresh, per graph
    take = fresh & (rank <= targets[graph_id])

    ct = jnp.cumsum(take.astype(jnp.int32))
    counts = ct[ends] - jnp.where(offs_ex > 0, ct[jnp.maximum(offs_ex - 1, 0)], 0)
    counts = jnp.where(cum_asks > offs_ex, counts, 0)
    return take, counts


@functools.partial(jax.jit, static_argnames=("node_bits",))
def _segmented_unique_jit(src, dst, asks, targets, *, node_bits):
    n = src.shape[0]
    cum_asks = jnp.cumsum(asks)
    graph_id = jnp.searchsorted(
        cum_asks, jnp.arange(n, dtype=asks.dtype), side="right"
    ).astype(jnp.int32)
    return segmented_unique_mask(
        graph_id, src, dst, cum_asks, targets, node_bits=node_bits, max_ask=n
    )


def call_x64(fn, *args, **kwargs):
    """Run a jitted dedup entry point under the x64 context (int64 keys).

    All dtypes inside the traced code are pinned explicitly, so the context
    only makes int64 available — inputs/outputs keep their 32-bit dtypes.
    """
    with jax.enable_x64(True):
        return fn(*args, **kwargs)


def segmented_unique(
    src: np.ndarray,
    dst: np.ndarray,
    asks: np.ndarray,
    targets: np.ndarray,
    *,
    node_bits: int,
) -> Tuple[np.ndarray, np.ndarray]:
    """One-shot convenience wrapper: dedup a host candidate stream per graph.

    ``asks.sum()`` must equal ``len(src)``.  Returns host ``(take, counts)``.
    """
    take, counts = call_x64(
        _segmented_unique_jit,
        jnp.asarray(src, jnp.int32),
        jnp.asarray(dst, jnp.int32),
        jnp.asarray(asks, jnp.int32),
        jnp.asarray(targets, jnp.int32),
        node_bits=node_bits,
    )
    return np.asarray(take), np.asarray(counts)


def host_unique_reference(
    src: np.ndarray,
    dst: np.ndarray,
    asks: np.ndarray,
    targets: np.ndarray,
) -> Tuple[np.ndarray, np.ndarray]:
    """The PR-1 host semantics (np.unique in arrival order, capped), as a
    reference oracle for the device path."""
    take = np.zeros(src.shape[0], dtype=bool)
    counts = np.zeros(len(asks), dtype=np.int64)
    off = 0
    for g, ask in enumerate(np.asarray(asks, dtype=np.int64)):
        chunk = slice(off, off + int(ask))
        flat = src[chunk].astype(np.int64) << 32 | dst[chunk].astype(np.int64)
        _, first_idx = np.unique(flat, return_index=True)
        keep_local = np.sort(first_idx)[: int(targets[g])]
        take[off + keep_local] = True
        counts[g] = keep_local.size
        off += int(ask)
    return take, counts
