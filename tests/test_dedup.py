"""Device segmented dedup == the PR-1 host np.unique path, per graph.

The sorted segmented dedup (core/dedup.py) must reproduce the host
semantics exactly: per graph, keep the FIRST ``target`` distinct (src, dst)
pairs of the candidate stream in arrival order.  Covers the packed-int64 and
multi-operand sort paths, the all-duplicates and zero-target edge cases, and
the batch-planning helpers."""

import numpy as np
import pytest

from repro.core import dedup


def _random_case(rng, num_graphs, node_bits, max_ask, dup_heavy=False):
    asks = rng.integers(0, max_ask, size=num_graphs)
    n_ids = 4 if dup_heavy else (1 << node_bits)
    total = int(asks.sum())
    src = rng.integers(0, n_ids, size=total).astype(np.int32)
    dst = rng.integers(0, n_ids, size=total).astype(np.int32)
    targets = rng.integers(0, max_ask, size=num_graphs)
    return src, dst, asks, targets


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("dup_heavy", [False, True])
def test_matches_host_unique_exactly(seed, dup_heavy):
    rng = np.random.default_rng(seed)
    src, dst, asks, targets = _random_case(
        rng, num_graphs=7, node_bits=5, max_ask=200, dup_heavy=dup_heavy
    )
    take, counts = dedup.segmented_unique(src, dst, asks, targets, node_bits=5)
    tref, cref = dedup.host_unique_reference(src, dst, asks, targets)
    np.testing.assert_array_equal(counts, cref)
    # arrival-order capping is part of the contract, so the mask must match
    # EXACTLY (not just as per-graph sets)
    np.testing.assert_array_equal(take, tref)


def test_edge_sets_identical_per_graph():
    """Set-level equivalence (the Theorem-3-facing property): per graph the
    kept (src, dst) sets match the np.unique path."""
    rng = np.random.default_rng(42)
    src, dst, asks, targets = _random_case(rng, 5, 6, 300)
    take, counts = dedup.segmented_unique(src, dst, asks, targets, node_bits=6)
    tref, _ = dedup.host_unique_reference(src, dst, asks, targets)
    off = 0
    for g, ask in enumerate(asks):
        sl = slice(off, off + int(ask))
        got = set(zip(src[sl][take[sl]], dst[sl][take[sl]]))
        want = set(zip(src[sl][tref[sl]], dst[sl][tref[sl]]))
        assert got == want, f"graph {g}"
        off += int(ask)


def test_multikey_fallback_matches_packed():
    """node_bits too wide for a 63-bit packed key -> 4-operand lax.sort path;
    both paths must agree with the host reference."""
    rng = np.random.default_rng(3)
    asks = np.array([64, 0, 130])
    total = int(asks.sum())
    src = rng.integers(0, 50, size=total).astype(np.int32)
    dst = rng.integers(0, 50, size=total).astype(np.int32)
    targets = np.array([30, 10, 500])
    tref, cref = dedup.host_unique_reference(src, dst, asks, targets)
    for node_bits in (6, 31):  # packed / multikey
        take, counts = dedup.segmented_unique(
            src, dst, asks, targets, node_bits=node_bits
        )
        np.testing.assert_array_equal(take, tref, err_msg=f"bits={node_bits}")
        np.testing.assert_array_equal(counts, cref)


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("node_bits", [6, 25])
def test_within_graph_positions_match_global_arrival(seed, node_bits):
    """The packed key holds each candidate's position within its graph
    (bounded by ``max_ask``), not its global arrival index: same take mask
    and counts as the host reference.  At node_bits=25 a global index would
    need the 4-operand fallback while the within-graph key fits one int64."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    src, dst, asks, targets = _random_case(rng, 12, 5, 300, dup_heavy=True)
    max_ask = int(asks.max())
    cum = np.cumsum(asks)
    gid = np.searchsorted(cum, np.arange(int(cum[-1])), side="right")
    _, _, global_fits = dedup._packed_bits(node_bits, asks.size, int(cum[-1]))
    _, _, fits = dedup._packed_bits(node_bits, asks.size, max_ask)
    assert fits and global_fits == (node_bits == 6)

    @jax.jit
    def run(g, s, d, c, t):
        return dedup.segmented_unique_mask(
            g, s, d, c, t, node_bits=node_bits, max_ask=max_ask
        )

    take, counts = dedup.call_x64(
        run, jnp.asarray(gid, jnp.int32), jnp.asarray(src), jnp.asarray(dst),
        jnp.asarray(cum, jnp.int32), jnp.asarray(targets, jnp.int32),
    )
    tref, cref = dedup.host_unique_reference(src, dst, asks, targets)
    np.testing.assert_array_equal(np.asarray(take), tref)
    np.testing.assert_array_equal(np.asarray(counts), cref)


def test_all_duplicates_keep_one():
    asks = np.array([100, 50])
    src = np.concatenate([np.full(100, 3), np.full(50, 1)]).astype(np.int32)
    dst = np.concatenate([np.full(100, 4), np.full(50, 2)]).astype(np.int32)
    targets = np.array([10, 10])
    take, counts = dedup.segmented_unique(src, dst, asks, targets, node_bits=3)
    np.testing.assert_array_equal(counts, [1, 1])
    assert take[0] and take[100], "first arrival of each graph must win"
    assert take.sum() == 2


def test_zero_targets_take_nothing():
    rng = np.random.default_rng(0)
    asks = np.array([40, 30, 0])
    src = rng.integers(0, 8, size=70).astype(np.int32)
    dst = rng.integers(0, 8, size=70).astype(np.int32)
    take, counts = dedup.segmented_unique(
        src, dst, asks, np.zeros(3, np.int64), node_bits=3
    )
    assert take.sum() == 0
    np.testing.assert_array_equal(counts, [0, 0, 0])


def test_cap_keeps_first_arrivals():
    """target smaller than the unique count: exactly the first `target`
    distinct pairs in stream order survive (no value-order bias)."""
    asks = np.array([6])
    src = np.array([7, 1, 7, 5, 0, 2], dtype=np.int32)  # 7 dup at index 2
    dst = np.array([0, 0, 0, 0, 0, 0], dtype=np.int32)
    take, counts = dedup.segmented_unique(
        src, dst, asks, np.array([3]), node_bits=3
    )
    np.testing.assert_array_equal(take, [True, True, False, True, False, False])
    np.testing.assert_array_equal(counts, [3])


def test_bucket_size_grid():
    assert dedup.bucket_size(1) == 16
    assert dedup.bucket_size(17) == 18  # 9 * 2
    for x in (100, 1000, 12345, 10**6):
        b = dedup.bucket_size(x)
        assert b >= x and b <= x * 1.125 + 16
    assert dedup.bucket_size(100, tile=512) % 512 == 0


def test_plan_asks_consumes_full_batch():
    needs = np.array([100, 0, 55, 7])
    asks, n = dedup.plan_asks(needs, 1.1)
    assert int(asks.sum()) == n
    assert asks[1] == 0  # satisfied graphs draw nothing
    assert (asks[needs > 0] >= needs[needs > 0]).all()
    asks2, n2 = dedup.plan_asks(np.zeros(4, np.int64), 1.1)
    assert n2 == 0 and asks2.sum() == 0


def test_uniform_ask_covers_max_need_and_buckets():
    needs = np.array([100, 0, 55, 7])
    a = dedup.uniform_ask(needs, 1.05)
    assert a == dedup.bucket_size(int(100 * 1.05) + 16)
    assert a >= int(needs.max() * 1.05) + 16
    # layout-invariant: only the max matters, not the graph count or order
    assert dedup.uniform_ask(needs[::-1], 1.05) == a
    assert dedup.uniform_ask(np.array([100]), 1.05) == a
    assert dedup.uniform_ask(np.zeros(5, np.int64), 1.05) == 0
    assert dedup.uniform_ask(np.array([-3, 0]), 1.05) == 0


def test_dedup_edges_keeps_first_arrivals():
    edges = np.array([[3, 1], [0, 2], [3, 1], [0, 0], [0, 2], [3, 1]])
    np.testing.assert_array_equal(
        dedup.dedup_edges(edges), [[3, 1], [0, 2], [0, 0]]
    )
    assert dedup.dedup_edges(np.empty((0, 2))).shape == (0, 2)
    # already-unique streams come back untouched, in order
    uniq = np.array([[5, 5], [1, 9], [0, 0]])
    np.testing.assert_array_equal(dedup.dedup_edges(uniq), uniq)


# ---------------------------------------------------------------------------
# boundary coverage: rechunk / chunk iteration / ask planning / valid mask
# ---------------------------------------------------------------------------


def test_rechunk_edges_boundaries():
    pieces = [np.arange(10).reshape(5, 2)]
    # chunk_edges=1: one row per chunk, order preserved
    chunks = list(dedup.rechunk_edges(pieces, 1))
    assert [c.shape for c in chunks] == [(1, 2)] * 5
    np.testing.assert_array_equal(np.concatenate(chunks), pieces[0])
    # chunk_edges >= total: a single short chunk
    chunks = list(dedup.rechunk_edges(pieces, 100))
    assert len(chunks) == 1
    np.testing.assert_array_equal(chunks[0], pieces[0])
    # chunk_edges == total exactly: one full chunk, no trailing empty
    chunks = list(dedup.rechunk_edges(pieces, 5))
    assert [c.shape for c in chunks] == [(5, 2)]
    # all-empty pieces: nothing yielded (not a zero-row chunk)
    assert list(dedup.rechunk_edges([np.zeros((0, 2))] * 3, 4)) == []
    assert list(dedup.rechunk_edges([], 4)) == []
    # empty pieces interleaved: invisible in the output
    inter = [np.zeros((0, 2)), pieces[0][:2], np.zeros((0, 2)), pieces[0][2:]]
    np.testing.assert_array_equal(
        np.concatenate(list(dedup.rechunk_edges(inter, 2))), pieces[0]
    )
    with pytest.raises(ValueError, match="chunk_edges"):
        list(dedup.rechunk_edges(pieces, 0))
    with pytest.raises(ValueError, match="chunk_edges"):
        list(dedup.rechunk_edges(pieces, -3))


def test_iter_edge_chunks_boundaries():
    src = np.array([5, 6, 7, 8], dtype=np.int64)
    dst = np.array([1, 2, 3, 4], dtype=np.int64)
    keep = np.array([True, False, True, True])
    want = np.array([[5, 1], [7, 3], [8, 4]])
    # chunk_edges=1 and chunk_edges >= kept rows
    for ce, shapes in [(1, [(1, 2)] * 3), (64, [(3, 2)])]:
        chunks = list(dedup.iter_edge_chunks(src, dst, keep, ce))
        assert [c.shape for c in chunks] == shapes
        np.testing.assert_array_equal(np.concatenate(chunks), want)
    # nothing kept, no tail: empty stream
    assert list(dedup.iter_edge_chunks(src, dst, np.zeros(4, bool), 8)) == []
    # tail-only emission (host top-up with zero device keeps)
    tail = [np.array([[9, 9], [2, 2]])]
    chunks = list(
        dedup.iter_edge_chunks(src, dst, np.zeros(4, bool), 8, tail=tail)
    )
    np.testing.assert_array_equal(np.concatenate(chunks), tail[0])
    # device keeps + tail append in emission order
    chunks = list(dedup.iter_edge_chunks(src, dst, keep, 2, tail=tail))
    np.testing.assert_array_equal(
        np.concatenate(chunks), np.concatenate([want, tail[0]])
    )


def test_uniform_ask_all_zero_needs():
    """No graph needs anything -> 0 slots (not bucket_size(16))."""
    assert dedup.uniform_ask(np.zeros(5, np.int64), 1.5) == 0
    assert dedup.uniform_ask(np.array([-3, 0, -1]), 2.0) == 0  # clamped
    assert dedup.uniform_ask(np.array([]), 1.5) == 0
    # one positive need still gets the +16 margin and bucketing
    assert dedup.uniform_ask(np.array([0, 4, 0]), 1.0) >= 20


def test_valid_mask_excludes_rejected_candidates():
    """segmented_unique_mask(valid=...): invalid rows are never taken and
    never shadow a later valid copy of the same pair; valid=None is
    bit-identical to the pre-existing behaviour."""
    import jax.numpy as jnp

    asks = np.array([6, 4], dtype=np.int32)
    # graph 0: invalid (3,3) first, then valid (3,3) -> the VALID copy wins
    src = np.array([3, 3, 0, 0, 1, 2, 5, 5, -1, 4], dtype=np.int32)
    dst = np.array([3, 3, 0, 0, 1, 0, 5, 5, -1, 4], dtype=np.int32)
    valid = np.array([0, 1, 1, 1, 1, 0, 1, 1, 0, 1], dtype=bool)
    targets = np.array([10, 10], dtype=np.int32)
    gid = np.repeat(np.arange(2), asks).astype(np.int32)
    cum = np.cumsum(asks).astype(np.int32)

    def run(valid_arg):
        take, counts = dedup.call_x64(
            dedup.segmented_unique_mask,
            jnp.asarray(gid),
            jnp.asarray(src),
            jnp.asarray(dst),
            jnp.asarray(cum),
            jnp.asarray(targets),
            node_bits=4,
            max_ask=int(asks.max()),
            valid=valid_arg,
        )
        return np.asarray(take), np.asarray(counts)

    take, counts = run(jnp.asarray(valid))
    np.testing.assert_array_equal(
        take, [False, True, True, False, True, False, True, False, False, True]
    )
    np.testing.assert_array_equal(counts, [3, 2])
    # valid=None path unchanged: matches the host reference exactly
    take0, counts0 = run(None)
    tref, cref = dedup.host_unique_reference(src, dst, asks, targets)
    np.testing.assert_array_equal(take0, tref)
    np.testing.assert_array_equal(counts0, cref)
