"""Counter-based in-kernel PRNG: bit-identity, uniformity, and the
cumulative-slot (prefix-stable top-up) contract.

The counter PRNG replaces the HBM uniforms operand of the descent kernels
with a splitmix-style hash of ``(seed, graph, slot*64 + channel)`` computed
inside the kernel body.  Everything downstream leans on three properties
pinned here:

- **bit-identity** — the Pallas kernels and the jnp fallback share the
  exact uint32 math, so kernel path == jnp path edge for edge (the engine
  parity test in test_quilt_plan rides on this at the round level);
- **uniformity** — chi-square on the raw hash stream and on the rank
  channels (the 3-sigma suite then closes the loop on graph statistics);
- **cumulative slots** — slot s hashes the same regardless of how rounds
  chunk the candidate axis, so a top-up round extends the stream instead
  of reshuffling it (mesh-layout invariance is the same property across
  shards).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from scipy import stats

from repro.kernels import ops, ref
from repro.kernels import quadrant_descent as qd

THETA = np.array([[0.15, 0.7], [0.7, 0.85]], dtype=np.float32)


def _thetas(d):
    return jnp.asarray(np.broadcast_to(THETA, (d, 2, 2)).copy())


def _cum(thetas):
    flat = thetas.reshape(-1, 4)
    return jnp.cumsum(flat / flat.sum(axis=1, keepdims=True), axis=1)


def _seed(i=0):
    return ops.counter_seed(jax.random.PRNGKey(i))


# ---------------------------------------------------------------------------
# raw-stream uniformity
# ---------------------------------------------------------------------------


def test_counter_hash_chi_square_uniform():
    """64-bin chi-square on the raw 32-bit hash stream (one graph)."""
    seed = _seed(0)
    n = 1 << 16
    word = jnp.arange(n, dtype=jnp.uint32)
    gid = jnp.zeros((n,), jnp.int32)
    bits = np.asarray(ops.counter_hash(seed[0, 0], seed[0, 1], gid, word))
    counts = np.bincount(bits >> np.uint32(26), minlength=64)
    chi2 = ((counts - n / 64) ** 2 / (n / 64)).sum()
    # 63 dof: P(chi2 > 103.4) = 0.1%
    assert chi2 < 103.4, f"chi2={chi2:.1f} on 63 dof"


def test_counter_u01_range_and_mean():
    seed = _seed(3)
    n = 1 << 15
    u = np.asarray(
        ops.counter_u01(
            seed[0, 0], seed[0, 1],
            jnp.zeros((n,), jnp.int32), jnp.arange(n, dtype=jnp.uint32),
        )
    )
    assert u.min() >= 0.0 and u.max() < 1.0
    assert abs(u.mean() - 0.5) < 4 / np.sqrt(12 * n)


def test_counter_rank_chi_square_uniform():
    """Rank channels must be uniform over num_blocks (non power of two)."""
    seed = _seed(5)
    n = 1 << 15
    B = 7
    kb, lb = ops.rank_pair(
        seed[0, 0], seed[0, 1],
        jnp.zeros((n,), jnp.int32), jnp.arange(n, dtype=jnp.int32), B,
    )
    for r in (np.asarray(kb), np.asarray(lb)):
        assert r.min() >= 0 and r.max() < B
        counts = np.bincount(r, minlength=B)
        chi2 = ((counts - n / B) ** 2 / (n / B)).sum()
        assert chi2 < stats.chi2.ppf(0.999, B - 1), f"chi2={chi2:.1f}"


def test_streams_decorrelated_across_seed_and_graph():
    """Different seeds and different graph ids give unrelated streams."""
    n = 1 << 14
    word = jnp.arange(n, dtype=jnp.uint32)
    gid0 = jnp.zeros((n,), jnp.int32)
    s0, s1 = _seed(0), _seed(1)
    a = np.asarray(ops.counter_hash(s0[0, 0], s0[0, 1], gid0, word))
    b = np.asarray(ops.counter_hash(s1[0, 0], s1[0, 1], gid0, word))
    c = np.asarray(
        ops.counter_hash(s0[0, 0], s0[0, 1], jnp.ones((n,), jnp.int32), word)
    )
    assert (a == b).mean() < 0.01
    assert (a == c).mean() < 0.01


# ---------------------------------------------------------------------------
# kernel == jnp fallback bit-identity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("d", [1, 7, 20])
def test_prng_kernel_matches_jnp_twin(d):
    thetas = _thetas(d)
    seed = _seed(d)
    n = 2 * qd.TILE
    src_k, dst_k = qd.quadrant_descent_prng(
        seed, _cum(thetas), num_slots=n, interpret=True
    )
    slot = jnp.arange(n, dtype=jnp.int32)
    gid = jnp.zeros((n,), jnp.int32)
    u = ops.descent_uniforms(seed[0, 0], seed[0, 1], gid, slot, d)
    src_j, dst_j = ref.quadrant_descent_ref(u, _cum(thetas))
    np.testing.assert_array_equal(np.asarray(src_k), np.asarray(src_j))
    np.testing.assert_array_equal(np.asarray(dst_k), np.asarray(dst_j))


@pytest.mark.parametrize("ranks", [False, True])
def test_fused_prng_kernel_matches_jnp_twin(ranks):
    """descent_prng over several graphs == the jnp assembly of
    descent_uniforms / rank_pair + descent, every output bit-exact."""
    d, bsz = 6, 5
    a_tot, gc = 700, 3
    rng = np.random.default_rng(42)
    thetas = _thetas(d)
    seed = _seed(9)
    gids = jnp.asarray(
        rng.choice(bsz * bsz, size=gc, replace=False).astype(np.int32)
    )
    got = ops.descent_prng_pallas(
        seed, gids, _cum(thetas), a_tot=a_tot, num_blocks=bsz, ranks=ranks,
    )
    n = gc * a_tot
    local = jnp.arange(n, dtype=jnp.int32) // a_tot
    gid = gids[local]
    slot = jnp.arange(n, dtype=jnp.int32) - local * a_tot
    u = ops.descent_uniforms(seed[0, 0], seed[0, 1], gid, slot, d)
    want = ref.quadrant_descent_ref(u, _cum(thetas))
    names = ("scfg", "dcfg")
    if ranks:
        want = want + ops.rank_pair(seed[0, 0], seed[0, 1], gid, slot, bsz)
        names = names + ("kb", "lb")
    assert len(got) == len(want)
    for g, w, name in zip(got, want, names):
        assert g.shape == (n,)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), err_msg=name)


def test_counter_seed_typed_and_raw_keys_agree():
    key = jax.random.PRNGKey(123)
    raw = jax.random.key_data(jax.random.wrap_key_data(jax.random.key_data(key)))
    typed = jax.random.wrap_key_data(jax.random.key_data(key))
    s_key = np.asarray(ops.counter_seed(key))
    s_raw = np.asarray(ops.counter_seed(raw))
    s_typed = np.asarray(ops.counter_seed(typed))
    assert s_key.shape == (1, 2) and s_key.dtype == np.int32
    np.testing.assert_array_equal(s_key, s_raw)
    np.testing.assert_array_equal(s_key, s_typed)


def test_counter_seed_traceable_under_jit():
    got = jax.jit(ops.counter_seed)(jax.random.PRNGKey(123))
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(ops.counter_seed(jax.random.PRNGKey(123)))
    )


def test_tpu_native_raises_in_interpret_mode():
    with pytest.raises(ValueError, match="tpu_native"):
        qd.quadrant_descent_prng(
            _seed(0), _cum(_thetas(3)),
            num_slots=qd.TILE, interpret=True, tpu_native=True,
        )


# ---------------------------------------------------------------------------
# cumulative slots: prefix-stable streams
# ---------------------------------------------------------------------------


def test_sample_edge_batch_prng_prefix_property():
    """A shorter draw is a strict prefix of a longer one under the same
    key — the property that makes top-up rounds extend, not reshuffle."""
    d = 8
    thetas = _thetas(d)
    key = jax.random.PRNGKey(17)
    s_small, t_small = ops.sample_edge_batch_prng(key, thetas, 100)
    s_big, t_big = ops.sample_edge_batch_prng(key, thetas, 8000)
    np.testing.assert_array_equal(np.asarray(s_small), np.asarray(s_big)[:100])
    np.testing.assert_array_equal(np.asarray(t_small), np.asarray(t_big)[:100])


def test_sample_edge_batch_prng_distribution():
    d = 6
    thetas = _thetas(d)
    src, dst = ops.sample_edge_batch_prng(jax.random.PRNGKey(0), thetas, 8000)
    a = (np.asarray(src) >= 2 ** (d - 1)).astype(int)
    b = (np.asarray(dst) >= 2 ** (d - 1)).astype(int)
    frac = np.bincount(2 * a + b, minlength=4) / 8000
    np.testing.assert_allclose(frac, THETA.reshape(-1) / THETA.sum(), atol=0.03)
