"""The config -> node lookup inside the descent kernel (interpret mode).

Where a round runs the Pallas kernel and the plan's dense inverse has rows
of at most ``KERNEL_LOOKUP_MAX_ROW`` entries, the kernel looks each graph's
configs up in its two block rows itself.  It must give what the descent
followed by ``quilt.gather_nodes`` gives, bit for bit; every other round
keeps the XLA gather and counts no ``quilt.kernel_lookup_slots``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import KPGMSampler, MAGMSampler, SamplerConfig
from repro.core import kpgm, magm, quilt
from repro.kernels import ops
from repro.kernels import quadrant_descent as qd

THETA = np.array([[0.15, 0.7], [0.7, 0.85]], dtype=np.float32)


def _cum(d):
    thetas = jnp.asarray(np.broadcast_to(THETA, (d, 2, 2)).copy())
    return kpgm._level_cumprobs(thetas)


def _inverse(rng, bsz, d, hit):
    """A (B, 2^d) table of node ids, -1 on a share ``1 - hit`` of it."""
    inv = rng.integers(0, 1 << 20, size=(bsz, 1 << d)).astype(np.int32)
    inv[rng.random(inv.shape) >= hit] = -1
    return jnp.asarray(inv)


# a_tot 1,000 and 5,000 are no multiple of the kernel's tile; gids run past
# B^2 as sample_batch's do (sample s's block pair g at s * B^2 + g)
@pytest.mark.parametrize("d", [5, 10, 16])
@pytest.mark.parametrize("bsz", [1, 2, 8])
def test_kernel_lookup_matches_descent_then_gather(d, bsz):
    rng = np.random.default_rng(100 * d + bsz)
    a_tot = 1_000 if d == 16 else 5_000
    gc = 2 if d == 16 else 3
    gids = jnp.asarray(
        rng.integers(0, 3 * bsz * bsz, size=gc).astype(np.int32)
    )
    inv = _inverse(rng, bsz, d, hit=0.6)
    seed = ops.counter_seed(jax.random.PRNGKey(d + bsz))
    cum = _cum(d)
    got = ops.descent_prng_pallas(
        seed, gids, cum, a_tot=a_tot, num_blocks=bsz, inv=inv
    )
    scfg, dcfg = ops.descent_prng_pallas(seed, gids, cum, a_tot=a_tot)
    _, gid = quilt.graph_major(gids, a_tot)
    block = gid % (bsz * bsz)
    snode, dnode = quilt.gather_nodes(
        (inv,), block // bsz, block % bsz, scfg, dcfg, d
    )
    names = ("scfg", "dcfg", "snode", "dnode")
    for g, w, name in zip(got, (scfg, dcfg, snode, dnode), names):
        assert g.shape == (gc * a_tot,) and g.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), name)
    for node in (np.asarray(snode), np.asarray(dnode)):
        assert (node >= 0).any() and (node == -1).any()


def test_kernel_lookup_refuses_rows_past_the_limit_and_rank_channels():
    seed = ops.counter_seed(jax.random.PRNGKey(0))
    gids = jnp.zeros((1,), jnp.int32)
    cum = _cum(5)
    wide = jnp.zeros((1, 2 * qd.KERNEL_LOOKUP_MAX_ROW), jnp.int32)
    with pytest.raises(ValueError, match="KERNEL_LOOKUP_MAX_ROW"):
        qd.descent_prng(seed, gids, cum, a_tot=8, inv=wide)
    with pytest.raises(ValueError, match="rank channels"):
        qd.descent_prng(
            seed, gids, cum, a_tot=8, ranks=True,
            inv=jnp.zeros((1, 32), jnp.int32),
        )


def _magm_config(use_kernel, d=6, n=48, **kw):
    F = np.asarray(
        magm.sample_attributes(jax.random.PRNGKey(3), n, jnp.full((d,), 0.5))
    )
    return SamplerConfig(
        params=magm.make_params(THETA, 0.5, d), F=F, use_kernel=use_kernel,
        **kw,
    )


def _lookup_slots():
    return quilt.ENGINE_COUNTERS["kernel_lookup_slots"]


def test_magm_sample_and_stream_match_the_jnp_path():
    """The kernel path (lookup in the kernel) gives the edges of the jnp
    twin, whole and streamed, and counts every slot as looked up there."""
    key = jax.random.PRNGKey(11)
    want = MAGMSampler(_magm_config(False)).sample(key).edges
    sampler = MAGMSampler(_magm_config(True))
    assert quilt.kernel_lookup(quilt.device_lookup(sampler.plan), True)
    before = dict(quilt.ENGINE_COUNTERS)
    got = sampler.sample(key).edges
    np.testing.assert_array_equal(got, want)
    rise = {k: quilt.ENGINE_COUNTERS[k] - before[k] for k in before}
    assert rise["kernel_lookup_slots"] == rise["candidate_slots"] > 0
    chunks = list(sampler.sample_stream(key, chunk_edges=64))
    np.testing.assert_array_equal(np.concatenate(chunks), want)


def test_magm_sample_batch_matches_the_jnp_path():
    """Fused batches put graph ids past B^2 into the kernel's row choice."""
    key = jax.random.PRNGKey(12)
    want = MAGMSampler(_magm_config(False)).sample_batch(3, key)
    got = MAGMSampler(_magm_config(True)).sample_batch(3, key)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g.edges, w.edges)


def _kpgm_d20_plan_keeps_the_gather():
    cfg = SamplerConfig(params=kpgm.make_params(THETA, d=20), use_kernel=True)
    sampler = KPGMSampler(cfg)
    assert sampler.plan.inv.shape == (1, 1 << 20)
    return sampler, lambda s: s.sample(jax.random.PRNGKey(2), num_edges=200)


def _bycfg_plan_keeps_the_gather():
    sampler = MAGMSampler(_magm_config(True))
    plan = sampler.plan._replace(inv=None)
    assert quilt.device_lookup(plan) is not None
    sampler.plan = plan
    return sampler, lambda s: s.sample(jax.random.PRNGKey(5))


def _balldrop_keeps_the_gather():
    sampler = MAGMSampler(_magm_config(True, backend="balldrop"))
    return sampler, lambda s: s.sample(jax.random.PRNGKey(6))


@pytest.mark.parametrize(
    "build",
    [_kpgm_d20_plan_keeps_the_gather, _bycfg_plan_keeps_the_gather,
     _balldrop_keeps_the_gather],
    ids=["kpgm_d20", "by_config", "balldrop"],
)
def test_other_rounds_keep_the_xla_gather(build):
    sampler, draw = build()
    tables = quilt.device_lookup(sampler.plan)
    if sampler.config.backend != "balldrop":
        assert not quilt.kernel_lookup(tables, True)
    before = dict(quilt.ENGINE_COUNTERS)
    assert draw(sampler).edges.shape[0] > 0
    assert quilt.ENGINE_COUNTERS["candidate_slots"] > before["candidate_slots"]
    assert _lookup_slots() == before["kernel_lookup_slots"]


def test_jnp_twin_keeps_the_xla_gather():
    tables = (jnp.zeros((2, 64), jnp.int32),)
    assert quilt.kernel_lookup(tables, True)
    assert not quilt.kernel_lookup(tables, False)
    assert not quilt.kernel_lookup(
        (jnp.zeros((1, 2 * ops.KERNEL_LOOKUP_MAX_ROW), jnp.int32),), True
    )
