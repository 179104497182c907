"""Cross-backend statistical validation (the acceptance gate of the
ball-dropping backend).

All three backends — "auto" (device quilting), "host" (the reference
loop), and "balldrop" (arXiv:1202.6001) — sample the SAME conditional
graph distribution for one realized attribute matrix, so their edge-count,
per-block, degree-histogram, and isolated-node statistics must agree with
each other AND with the closed-form Kronecker quadratic forms, to 3 sigma
at n = 2^12.  The kron machinery itself is pinned against dense
constructions at small d, and the isolated-node expectation against the
exact product formula (arXiv:1901.09698 asymptotics with higher-order
corrections).
"""

import itertools
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.analysis import validate
from repro.api import KPGMSampler, MAGMSampler, SamplerConfig
from repro.core import kpgm, kron, magm, quilt

# multi-seed n=2^12 sampling statistics: slow_stats CI job, not tier-1 fast
pytestmark = pytest.mark.slow_stats

THETA = np.array([[0.35, 0.52], [0.52, 0.95]], dtype=np.float32)
N = 1 << 12
D = 12
MU = 0.5
SEEDS = range(4)
BACKENDS = ("auto", "host", "balldrop")


def _dense_P(thetas: np.ndarray) -> np.ndarray:
    P = np.ones((1, 1))
    for th in thetas:
        P = np.kron(P, np.asarray(th, dtype=np.float64))
    return P


# ---------------------------------------------------------------------------
# kron quadratic forms vs dense constructions (small d)
# ---------------------------------------------------------------------------


def test_kron_matvec_matches_dense():
    rng = np.random.default_rng(0)
    thetas = rng.uniform(0.1, 0.9, size=(5, 2, 2))
    v = rng.normal(size=1 << 5)
    P = _dense_P(thetas)
    np.testing.assert_allclose(kron.kron_matvec(thetas, v), P @ v, rtol=1e-12)
    np.testing.assert_allclose(
        kron.kron_rmatvec(thetas, v), P.T @ v, rtol=1e-12
    )
    np.testing.assert_allclose(kron.kron_diag(thetas), np.diag(P), rtol=1e-12)


def test_edge_count_moments_match_dense():
    rng = np.random.default_rng(1)
    thetas = rng.uniform(0.1, 0.9, size=(4, 2, 2))
    c = rng.integers(0, 4, size=1 << 4).astype(np.float64)
    P = _dense_P(thetas)
    mean, std = kron.edge_count_moments(c, thetas)
    np.testing.assert_allclose(mean, c @ P @ c, rtol=1e-12)
    np.testing.assert_allclose(
        std, np.sqrt(c @ P @ c - c @ (P * P) @ c), rtol=1e-12
    )


def test_block_moments_match_dense_small():
    """theory_moments block means == brute-force sums over node pairs."""
    d, n = 6, 96
    params = magm.make_params(THETA, MU, d)
    F = np.asarray(magm.sample_attributes(jax.random.PRNGKey(5), n, params.mu))
    tm = validate.theory_moments(F, np.asarray(params.thetas))
    plan = quilt.get_quilt_plan(F, params.thetas)
    ranks = np.asarray(plan.part.ranks)
    lam = np.asarray(magm.configs_from_attributes(jax.numpy.asarray(F)))
    Q = _dense_P(np.asarray(params.thetas))[np.ix_(lam, lam)]
    B = int(ranks.max())
    expect = np.zeros((B, B))
    for k, l in itertools.product(range(B), range(B)):
        expect[k, l] = Q[np.ix_(ranks == k + 1, ranks == l + 1)].sum()
    np.testing.assert_allclose(tm.block_mean, expect, rtol=1e-10)
    np.testing.assert_allclose(tm.block_mean.sum(), tm.mean_edges, rtol=1e-10)


def test_expected_isolated_matches_exact_product():
    """order-3 log-survival vs the exact prod(1 - Q) at small n."""
    d, n = 6, 64
    params = magm.make_params(THETA, MU, d)
    F = np.asarray(magm.sample_attributes(jax.random.PRNGKey(9), n, params.mu))
    lam = np.asarray(magm.configs_from_attributes(jax.numpy.asarray(F)))
    Q = _dense_P(np.asarray(params.thetas))[np.ix_(lam, lam)]
    log1m = np.log1p(-Q)
    # isolated: no out-edge (row i) and no in-edge (column i, j != i)
    exact = np.exp(log1m.sum(axis=1) + log1m.sum(axis=0) - np.diag(log1m)).sum()
    c = np.bincount(lam, minlength=1 << d).astype(np.float64)
    approx = validate.expected_isolated(c, np.asarray(params.thetas), order=3)
    near_exact = validate.expected_isolated(
        c, np.asarray(params.thetas), order=30
    )
    np.testing.assert_allclose(near_exact, exact, rtol=1e-10)
    assert abs(approx - exact) < 0.05 * max(exact, 1.0)


# ---------------------------------------------------------------------------
# the three backends at n = 2^12
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def suite():
    params = magm.make_params(THETA, MU, D)
    F = np.asarray(
        magm.sample_attributes(jax.random.PRNGKey(1), N, params.mu)
    )
    plan = quilt.get_quilt_plan(F, params.thetas)
    ranks = np.asarray(plan.part.ranks)
    bins = validate.degree_bin_edges(N)
    theory = validate.theory_moments(F, np.asarray(params.thetas))
    stats = {}
    for b in BACKENDS:
        sampler = MAGMSampler(SamplerConfig(params=params, F=F, backend=b))
        stats[b] = validate.collect(
            b,
            lambda s: np.asarray(sampler.sample(jax.random.PRNGKey(s)).edges),
            SEEDS,
            N,
            ranks,
            bins,
        )
    return {"params": params, "F": F, "stats": stats, "theory": theory}


@pytest.mark.parametrize(
    "a,b",
    list(itertools.combinations(BACKENDS, 2)),
    ids=["~".join(p) for p in itertools.combinations(BACKENDS, 2)],
)
def test_cross_backend_equivalence(suite, a, b):
    claims = validate.compare_backends(
        suite["stats"][a], suite["stats"][b], nsigma=3.0
    )
    assert not validate.failures(claims), validate.failures(claims)


@pytest.mark.parametrize("backend", BACKENDS)
def test_backend_matches_theory(suite, backend):
    claims = validate.compare_to_theory(
        suite["stats"][backend], suite["theory"], nsigma=3.0
    )
    assert not validate.failures(claims), validate.failures(claims)


@pytest.mark.parametrize("backend", ("auto", "balldrop"))
def test_per_cell_block_z(suite, backend):
    """Per-cell z within +-3 at n=2^12 (the exact-cell acceptance fix).

    The drawn-target law undercounted dense high-Q cells (duplicate
    proposals collide, the realized distinct count falls short of the
    Bernoulli target — the deficit the MAGFIT recovery suite surfaced
    against the exact_edges reference).  Exact-cell mode makes per-cell
    inclusion exactly Bernoulli(p), so EVERY (rank, rank) block mean must
    sit within 3 of its closed-form SE — elementwise, not just the
    aggregate claims of compare_to_theory.  The SE folds the Poisson-scale
    proxy (mean + 1) next to the binomial block variance, matching the
    honesty convention of validate._gap_claim at small seed counts.
    """
    st = suite["stats"][backend]
    tm = suite["theory"]
    k = st.blocks.shape[0]
    se = np.sqrt((tm.block_std**2 + np.abs(tm.block_mean) + 1.0) / k)
    z = (st.blocks.mean(axis=0) - tm.block_mean) / se
    assert float(np.abs(z).max()) <= 3.0, f"per-cell z:\n{z}"


def test_isolated_count_scale(suite):
    """Sanity anchor: the realized isolated-node counts sit at the
    predicted O(100) scale, not at 0 or O(n)."""
    iso = suite["theory"].isolated
    assert 10 < iso < N / 4
    for s in suite["stats"].values():
        assert np.all(s.isolated > 0)
        assert np.all(s.isolated < 5 * iso)


def test_balldrop_stream_matches_sample(suite):
    """sample_stream concatenation is bit-identical to sample at n=2^12."""
    sampler = MAGMSampler(
        SamplerConfig(
            params=suite["params"], F=suite["F"], backend="balldrop"
        )
    )
    key = jax.random.PRNGKey(77)
    edges = sampler.sample(key).edges
    chunks = list(sampler.sample_stream(key, chunk_edges=1 << 12))
    assert all(c.shape[0] == 1 << 12 for c in chunks[:-1])
    np.testing.assert_array_equal(edges, np.concatenate(chunks))


def test_balldrop_sample_batch_deduped(suite):
    sampler = MAGMSampler(
        SamplerConfig(
            params=suite["params"], F=suite["F"], backend="balldrop"
        )
    )
    batch = sampler.sample_batch(3, jax.random.PRNGKey(3))
    sizes = set()
    for gs in batch:
        flat = gs.edges[:, 0].astype(np.int64) * N + gs.edges[:, 1]
        assert np.unique(flat).size == gs.edges.shape[0]
        assert np.all(gs.edges >= 0) and np.all(gs.edges < N)
        sizes.add(gs.edges.shape[0])
    assert len(sizes) > 1  # per-sample |E| targets are independent draws


def test_balldrop_kpgm_honors_num_edges():
    sampler = KPGMSampler(
        SamplerConfig(params=kpgm.make_params(THETA, d=8), backend="balldrop")
    )
    gs = sampler.sample(jax.random.PRNGKey(0), num_edges=500)
    assert gs.num_edges == 500
    assert gs.stats.target_edges == 500
    flat = gs.edges[:, 0].astype(np.int64) * gs.n + gs.edges[:, 1]
    assert np.unique(flat).size == 500


def test_balldrop_unavailable_past_moment_cap():
    """d past kron.MOMENT_CAP has no c^T P c moments: the session must
    refuse backend='balldrop' at build time, not on the first sample."""
    d = kron.MOMENT_CAP.bit_length()  # 2^d > MOMENT_CAP
    params = magm.make_params(THETA, MU, d)
    F = np.asarray(
        magm.sample_attributes(jax.random.PRNGKey(2), 48, params.mu)
    )
    with pytest.raises(ValueError, match="balldrop"):
        MAGMSampler(SamplerConfig(params=params, F=F, backend="balldrop"))


def test_balldrop_mesh_parity(tmp_path):
    """balldrop on a 4-virtual-device mesh == no-mesh, bit-identical.

    Same subprocess idiom as test_api: device count is fixed at jax init,
    so the sharded half runs under XLA_FLAGS in a child process.
    """
    params = magm.make_params(THETA, MU, 8)
    F = np.asarray(
        magm.sample_attributes(jax.random.PRNGKey(3), 256, params.mu)
    )
    key = jax.random.PRNGKey(7)
    ref = MAGMSampler(
        SamplerConfig(params=params, F=F, backend="balldrop")
    ).sample(key)
    out_f = tmp_path / "F.npy"
    out_e = tmp_path / "edges4.npy"
    np.save(out_f, F)
    script = textwrap.dedent(
        f"""
        import jax
        import numpy as np
        from repro.api import MAGMSampler, SamplerConfig
        from repro.core import magm

        assert len(jax.devices()) == 4, jax.devices()
        theta = np.array([[0.35, 0.52], [0.52, 0.95]], dtype=np.float32)
        params = magm.make_params(theta, 0.5, 8)
        F = np.load({str(out_f)!r})
        sampler = MAGMSampler(SamplerConfig(
            params=params, F=F, backend="balldrop", mesh="auto"))
        assert sampler.mesh.devices.size == 4
        gs = sampler.sample(jax.random.PRNGKey(7))
        np.save({str(out_e)!r}, gs.edges)
        """
    )
    env = dict(os.environ)
    # the child runs on CPU devices: the parent may hold the chip
    env["JAX_PLATFORMS"] = "cpu"
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
    ).strip()
    proc = subprocess.run(
        [sys.executable, "-c", script],
        env=env,
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert proc.returncode == 0, proc.stderr
    np.testing.assert_array_equal(ref.edges, np.load(out_e))
