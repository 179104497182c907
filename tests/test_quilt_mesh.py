"""Mesh-sharded quilting: device-count invariance + the on-device top-up.

The B^2 block-pair candidate streams are iid (Theorem 4), so quilt_sample
shards them along the ``graphs`` logical axis with per-graph PRNG key
folding.  The contract under test:

- a mesh of ANY device count returns the exact edge set (indeed the exact
  array) of the single-device path for the same key — 1-device mesh
  in-process, a 1x4 virtual-device CPU mesh via a subprocess (the host
  device count is fixed at jax init, so the 4-device half runs under
  ``XLA_FLAGS=--xla_force_host_platform_device_count=4``);
- the duplicate-collision shortfall is finished by FIXED-SHAPE on-device
  top-up rounds: O(max_rounds) dispatches total and zero host-side dedup
  calls on the default backend.
"""

import os
import subprocess
import sys
import textwrap

import jax
import numpy as np

from repro.core import magm, quilt
from repro.dist import sharding
from repro.launch import mesh as mesh_mod

THETA = np.array([[0.35, 0.52], [0.52, 0.95]], dtype=np.float32)


def _attrs(n, d, mu=0.5, seed=3):
    params = magm.make_params(THETA, mu, d)
    F = np.asarray(
        magm.sample_attributes(jax.random.PRNGKey(seed), n, params.mu)
    )
    return params, F


def test_one_device_mesh_matches_no_mesh_exactly():
    params, F = _attrs(192, 8)
    e_ref = quilt.quilt_sample(jax.random.PRNGKey(7), params, F)
    e_mesh = quilt.quilt_sample(
        jax.random.PRNGKey(7), params, F, mesh=mesh_mod.make_sampler_mesh()
    )
    np.testing.assert_array_equal(e_ref, e_mesh)


def test_data_axis_mesh_is_also_usable():
    """A generic 'data' mesh (no dedicated 'graphs' axis) carries the role."""
    params, F = _attrs(96, 7)
    e_ref = quilt.quilt_sample(jax.random.PRNGKey(2), params, F)
    e_mesh = quilt.quilt_sample(
        jax.random.PRNGKey(2), params, F, mesh=mesh_mod.make_host_mesh()
    )
    np.testing.assert_array_equal(e_ref, e_mesh)


def test_graph_shard_axes_resolution():
    assert sharding.graph_shard_axes(None) == ((), 1)
    m = mesh_mod.make_sampler_mesh()
    axes, n = sharding.graph_shard_axes(m)
    assert axes == ("graphs",) and n == len(jax.devices())
    axes, n = sharding.graph_shard_axes(mesh_mod.make_host_mesh())
    assert axes == ("data",)
    # a model-only mesh has no graph-parallel axis: unsharded fallback
    model_mesh = jax.make_mesh((1,), ("model",))
    assert sharding.graph_shard_axes(model_mesh) == ((), 1)


def test_four_virtual_devices_match_single_device(tmp_path):
    """1x4 CPU mesh == single-device edges, exactly, for the same key.

    The device count is baked in at jax init, so the 4-device half runs in
    a subprocess with XLA_FLAGS forcing 4 virtual host devices; the PRNG is
    deterministic, so both halves rebuild identical (params, F).
    """
    params, F = _attrs(192, 8)
    e_ref = quilt.quilt_sample(jax.random.PRNGKey(7), params, F)

    out = tmp_path / "edges4.npy"
    script = textwrap.dedent(
        f"""
        import jax
        import numpy as np
        from repro.core import magm, quilt
        from repro.launch import mesh as mesh_mod

        assert len(jax.devices()) == 4, jax.devices()
        theta = np.array([[0.35, 0.52], [0.52, 0.95]], dtype=np.float32)
        params = magm.make_params(theta, 0.5, 8)
        F = np.asarray(
            magm.sample_attributes(jax.random.PRNGKey(3), 192, params.mu)
        )
        edges = quilt.quilt_sample(
            jax.random.PRNGKey(7), params, F, mesh=mesh_mod.make_sampler_mesh()
        )
        assert quilt.DISPATCH_COUNTERS["host_topup_rounds"] == 0
        np.save({str(out)!r}, edges)
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
    e4 = np.load(out)
    np.testing.assert_array_equal(e_ref, e4)


def test_topup_round_stays_on_device():
    """A collision-heavy config NEEDS top-ups; they must all be device
    rounds: dispatch count O(max_rounds), zero host dedup calls."""
    # near-uniform quadrant probabilities over only 64 cells with ~55-edge
    # targets: the first round's candidates collide heavily, so a shortfall
    # is essentially certain
    params = magm.make_params(
        np.array([[0.95, 0.95], [0.95, 0.95]], np.float32), 0.5, 3
    )
    F = np.asarray(
        magm.sample_attributes(jax.random.PRNGKey(1), 16, params.mu)
    )
    max_rounds = 8
    for k in quilt.DISPATCH_COUNTERS:
        quilt.DISPATCH_COUNTERS[k] = 0
    edges = quilt.quilt_sample(
        jax.random.PRNGKey(5), params, F, max_rounds=max_rounds,
        exact_cells=False,
    )
    c = quilt.DISPATCH_COUNTERS
    assert c["host_topup_rounds"] == 0, c
    assert c["device_topup_rounds"] >= 1, c
    assert c["device_rounds"] + c["device_topup_rounds"] <= max_rounds, c
    flat = edges[:, 0] * 16 + edges[:, 1]
    assert np.unique(flat).size == flat.size


def test_topup_matches_host_backend_distribution():
    """Edges produced across device top-up rounds are still unique, valid
    node pairs with a plausible count (the host backend's scale)."""
    params, F = _attrs(64, 6, seed=9)
    counts = [
        quilt.quilt_sample(jax.random.PRNGKey(100 + s), params, F).shape[0]
        for s in range(4)
    ]
    host = [
        quilt.quilt_sample(
            jax.random.PRNGKey(200 + s), params, F, backend="host"
        ).shape[0]
        for s in range(4)
    ]
    assert abs(np.mean(counts) - np.mean(host)) < 6 * (
        np.std(host) + np.sqrt(np.mean(host)) + 1
    )


def test_topup_budget_guard_falls_back_to_host(monkeypatch):
    """When the cumulative stream would outgrow the device budget, the
    top-up loop stops and the host fallback finishes — with the SAME edges
    on any mesh (the guard is layout-invariant)."""
    from repro.core import kpgm

    params = magm.make_params(
        np.array([[0.95, 0.95], [0.95, 0.95]], np.float32), 0.5, 3
    )
    F = np.asarray(
        magm.sample_attributes(jax.random.PRNGKey(1), 16, params.mu)
    )
    e_full = quilt.quilt_sample(
        jax.random.PRNGKey(5), params, F, exact_cells=False
    )
    # budget admits round 0 (G * ask0) but nothing more: top-ups go host-side
    plan = quilt.get_quilt_plan(F, params.thetas)
    cap = plan.num_graphs * 128
    monkeypatch.setattr(kpgm, "DEVICE_MAX_CANDIDATES", cap)
    for k in quilt.DISPATCH_COUNTERS:
        quilt.DISPATCH_COUNTERS[k] = 0
    e_capped = quilt.quilt_sample(
        jax.random.PRNGKey(5), params, F, exact_cells=False
    )
    c = quilt.DISPATCH_COUNTERS
    assert c["host_topup_rounds"] >= 1, c
    flat = e_capped[:, 0] * 16 + e_capped[:, 1]
    assert np.unique(flat).size == flat.size
    # capped mesh run must equal the capped no-mesh run exactly
    e_capped_mesh = quilt.quilt_sample(
        jax.random.PRNGKey(5), params, F, mesh=mesh_mod.make_sampler_mesh(),
        exact_cells=False,
    )
    np.testing.assert_array_equal(e_capped, e_capped_mesh)
    # and the un-capped result is a superset scale sanity check
    assert abs(e_capped.shape[0] - e_full.shape[0]) <= max(
        8, e_full.shape[0] // 4
    )


def test_quilt_sample_fast_accepts_mesh():
    params, F = _attrs(128, 7, mu=0.7, seed=4)
    e_ref = quilt.quilt_sample_fast(jax.random.PRNGKey(11), params, F)
    e_mesh = quilt.quilt_sample_fast(
        jax.random.PRNGKey(11), params, F, mesh=mesh_mod.make_sampler_mesh()
    )
    np.testing.assert_array_equal(e_ref, e_mesh)
