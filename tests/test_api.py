"""repro.api facade: session/shim/stream equivalence + lifecycle contracts.

The acceptance surface of the session redesign:

- for a fixed key, ``MAGMSampler.sample()``, the deprecated
  ``quilt_sample`` shim, and the concatenation of ``sample_stream()``
  chunks are bit-identical — on the no-mesh path in-process and on a
  1x4-virtual-device mesh via a subprocess;
- ``GraphSample.stats`` matches the old ``return_stats=True`` tuple
  field-for-field;
- the shims raise under ``-W error::DeprecationWarning`` while the session
  path stays warning-free;
- sessions own their plan: ``clear_plan_cache()`` never touches it, and
  repeated samples never re-partition.
"""

import os
import subprocess
import sys
import textwrap
import warnings

import jax
import numpy as np
import pytest

from repro.api import (
    GraphSample,
    KPGMSampler,
    KPGMStats,
    MAGMSampler,
    SamplerConfig,
)
from repro.core import dedup, kpgm, magm, quilt
from repro.dist import sharding
from repro.launch import mesh as mesh_mod

THETA = np.array([[0.35, 0.52], [0.52, 0.95]], dtype=np.float32)


def _attrs(n, d, mu=0.5, seed=3):
    params = magm.make_params(THETA, mu, d)
    F = np.asarray(
        magm.sample_attributes(jax.random.PRNGKey(seed), n, params.mu)
    )
    return params, F


def _shim_sample(key, params, F, **kw):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return quilt.quilt_sample(key, params, F, **kw)


# ---------------------------------------------------------------------------
# config
# ---------------------------------------------------------------------------


def test_config_validation():
    params, F = _attrs(32, 5)
    with pytest.raises(ValueError):
        SamplerConfig(params=params, backend="gpu")
    with pytest.raises(ValueError):
        SamplerConfig(params=params, oversample=0.5)
    with pytest.raises(ValueError):
        SamplerConfig(params=params, max_rounds=0)
    with pytest.raises(ValueError):
        SamplerConfig(params=params, dtype=np.float32)
    cfg = SamplerConfig(params=params, F=F)
    assert cfg.replace(backend="host").backend == "host"
    assert cfg.backend == "auto"  # original untouched (frozen value)


def test_attribute_source_resolution():
    params, F = _attrs(32, 5)
    with pytest.raises(ValueError):
        MAGMSampler(SamplerConfig(params=params))  # no F, no num_nodes
    with pytest.raises(ValueError):
        MAGMSampler(SamplerConfig(params=params, F=F[:, :3]))  # wrong d
    s = MAGMSampler(
        SamplerConfig(
            params=params, num_nodes=32, attribute_key=jax.random.PRNGKey(3)
        )
    )
    # same attribute_key => same matrix as sampling it by hand
    np.testing.assert_array_equal(s.F, F)
    with pytest.raises(TypeError):
        KPGMSampler(SamplerConfig(params=params))  # MAGM params
    with pytest.raises(TypeError):
        MAGMSampler(SamplerConfig(params=kpgm.make_params(THETA, 5)))


def test_dtype_contract():
    params, F = _attrs(48, 6)
    s = MAGMSampler(SamplerConfig(params=params, F=F, dtype=np.int32))
    gs = s.sample(jax.random.PRNGKey(0))
    assert gs.edges.dtype == np.int32
    ref = MAGMSampler(SamplerConfig(params=params, F=F)).sample(
        jax.random.PRNGKey(0)
    )
    np.testing.assert_array_equal(gs.edges.astype(np.int64), ref.edges)
    with pytest.raises(ValueError):
        MAGMSampler(
            SamplerConfig(params=params, num_nodes=300, dtype=np.int8)
        )


# ---------------------------------------------------------------------------
# shim == session == stream (the acceptance bit-identity)
# ---------------------------------------------------------------------------


def test_shim_session_stream_bit_identical_no_mesh():
    params, F = _attrs(192, 8)
    key = jax.random.PRNGKey(7)
    e_shim, st_shim = _shim_sample(key, params, F, return_stats=True)
    sampler = MAGMSampler(SamplerConfig(params=params, F=F))
    gs = sampler.sample(key)
    np.testing.assert_array_equal(e_shim, gs.edges)
    assert st_shim == gs.stats  # field-for-field (same NamedTuple type)
    assert gs.n == 192 and gs.key is key
    chunks = list(sampler.sample_stream(key, chunk_edges=64))
    assert all(c.shape == (64, 2) for c in chunks[:-1])
    assert chunks[-1].shape[0] <= 64
    np.testing.assert_array_equal(np.concatenate(chunks), gs.edges)


def test_shim_session_stream_bit_identical_host_backend():
    params, F = _attrs(96, 6)
    key = jax.random.PRNGKey(13)
    e_shim, st_shim = _shim_sample(
        key, params, F, backend="host", return_stats=True
    )
    sampler = MAGMSampler(SamplerConfig(params=params, F=F, backend="host"))
    gs = sampler.sample(key)
    np.testing.assert_array_equal(e_shim, gs.edges)
    assert st_shim == gs.stats
    chunks = list(sampler.sample_stream(key, chunk_edges=64))
    np.testing.assert_array_equal(np.concatenate(chunks), gs.edges)


def test_shim_session_stream_bit_identical_one_device_mesh():
    params, F = _attrs(192, 8)
    key = jax.random.PRNGKey(7)
    mesh = mesh_mod.make_sampler_mesh()
    e_shim = _shim_sample(key, params, F, mesh=mesh)
    sampler = MAGMSampler(SamplerConfig(params=params, F=F, mesh=mesh))
    gs = sampler.sample(key)
    np.testing.assert_array_equal(e_shim, gs.edges)
    chunks = list(sampler.sample_stream(key, chunk_edges=100))
    np.testing.assert_array_equal(np.concatenate(chunks), gs.edges)
    # and identical to the no-mesh session (device-count invariance)
    ref = MAGMSampler(SamplerConfig(params=params, F=F)).sample(key)
    np.testing.assert_array_equal(ref.edges, gs.edges)


def test_four_virtual_devices_session_matches(tmp_path):
    """shim == session == stream-concat on a 1x4 virtual CPU mesh.

    Device count is baked in at jax init, so the 4-device half runs in a
    subprocess (XLA_FLAGS); it writes the session edges and the streamed
    concatenation, both of which must equal the local no-mesh reference.
    """
    params, F = _attrs(192, 8)
    key = jax.random.PRNGKey(7)
    e_ref = MAGMSampler(SamplerConfig(params=params, F=F)).sample(key).edges

    out_s = tmp_path / "sess4.npy"
    out_c = tmp_path / "chunks4.npy"
    script = textwrap.dedent(
        f"""
        import jax
        import numpy as np
        from repro.api import MAGMSampler, SamplerConfig
        from repro.core import magm

        assert len(jax.devices()) == 4, jax.devices()
        theta = np.array([[0.35, 0.52], [0.52, 0.95]], dtype=np.float32)
        params = magm.make_params(theta, 0.5, 8)
        config = SamplerConfig(
            params=params, num_nodes=192,
            attribute_key=jax.random.PRNGKey(3), mesh="auto",
        )
        sampler = MAGMSampler(config)
        assert sampler.mesh.devices.size == 4
        key = jax.random.PRNGKey(7)
        gs = sampler.sample(key)
        chunks = list(sampler.sample_stream(key, chunk_edges=64))
        assert all(c.shape == (64, 2) for c in chunks[:-1])
        np.save({str(out_s)!r}, gs.edges)
        np.save({str(out_c)!r}, np.concatenate(chunks))
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
    np.testing.assert_array_equal(e_ref, np.load(out_s))
    np.testing.assert_array_equal(e_ref, np.load(out_c))


def test_split_session_matches_fast_shim():
    params, F = _attrs(128, 7, mu=0.7, seed=4)
    key = jax.random.PRNGKey(11)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        e_shim, st_shim = quilt.quilt_sample_fast(
            key, params, F, return_stats=True
        )
    sampler = MAGMSampler(SamplerConfig(params=params, F=F, split=True))
    gs = sampler.sample(key)
    np.testing.assert_array_equal(e_shim, gs.edges)
    assert st_shim == gs.stats
    assert gs.stats.bprime == sampler.split_plan.bprime
    chunks = list(sampler.sample_stream(key, chunk_edges=50))
    np.testing.assert_array_equal(np.concatenate(chunks), gs.edges)


def test_seed_alias_pins_old_stream():
    params, F = _attrs(96, 6, mu=0.8, seed=2)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        e_new = quilt.quilt_sample_fast(jax.random.PRNGKey(5), params, F)
        e_old = quilt.quilt_sample_fast(
            jax.random.PRNGKey(5), params, F, seed=0
        )
    # both are valid draws; the alias reproduces the legacy default_rng(0)
    # stream, the keyless path derives the generator from the key
    for e in (e_new, e_old):
        flat = e[:, 0] * 96 + e[:, 1]
        assert np.unique(flat).size == flat.size


# ---------------------------------------------------------------------------
# deprecation surface
# ---------------------------------------------------------------------------


def test_shims_warn_and_raise_under_error_filter():
    params, F = _attrs(48, 5)
    kp = kpgm.make_params(THETA, 5)
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        with pytest.raises(DeprecationWarning):
            quilt.quilt_sample(jax.random.PRNGKey(0), params, F)
        with pytest.raises(DeprecationWarning):
            quilt.quilt_sample_fast(jax.random.PRNGKey(0), params, F)
        with pytest.raises(DeprecationWarning):
            kpgm.kpgm_sample(jax.random.PRNGKey(0), kp)
    with warnings.catch_warnings():
        # the seed= alias carries its own warning on top of the shim one
        warnings.simplefilter("ignore", DeprecationWarning)
        warnings.filterwarnings(
            "error",
            message=r"quilt_sample_fast\(seed=",
            category=DeprecationWarning,
        )
        with pytest.raises(DeprecationWarning):
            quilt.quilt_sample_fast(jax.random.PRNGKey(0), params, F, seed=1)


def test_session_path_is_warning_free():
    params, F = _attrs(48, 5)
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        s = MAGMSampler(SamplerConfig(params=params, F=F))
        s.sample(jax.random.PRNGKey(0))
        list(s.sample_stream(jax.random.PRNGKey(1), chunk_edges=32))
        s.sample_batch(2, jax.random.PRNGKey(2))
        k = KPGMSampler(SamplerConfig(params=kpgm.make_params(THETA, 5)))
        k.sample(jax.random.PRNGKey(3))


# ---------------------------------------------------------------------------
# session lifecycle: owned plan, cache independence, key stream
# ---------------------------------------------------------------------------


def test_session_owns_plan_and_survives_cache_clear():
    params, F = _attrs(128, 7, seed=11)
    quilt.clear_plan_cache()
    sampler = MAGMSampler(SamplerConfig(params=params, F=F))
    ref = sampler.sample(jax.random.PRNGKey(1)).edges
    before = dict(quilt.PLAN_STATS)
    quilt.clear_plan_cache()  # must NOT touch the session's owned plan
    again = sampler.sample(jax.random.PRNGKey(1)).edges
    np.testing.assert_array_equal(ref, again)
    assert quilt.PLAN_STATS == before  # no rebuild, no cache hit needed
    # the shim path, by contrast, rebuilds after a clear
    _shim_sample(jax.random.PRNGKey(1), params, F)
    assert (
        quilt.PLAN_STATS["partition_builds"] == before["partition_builds"] + 1
    )


def test_session_builds_once_not_per_sample():
    params, F = _attrs(96, 6, seed=8)
    before = quilt.PLAN_STATS["partition_builds"]
    sampler = MAGMSampler(SamplerConfig(params=params, F=F))
    assert quilt.PLAN_STATS["partition_builds"] == before + 1
    for s in range(3):
        sampler.sample(jax.random.PRNGKey(s))
    assert quilt.PLAN_STATS["partition_builds"] == before + 1


def test_session_key_stream_advances():
    params, F = _attrs(64, 6, seed=5)
    sampler = MAGMSampler(
        SamplerConfig(params=params, F=F), key=jax.random.PRNGKey(42)
    )
    a = sampler.sample()
    b = sampler.sample()
    assert not np.array_equal(np.asarray(a.key), np.asarray(b.key))
    # provenance: replaying a GraphSample's key reproduces it exactly
    np.testing.assert_array_equal(sampler.sample(a.key).edges, a.edges)


# ---------------------------------------------------------------------------
# batching
# ---------------------------------------------------------------------------


def test_magm_sample_batch_fused_and_valid():
    params, F = _attrs(128, 7, seed=6)
    sampler = MAGMSampler(SamplerConfig(params=params, F=F))
    for k in quilt.DISPATCH_COUNTERS:
        quilt.DISPATCH_COUNTERS[k] = 0
    batch = sampler.sample_batch(4, jax.random.PRNGKey(3))
    assert len(batch) == 4
    total = sum(quilt.DISPATCH_COUNTERS.values())
    assert total <= sampler.config.max_rounds  # fused, not 4x rounds
    singles = [
        sampler.sample(jax.random.PRNGKey(100 + s)).num_edges
        for s in range(4)
    ]
    for gs in batch:
        flat = gs.edges[:, 0] * 128 + gs.edges[:, 1]
        assert np.unique(flat).size == flat.size
        assert gs.edges.min(initial=0) >= 0
        assert gs.edges.max(initial=0) < 128
        assert gs.stats.kept_edges == gs.num_edges
        assert gs.stats.num_kpgm_draws == sampler.plan.num_graphs
    # batched draws live on the same scale as independent singles
    assert abs(
        np.mean([g.num_edges for g in batch]) - np.mean(singles)
    ) < 6 * (np.std(singles) + np.sqrt(np.mean(singles)) + 1)


def test_magm_sample_batch_mesh_matches_no_mesh():
    params, F = _attrs(96, 7, seed=9)
    config = SamplerConfig(params=params, F=F)
    key = jax.random.PRNGKey(4)
    a = MAGMSampler(config).sample_batch(3, key)
    b = MAGMSampler(config.replace(mesh="auto")).sample_batch(3, key)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x.edges, y.edges)


def test_magm_sample_batch_host_fallback():
    params, F = _attrs(64, 6, seed=7)
    sampler = MAGMSampler(SamplerConfig(params=params, F=F, backend="host"))
    batch = sampler.sample_batch(2, jax.random.PRNGKey(1))
    assert len(batch) == 2
    for gs in batch:
        flat = gs.edges[:, 0] * 64 + gs.edges[:, 1]
        assert np.unique(flat).size == flat.size


# ---------------------------------------------------------------------------
# KPGM parity
# ---------------------------------------------------------------------------


def test_kpgm_shim_session_bit_identical():
    kp = kpgm.make_params(THETA, 8)
    key = jax.random.PRNGKey(0)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        e_shim = kpgm.kpgm_sample(key, kp)
    sampler = KPGMSampler(SamplerConfig(params=kp))
    gs = sampler.sample(key)
    np.testing.assert_array_equal(e_shim, gs.edges)
    assert isinstance(gs.stats, KPGMStats)
    assert gs.stats.sampled_edges == gs.num_edges
    assert gs.n == 256


def test_kpgm_mesh_and_stream_parity():
    kp = kpgm.make_params(THETA, 8)
    key = jax.random.PRNGKey(5)
    ref = KPGMSampler(SamplerConfig(params=kp)).sample(key)
    meshed = KPGMSampler(SamplerConfig(params=kp, mesh="auto"))
    gs = meshed.sample(key)
    np.testing.assert_array_equal(ref.edges, gs.edges)
    chunks = list(meshed.sample_stream(key, chunk_edges=128))
    np.testing.assert_array_equal(np.concatenate(chunks), ref.edges)


def test_kpgm_num_edges_and_host_backend():
    kp = kpgm.make_params(THETA, 9)
    sampler = KPGMSampler(SamplerConfig(params=kp))
    gs = sampler.sample(jax.random.PRNGKey(2), num_edges=777)
    assert gs.num_edges == 777 and gs.stats.target_edges == 777
    host = KPGMSampler(SamplerConfig(params=kp, backend="host"))
    hs = host.sample(jax.random.PRNGKey(2))
    assert host.plan is None and hs.stats is None
    flat = hs.edges[:, 0] * 512 + hs.edges[:, 1]
    assert np.unique(flat).size == flat.size
    # scale agreement between the identity-quilt path and the host loop
    a = [
        sampler.sample(jax.random.PRNGKey(10 + s)).num_edges
        for s in range(4)
    ]
    b = [host.sample(jax.random.PRNGKey(20 + s)).num_edges for s in range(4)]
    assert abs(np.mean(a) - np.mean(b)) < 6 * (
        np.std(b) + np.sqrt(np.mean(b)) + 1
    )


def test_empty_attribute_source_session():
    """An empty F builds a working (empty-emitting) session, like the shim."""
    params, _ = _attrs(8, 4)
    for split in (False, True):
        s = MAGMSampler(
            SamplerConfig(params=params, F=np.zeros((0, 4), np.int8), split=split)
        )
        gs = s.sample(jax.random.PRNGKey(0))
        assert gs.edges.shape == (0, 2) and gs.n == 0
        assert list(s.sample_stream(jax.random.PRNGKey(0))) == []
        assert all(
            b.num_edges == 0 for b in s.sample_batch(2, jax.random.PRNGKey(1))
        )


def test_kpgm_engine_host_fallback_reports_no_fake_target(monkeypatch):
    """When the engine's auto decision falls back to its internal host path,
    the unused Normal target draw must not surface as stats.target_edges."""
    kp = kpgm.make_params(THETA, 8)
    sampler = KPGMSampler(SamplerConfig(params=kp))
    monkeypatch.setattr(kpgm, "DEVICE_MAX_CANDIDATES", 100)
    gs = sampler.sample(jax.random.PRNGKey(1))
    assert gs.stats is None  # host path drew its own X; no fabricated target
    flat = gs.edges[:, 0] * 256 + gs.edges[:, 1]
    assert np.unique(flat).size == flat.size


def test_auto_backend_host_branch_is_counted_and_warned(monkeypatch):
    """Past the device budget, backend="auto" may still finish on the host,
    but never silently: the exact-cell downgrade and the host loop each
    bump their DISPATCH_COUNTERS entry and raise a RuntimeWarning."""
    params, F = _attrs(64, 6, seed=1)
    sampler = MAGMSampler(SamplerConfig(params=params, F=F))
    monkeypatch.setattr(kpgm, "DEVICE_MAX_CANDIDATES", 8)
    before = dict(quilt.DISPATCH_COUNTERS)
    with pytest.warns(RuntimeWarning) as rec:
        gs = sampler.sample(jax.random.PRNGKey(4))
    msgs = [str(w.message) for w in rec]
    assert any("exact-cell round over the device budget" in m for m in msgs)
    assert any("sampling on the host" in m for m in msgs)
    c = quilt.DISPATCH_COUNTERS
    assert c["exact_fallbacks"] == before["exact_fallbacks"] + 1
    assert c["host_fallbacks"] == before["host_fallbacks"] + 1
    assert c["device_rounds"] == before["device_rounds"]
    assert gs.num_edges > 0


def test_host_backend_honors_rejection_knobs():
    """SamplerConfig.max_rounds/oversample reach the host reference path."""
    params, F = _attrs(64, 6, seed=1)
    key = jax.random.PRNGKey(9)
    a = MAGMSampler(
        SamplerConfig(params=params, F=F, backend="host", oversample=1.05)
    ).sample(key)
    b = MAGMSampler(
        SamplerConfig(params=params, F=F, backend="host", oversample=2.0)
    ).sample(key)
    # different oversample => different candidate batch shapes => different
    # streams (would be identical if the knob were silently dropped)
    assert not np.array_equal(a.edges, b.edges)


def test_kpgm_num_edges_honored_past_device_budget(monkeypatch):
    """An explicit num_edges too large for the device budget must still be
    honored (host loop fallback), not silently replaced by an X-draw."""
    kp = kpgm.make_params(THETA, 8)
    sampler = KPGMSampler(SamplerConfig(params=kp))
    monkeypatch.setattr(kpgm, "DEVICE_MAX_CANDIDATES", 64)
    gs = sampler.sample(jax.random.PRNGKey(1), num_edges=300)
    assert gs.num_edges == 300
    chunks = list(
        sampler.sample_stream(
            jax.random.PRNGKey(1), num_edges=300, chunk_edges=64
        )
    )
    np.testing.assert_array_equal(np.concatenate(chunks), gs.edges)


def test_kpgm_explicit_device_backend_over_cap_raises():
    from repro.api import session as session_mod

    kp = kpgm.make_params(THETA, 21)  # n = 2M > KPGM_PLAN_MAX_NODES
    assert kp.num_nodes > session_mod.KPGM_PLAN_MAX_NODES
    with pytest.raises(ValueError):
        KPGMSampler(SamplerConfig(params=kp, backend="device"))


def test_fused_batch_members_have_no_provenance_key():
    params, F = _attrs(96, 7, seed=2)
    sampler = MAGMSampler(SamplerConfig(params=params, F=F))
    fused = sampler.sample_batch(2, jax.random.PRNGKey(3))
    assert all(gs.key is None for gs in fused)
    # the per-sample fallback loop DOES record reproducing keys
    host = MAGMSampler(SamplerConfig(params=params, F=F, backend="host"))
    looped = host.sample_batch(2, jax.random.PRNGKey(3))
    for gs in looped:
        np.testing.assert_array_equal(host.sample(gs.key).edges, gs.edges)


def test_kpgm_identity_plan_cached_across_sessions():
    """Repeated KPGM sessions (and thus repeated shim calls) reuse the
    content-cached identity plan instead of rebuilding the O(2^d)
    partition every time."""
    quilt.clear_plan_cache()
    kp = kpgm.make_params(THETA, 8)
    KPGMSampler(SamplerConfig(params=kp))
    builds = quilt.PLAN_STATS["partition_builds"]
    KPGMSampler(SamplerConfig(params=kp))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        kpgm.kpgm_sample(jax.random.PRNGKey(0), kp)
    assert quilt.PLAN_STATS["partition_builds"] == builds


def test_kpgm_sample_batch_shared_rounds():
    kp = kpgm.make_params(THETA, 7)
    sampler = KPGMSampler(SamplerConfig(params=kp))
    for k in quilt.DISPATCH_COUNTERS:
        quilt.DISPATCH_COUNTERS[k] = 0
    batch = sampler.sample_batch(5, jax.random.PRNGKey(8))
    assert len(batch) == 5
    assert sum(quilt.DISPATCH_COUNTERS.values()) <= sampler.config.max_rounds
    for gs in batch:
        flat = gs.edges[:, 0] * 128 + gs.edges[:, 1]
        assert np.unique(flat).size == flat.size


# ---------------------------------------------------------------------------
# chunked emission hook + layout helper units
# ---------------------------------------------------------------------------


def test_rechunk_edges_shapes_and_content():
    pieces = [np.arange(10).reshape(5, 2), np.arange(6).reshape(3, 2)]
    chunks = list(dedup.rechunk_edges(pieces, 3))
    assert [c.shape[0] for c in chunks] == [3, 3, 2]
    np.testing.assert_array_equal(
        np.concatenate(chunks), np.concatenate(pieces)
    )
    with pytest.raises(ValueError):
        list(dedup.rechunk_edges(pieces, 0))


def test_iter_edge_chunks_matches_dense_gather():
    rng = np.random.default_rng(0)
    n = 5000
    src = rng.integers(0, 100, n)
    dst = rng.integers(0, 100, n)
    keep = rng.random(n) < 0.3
    tail = [np.array([[7, 8], [9, 10]])]
    chunks = list(dedup.iter_edge_chunks(src, dst, keep, 128, tail=tail))
    dense = np.concatenate(
        [np.stack([src[keep], dst[keep]], axis=1)] + tail
    )
    assert all(c.shape[0] == 128 for c in chunks[:-1])
    np.testing.assert_array_equal(np.concatenate(chunks), dense)


def test_graph_layout_helper():
    assert sharding.graph_layout(None, 7) == ((), 1, 7)
    mesh = mesh_mod.make_sampler_mesh()
    lay = sharding.graph_layout(mesh, 7)
    assert lay.nshards == len(jax.devices())
    assert lay.padded % lay.nshards == 0 and lay.padded >= 7


# ---------------------------------------------------------------------------
# example smoke: SamplerConfig end-to-end on 4 virtual CPU devices
# ---------------------------------------------------------------------------


def test_distributed_example_smoke_four_devices():
    here = os.path.dirname(__file__)
    example = os.path.abspath(
        os.path.join(here, "..", "examples", "distributed_sampling.py")
    )
    env = dict(os.environ)
    # the child runs on CPU devices: the parent may hold the chip
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.abspath(os.path.join(here, "..", "src"))
    env.pop("XLA_FLAGS", None)  # the example forces 4 virtual devices itself
    proc = subprocess.run(
        [sys.executable, example],
        env=env,
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert proc.returncode == 0, proc.stderr
    assert "4-device edge set: exact" in proc.stdout
    assert "concat exact" in proc.stdout
