"""Resilience of the sampling runtime: kill-mid-stream resume (bit-identical
splice), mesh degradation on device loss (bit-identical re-run over the
survivors), and the observable host-fallback degradation counter.

The correctness backbone for all of it is Theorem-4 layout invariance:
per-graph ``fold_in`` keys + shared slot counts mean no candidate stream
ever depended on device layout, so a smaller mesh — or a from-scratch
replay — regenerates exactly the same edges.
"""

import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.api import KPGMSampler, MAGMSampler, SamplerConfig
from repro.core import balldrop, magm, quilt
from repro.dist import chaos, checkpoint as ckpt
from repro.launch import mesh as mesh_mod

THETA = np.array([[0.35, 0.52], [0.52, 0.95]], dtype=np.float32)


def _magm_config(n=128, d=6, **kw):
    return SamplerConfig(
        params=magm.make_params(THETA, 0.5, d), num_nodes=n, **kw
    )


def _stream_killed_at(sampler, key, chunk_edges, directory, visit):
    """Run a checkpointed stream under a FaultSchedule that kills the
    stream.chunk site at ``visit``; returns the chunks delivered."""
    sched = chaos.FaultSchedule([chaos.FaultSpec("stream.chunk", (visit,))])
    got = []
    with chaos.active(sched):
        with pytest.raises(chaos.InjectedFault):
            for chunk in sampler.sample_stream(
                key, chunk_edges=chunk_edges, checkpoint_dir=directory
            ):
                got.append(chunk)
    assert len(got) == visit  # fault at visit k => exactly k delivered
    return got


# -- kill-mid-stream resume -------------------------------------------------


def test_magm_kill_mid_stream_resume_bit_identical(tmp_path):
    cfg = _magm_config()
    key = jax.random.PRNGKey(7)
    full = np.concatenate(
        list(MAGMSampler(cfg).sample_stream(key, chunk_edges=64))
    )
    assert full.shape[0] > 3 * 64  # the kill point is mid-stream

    d = str(tmp_path)
    got = _stream_killed_at(MAGMSampler(cfg), key, 64, d, visit=3)
    # a FRESH session (no memory of the killed one) resumes from disk
    rest = list(MAGMSampler(cfg).resume_stream(d))
    assert rest  # there was more stream to emit
    np.testing.assert_array_equal(np.concatenate(got + rest), full)


def test_resume_survives_repeated_kills(tmp_path):
    """Fault -> resume -> fault again -> resume: the cursor advances
    through every incident and the final splice is still exact."""
    cfg = _magm_config()
    key = jax.random.PRNGKey(3)
    full = np.concatenate(
        list(MAGMSampler(cfg).sample_stream(key, chunk_edges=32))
    )
    d = str(tmp_path)
    got = _stream_killed_at(MAGMSampler(cfg), key, 32, d, visit=2)
    sched = chaos.FaultSchedule([chaos.FaultSpec("stream.chunk", (4,))])
    with chaos.active(sched):
        with pytest.raises(chaos.InjectedFault):
            for chunk in MAGMSampler(cfg).resume_stream(d):
                got.append(chunk)
    got += list(MAGMSampler(cfg).resume_stream(d))
    np.testing.assert_array_equal(np.concatenate(got), full)


def test_resume_finished_stream_yields_nothing(tmp_path):
    cfg = _magm_config()
    d = str(tmp_path)
    chunks = list(
        MAGMSampler(cfg).sample_stream(
            jax.random.PRNGKey(1), chunk_edges=64, checkpoint_dir=d
        )
    )
    assert chunks
    assert list(MAGMSampler(cfg).resume_stream(d)) == []


def test_resume_rejects_wrong_config(tmp_path):
    d = str(tmp_path)
    _stream_killed_at(
        MAGMSampler(_magm_config()), jax.random.PRNGKey(1), 64, d, visit=1
    )
    other = MAGMSampler(_magm_config(max_rounds=3))
    with pytest.raises(ValueError, match="different sampler config"):
        list(other.resume_stream(d))
    with pytest.raises(ValueError, match="no stream checkpoint"):
        list(
            MAGMSampler(_magm_config()).resume_stream(str(tmp_path / "nope"))
        )


def test_resume_is_mesh_independent(tmp_path):
    """The headline degradation property: a stream checkpointed with a
    mesh resumes bit-identically WITHOUT one (config digest excludes
    layout)."""
    key = jax.random.PRNGKey(5)
    full = np.concatenate(
        list(MAGMSampler(_magm_config()).sample_stream(key, chunk_edges=64))
    )
    d = str(tmp_path)
    got = _stream_killed_at(
        MAGMSampler(_magm_config(mesh="auto")), key, 64, d, visit=2
    )
    rest = list(MAGMSampler(_magm_config(mesh=None)).resume_stream(d))
    np.testing.assert_array_equal(np.concatenate(got + rest), full)


def test_kpgm_kill_mid_stream_resume_with_num_edges(tmp_path):
    from repro.core import kpgm

    cfg = SamplerConfig(params=kpgm.make_params(THETA, d=7))
    key = jax.random.PRNGKey(2)
    full = np.concatenate(
        list(
            KPGMSampler(cfg).sample_stream(key, chunk_edges=32, num_edges=150)
        )
    )
    d = str(tmp_path)
    sched = chaos.FaultSchedule([chaos.FaultSpec("stream.chunk", (2,))])
    got = []
    with chaos.active(sched):
        with pytest.raises(chaos.InjectedFault):
            for chunk in KPGMSampler(cfg).sample_stream(
                key, chunk_edges=32, num_edges=150, checkpoint_dir=d
            ):
                got.append(chunk)
    # num_edges rides in the checkpoint: resume_stream takes only the dir
    rest = list(KPGMSampler(cfg).resume_stream(d))
    np.testing.assert_array_equal(np.concatenate(got + rest), full)


def test_checkpoint_cursor_tracks_delivery(tmp_path):
    """Checkpoint N is written only after chunk N-1's yield returned: a
    fault at visit k leaves the cursor at exactly k."""
    d = str(tmp_path)
    _stream_killed_at(
        MAGMSampler(_magm_config()), jax.random.PRNGKey(7), 64, d, visit=3
    )
    from repro.api import stream as stream_mod

    state = stream_mod.load_state(d, ckpt.latest_step(d), jax.random.PRNGKey(0))
    assert int(state["chunks_emitted"]) == 3
    assert int(state["edges_emitted"]) == 3 * 64
    assert int(state["done"]) == 0
    assert int(state["chunk_edges"]) == 64


# -- mesh degradation on device loss ----------------------------------------


def test_degrade_sampler_mesh_survivors():
    mesh = mesh_mod.make_sampler_mesh(1)
    with pytest.raises(ValueError, match="no survivors"):
        mesh_mod.degrade_sampler_mesh(mesh, 0)
    with pytest.raises(ValueError, match="out of range"):
        mesh_mod.degrade_sampler_mesh(mesh, 5)


def test_device_loss_without_mesh_is_fatal():
    params = magm.make_params(THETA, 0.5, 6)
    F = np.asarray(
        magm.sample_attributes(jax.random.PRNGKey(3), 128, params.mu)
    )
    plan = quilt.get_quilt_plan(F, params.thetas)
    sched = chaos.FaultSchedule(
        [chaos.FaultSpec("quilt.dispatch", (0,), "device_loss", 0)]
    )
    with chaos.active(sched):
        with pytest.raises(chaos.DeviceLoss):
            quilt.quilt_run(jax.random.PRNGKey(2), plan, mesh=None)


def test_four_device_loss_mid_run_bit_identical(tmp_path):
    """A 4-virtual-device run that loses device 2 mid-run rebuilds the
    mesh over the 3 survivors and emits the EXACT same edges as the
    no-fault single-device run (subprocess: host device count is fixed
    at jax init)."""
    params = magm.make_params(THETA, 0.5, 8)
    F = np.asarray(
        magm.sample_attributes(jax.random.PRNGKey(3), 192, params.mu)
    )
    plan = quilt.get_quilt_plan(F, params.thetas)
    e_ref = quilt.quilt_run(jax.random.PRNGKey(7), plan).edges()

    out = tmp_path / "edges_degraded.npy"
    script = textwrap.dedent(
        f"""
        import warnings
        import jax
        import numpy as np
        from repro.core import magm, quilt
        from repro.dist import chaos
        from repro.launch import mesh as mesh_mod

        assert len(jax.devices()) == 4, jax.devices()
        theta = np.array([[0.35, 0.52], [0.52, 0.95]], dtype=np.float32)
        params = magm.make_params(theta, 0.5, 8)
        F = np.asarray(
            magm.sample_attributes(jax.random.PRNGKey(3), 192, params.mu)
        )
        plan = quilt.get_quilt_plan(F, params.thetas)
        # lose device 2 on the very first fused dispatch
        sched = chaos.FaultSchedule(
            [chaos.FaultSpec("quilt.dispatch", (0,), "device_loss", 2)]
        )
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            with chaos.active(sched):
                run = quilt.quilt_run(
                    jax.random.PRNGKey(7), plan,
                    mesh=mesh_mod.make_sampler_mesh(),
                )
        assert sched.fired and sched.fired[0]["kind"] == "device_loss"
        assert quilt.DISPATCH_COUNTERS["mesh_degrades"] == 1
        assert any(
            "surviving device" in str(x.message)
            for x in w
            if x.category is RuntimeWarning
        ), [str(x.message) for x in w]
        np.save({str(out)!r}, run.edges())
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
    np.testing.assert_array_equal(e_ref, np.load(out))


def test_balldrop_device_loss_degrades_too():
    """The balldrop engine shares the degrade-and-rerun recovery (its
    per-sample streams are layout-invariant for the same reason)."""
    params = magm.make_params(THETA, 0.5, 6)
    F = np.asarray(
        magm.sample_attributes(jax.random.PRNGKey(3), 128, params.mu)
    )
    plan = quilt.get_quilt_plan(F, params.thetas)
    mesh1 = mesh_mod.make_sampler_mesh(1)  # 1 device: loss is unrecoverable
    sched = chaos.FaultSchedule(
        [chaos.FaultSpec("quilt.dispatch", (0,), "device_loss", 0)]
    )
    with chaos.active(sched):
        with pytest.raises(chaos.DeviceLoss):
            balldrop.balldrop_run(jax.random.PRNGKey(2), plan, mesh=mesh1)


# -- observable degradation to the host fallback ----------------------------


def test_max_rounds_exhaustion_warns_and_counts():
    """max_rounds=1 on a collision-heavy config forces the host top-up;
    the fall-through must warn and bump degraded_fallbacks — not silently
    degrade (the collision regime of test_topup_round_stays_on_device)."""
    params = magm.make_params(
        np.array([[0.95, 0.95], [0.95, 0.95]], np.float32), 0.5, 3
    )
    F = np.asarray(
        magm.sample_attributes(jax.random.PRNGKey(1), 16, params.mu)
    )
    plan = quilt.get_quilt_plan(F, params.thetas)
    for k in quilt.DISPATCH_COUNTERS:
        quilt.DISPATCH_COUNTERS[k] = 0
    with pytest.warns(RuntimeWarning, match="host"):
        run = quilt.quilt_run(
            jax.random.PRNGKey(5), plan, max_rounds=1, exact_cells=False
        )
    assert quilt.DISPATCH_COUNTERS["degraded_fallbacks"] == 1
    assert quilt.DISPATCH_COUNTERS["host_topup_rounds"] >= 1
    edges = run.edges()
    flat = edges[:, 0] * 16 + edges[:, 1]
    assert np.unique(flat).size == flat.size  # fallback edges still dedup


def test_ample_rounds_stay_silent():
    """The default path must NOT warn: degradation telemetry only fires
    when the host loop actually runs."""
    import warnings

    params = magm.make_params(THETA, 0.5, 6)
    F = np.asarray(
        magm.sample_attributes(jax.random.PRNGKey(3), 128, params.mu)
    )
    plan = quilt.get_quilt_plan(F, params.thetas)
    for k in quilt.DISPATCH_COUNTERS:
        quilt.DISPATCH_COUNTERS[k] = 0
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        quilt.quilt_run(jax.random.PRNGKey(2), plan)
    assert quilt.DISPATCH_COUNTERS["degraded_fallbacks"] == 0
    assert not [x for x in w if x.category is RuntimeWarning]


def test_quilt_round_site_fires_per_round():
    """quilt.round is visited once per engine round, so a schedule can
    target any round of a run."""
    params = magm.make_params(THETA, 0.5, 6)
    F = np.asarray(
        magm.sample_attributes(jax.random.PRNGKey(3), 128, params.mu)
    )
    plan = quilt.get_quilt_plan(F, params.thetas)
    sched = chaos.FaultSchedule([chaos.FaultSpec("quilt.round", (0,))])
    with chaos.active(sched):
        with pytest.raises(chaos.InjectedFault):
            quilt.quilt_run(jax.random.PRNGKey(2), plan)
    assert sched.counters["quilt.round"] == 1


# -- balldrop backend: the same resilience contract --------------------------


def test_balldrop_kill_mid_stream_resume_bit_identical(tmp_path):
    """The ball-dropping engine rides the identical checkpoint/resume
    machinery: a stream killed mid-flight splices back bit-identically."""
    cfg = _magm_config(backend="balldrop")
    key = jax.random.PRNGKey(9)
    full = np.concatenate(
        list(MAGMSampler(cfg).sample_stream(key, chunk_edges=64))
    )
    assert full.shape[0] > 2 * 64  # the kill point is mid-stream

    d = str(tmp_path)
    got = _stream_killed_at(MAGMSampler(cfg), key, 64, d, visit=2)
    rest = list(MAGMSampler(cfg).resume_stream(d))
    assert rest
    np.testing.assert_array_equal(np.concatenate(got + rest), full)


def test_balldrop_checkpoint_refuses_foreign_backend(tmp_path):
    """backend= is part of the stream config digest: a balldrop checkpoint
    must not resume under the quilt engine (different edge stream), and
    vice versa — in both directions the refusal is a config-digest error,
    not a silent wrong-graph splice."""
    d1 = str(tmp_path / "bd")
    _stream_killed_at(
        MAGMSampler(_magm_config(backend="balldrop")),
        jax.random.PRNGKey(4),
        64,
        d1,
        visit=1,
    )
    with pytest.raises(ValueError, match="different sampler config"):
        list(MAGMSampler(_magm_config(backend="auto")).resume_stream(d1))

    d2 = str(tmp_path / "auto")
    _stream_killed_at(
        MAGMSampler(_magm_config(backend="auto")),
        jax.random.PRNGKey(4),
        64,
        d2,
        visit=1,
    )
    with pytest.raises(ValueError, match="different sampler config"):
        list(
            MAGMSampler(_magm_config(backend="balldrop")).resume_stream(d2)
        )


# -- sample_batch ------------------------------------------------------------


def test_sample_batch_deterministic_and_valid():
    cfg = _magm_config()
    key = jax.random.PRNGKey(11)
    a = MAGMSampler(cfg).sample_batch(3, key)
    b = MAGMSampler(cfg).sample_batch(3, key)
    assert len(a) == len(b) == 3
    for ga, gb in zip(a, b):
        assert ga.n == 128 and ga.num_edges > 0
        np.testing.assert_array_equal(ga.edges, gb.edges)
    assert MAGMSampler(cfg).sample_batch(0) == []


def test_sample_batch_fallback_loop_matches_fold_in():
    """Configs the fused device batch cannot serve (host backend) fall
    back to the documented per-sample ``fold_in(key, s)`` loop, so each
    member is independently reproducible from its own key."""
    cfg = _magm_config(backend="host")
    key = jax.random.PRNGKey(12)
    sampler = MAGMSampler(cfg)
    batch = sampler.sample_batch(2, key)
    assert len(batch) == 2
    for s, gs in enumerate(batch):
        solo = MAGMSampler(cfg).sample(jax.random.fold_in(key, s))
        np.testing.assert_array_equal(gs.edges, solo.edges)


def test_sample_batch_then_resume_stream_coexist(tmp_path):
    """A session that just served a batch still resumes a checkpointed
    stream correctly (batch draws must not disturb the stream cursor)."""
    cfg = _magm_config()
    key = jax.random.PRNGKey(13)
    full = np.concatenate(
        list(MAGMSampler(cfg).sample_stream(key, chunk_edges=64))
    )
    d = str(tmp_path)
    got = _stream_killed_at(MAGMSampler(cfg), key, 64, d, visit=2)
    sampler = MAGMSampler(cfg)
    assert len(sampler.sample_batch(2, jax.random.PRNGKey(14))) == 2
    rest = list(sampler.resume_stream(d))
    np.testing.assert_array_equal(np.concatenate(got + rest), full)
