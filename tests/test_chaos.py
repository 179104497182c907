"""Fault-injection harness: FaultSchedule determinism + serialization,
with_retries semantics (classification, backoff, deadline), the
checkpoint-save chaos sites (crash mid-write leaves the previous
checkpoint restorable), and the StragglerMonitor action hook."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.dist import chaos, checkpoint as ckpt, fault


# -- FaultSchedule ----------------------------------------------------------


def test_spec_fires_at_exact_visits():
    sched = chaos.FaultSchedule([chaos.FaultSpec("site", (1, 3))])
    sched.check("site")  # visit 0
    with pytest.raises(chaos.InjectedFault):
        sched.check("site")  # visit 1
    sched.check("site")  # visit 2
    with pytest.raises(chaos.InjectedFault):
        sched.check("site")  # visit 3
    sched.check("other")  # other sites unaffected
    assert [f["visit"] for f in sched.fired] == [1, 3]


def test_device_loss_carries_device_index():
    sched = chaos.FaultSchedule(
        [chaos.FaultSpec("d", (0,), "device_loss", 2)]
    )
    with pytest.raises(chaos.DeviceLoss) as ei:
        sched.check("d")
    assert ei.value.device == 2
    assert isinstance(ei.value, chaos.InjectedFault)  # loss IS a fault


def test_fault_reexport_identity():
    # existing fault.InjectedFault call sites keep the same class
    assert fault.InjectedFault is chaos.InjectedFault
    assert fault.DeviceLoss is chaos.DeviceLoss


def test_rate_mode_is_deterministic_per_seed():
    a = chaos.FaultSchedule(seed=7, rates={"s": 0.3})
    fires = []
    for v in range(50):
        try:
            a.check("s")
            fires.append(False)
        except chaos.InjectedFault:
            fires.append(True)
    assert any(fires) and not all(fires)
    b = chaos.FaultSchedule(seed=7, rates={"s": 0.3})
    for v, f in enumerate(fires):  # identical firing pattern
        if f:
            with pytest.raises(chaos.InjectedFault):
                b.check("s")
        else:
            b.check("s")
    c = chaos.FaultSchedule(seed=8, rates={"s": 0.3})
    other = []
    for v in range(50):
        try:
            c.check("s")
            other.append(False)
        except chaos.InjectedFault:
            other.append(True)
    assert fires != other  # a different seed scatters differently


def test_json_roundtrip():
    sched = chaos.FaultSchedule(
        [
            chaos.FaultSpec("a", (0, 2), "fault", 0, "boom"),
            chaos.FaultSpec("b", (1,), "device_loss", 3),
        ],
        seed=42,
        rates={"c": 0.1},
    )
    back = chaos.FaultSchedule.from_json(sched.to_json())
    assert back.specs == sched.specs
    assert back.seed == sched.seed and back.rates == sched.rates
    with pytest.raises(ValueError):
        chaos.FaultSchedule.from_json('{"schema": "nope"}')


def test_invalid_kind_rejected():
    with pytest.raises(ValueError):
        chaos.FaultSchedule([chaos.FaultSpec("s", (0,), "meteor")])


def test_install_active_maybe_fail():
    chaos.maybe_fail("anything")  # no-op with nothing installed
    sched = chaos.FaultSchedule([chaos.FaultSpec("s", (0,))])
    with chaos.active(sched):
        assert chaos.active_schedule() is sched
        with pytest.raises(chaos.InjectedFault):
            chaos.maybe_fail("s")
    assert chaos.active_schedule() is None
    chaos.maybe_fail("s")


def test_check_is_thread_safe():
    sched = chaos.FaultSchedule([chaos.FaultSpec("s", (99,))])
    errs = []

    def worker():
        try:
            for _ in range(50):
                try:
                    sched.check("s")
                except chaos.InjectedFault:
                    pass
        except Exception as e:  # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    assert sched.counters["s"] == 200  # every visit counted exactly once
    assert len(sched.fired) == 1  # visit 99 fired for exactly one thread


# -- with_retries -----------------------------------------------------------


def test_retries_then_succeeds_with_recorded_backoff():
    calls, sleeps = [], []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise chaos.InjectedFault("transient")
        return "ok"

    policy = chaos.RetryPolicy(
        max_attempts=5, base_delay=0.1, jitter=0.0, seed=0
    )
    out = chaos.with_retries(flaky, policy, sleep=sleeps.append)
    assert out == "ok" and len(calls) == 3
    assert sleeps == [0.1, 0.2]  # exponential, deterministic (no jitter)


def test_backoff_jitter_is_seeded():
    p = chaos.RetryPolicy(base_delay=0.1, jitter=0.5, seed=3)
    assert p.backoff(0) == p.backoff(0)  # same seed+attempt -> same delay
    assert p.backoff(0) >= 0.1
    assert p.backoff(1) <= p._replace(jitter=0.0).backoff(1) * 1.5


def test_exhausted_retries_raise_last_fault():
    def always():
        raise chaos.InjectedFault("still broken")

    with pytest.raises(chaos.InjectedFault):
        chaos.with_retries(
            always, chaos.RetryPolicy(max_attempts=3), sleep=lambda s: None
        )


def test_fatal_faults_propagate_immediately():
    calls = []

    def lost():
        calls.append(1)
        raise chaos.DeviceLoss("gone", device=1)

    with pytest.raises(chaos.DeviceLoss):
        chaos.with_retries(
            lost, chaos.RetryPolicy(max_attempts=5), sleep=lambda s: None
        )
    assert len(calls) == 1  # DeviceLoss is fatal by default: no retry
    with pytest.raises(KeyError):  # unclassified -> fatal
        chaos.with_retries(
            lambda: (_ for _ in ()).throw(KeyError("x")),
            chaos.RetryPolicy(max_attempts=5),
            sleep=lambda s: None,
        )


def test_classify():
    p = chaos.RetryPolicy()
    assert p.classify(chaos.InjectedFault("x")) == "retryable"
    assert p.classify(chaos.DeviceLoss("x")) == "fatal"
    assert p.classify(ValueError("x")) == "fatal"
    assert chaos.is_retryable(chaos.InjectedFault("x"), p)


def test_deadline_cuts_the_loop():
    clock = {"t": 0.0}

    def tick(s):
        clock["t"] += s

    def always():
        clock["t"] += 1.0
        raise chaos.InjectedFault("slow and broken")

    with pytest.raises(chaos.DeadlineExceeded):
        chaos.with_retries(
            always,
            chaos.RetryPolicy(max_attempts=100, base_delay=1.0, deadline=3.0),
            sleep=tick,
            clock=lambda: clock["t"],
        )
    assert clock["t"] <= 5.0  # gave up near the budget, not after 100 tries


def test_on_retry_hook_sees_each_retry():
    seen = []
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise chaos.InjectedFault("again")
        return 1

    chaos.with_retries(
        flaky,
        chaos.RetryPolicy(max_attempts=5),
        on_retry=lambda a, e, d: seen.append((a, type(e).__name__)),
        sleep=lambda s: None,
    )
    assert seen == [(0, "InjectedFault"), (1, "InjectedFault")]


# -- checkpoint crash-mid-write (the property StreamCheckpoint rides on) ----


def _tree():
    return {"a": jnp.arange(12, dtype=jnp.float32), "b": jnp.int32(3)}


def test_crash_before_write_leaves_previous_checkpoint(tmp_path):
    t = _tree()
    ckpt.save(str(tmp_path), 1, t)
    sched = chaos.FaultSchedule([chaos.FaultSpec("checkpoint.write", (0,))])
    with chaos.active(sched):
        with pytest.raises(chaos.InjectedFault):
            ckpt.save(str(tmp_path), 2, t)
    assert ckpt.latest_step(str(tmp_path)) == 1
    restored, _ = ckpt.restore(str(tmp_path), 1, jax.eval_shape(lambda: t))
    np.testing.assert_array_equal(
        np.asarray(restored["a"]), np.asarray(t["a"])
    )


def test_crash_between_temp_write_and_rename(tmp_path):
    """Kill after the .tmp dir is fully written but before any rename:
    the previous checkpoint AT THE SAME STEP must restore cleanly."""
    t1 = {"a": jnp.zeros(4, jnp.float32)}
    t2 = {"a": jnp.ones(4, jnp.float32)}
    ckpt.save(str(tmp_path), 5, t1)
    sched = chaos.FaultSchedule([chaos.FaultSpec("checkpoint.rename", (0,))])
    with chaos.active(sched):
        with pytest.raises(chaos.InjectedFault):
            ckpt.save(str(tmp_path), 5, t2)
    # the half-finished save must not have clobbered the old copy
    assert ckpt.latest_step(str(tmp_path)) == 5
    restored, _ = ckpt.restore(str(tmp_path), 5, jax.eval_shape(lambda: t1))
    np.testing.assert_array_equal(
        np.asarray(restored["a"]), np.zeros(4, np.float32)
    )
    # and a clean retry of the same save wins
    ckpt.save(str(tmp_path), 5, t2)
    restored, _ = ckpt.restore(str(tmp_path), 5, jax.eval_shape(lambda: t2))
    np.testing.assert_array_equal(
        np.asarray(restored["a"]), np.ones(4, np.float32)
    )


# -- StragglerMonitor action hook -------------------------------------------


def test_on_straggler_callback_fires_with_context():
    mon = fault.StragglerMonitor(window=16, factor=2.0)
    events = []
    mon.on_straggler(lambda step, secs, median: events.append((step, secs, median)))
    for i in range(8):
        mon.observe(i, 0.1)
    mon.observe(8, 0.5)
    mon.observe(9, 0.11)  # not a straggler: no event
    assert len(events) == 1
    step, secs, median = events[0]
    assert step == 8 and secs == 0.5 and median == pytest.approx(0.1)


def test_supervisor_feeds_straggler_monitor(tmp_path):
    """TrainSupervisor(straggler_monitor=) times every step through the
    monitor, so a slow step fires the registered eviction hook."""
    import time

    mon = fault.StragglerMonitor(window=16, factor=3.0, min_history=4)
    flagged = []
    mon.on_straggler(lambda step, secs, median: flagged.append(step))

    def step_fn(params, opt_state, batch):
        # a steady 20ms baseline: a loaded host can stretch a 2ms sleep
        # past 3x the median, but not a 20ms one
        time.sleep(0.5 if batch == 8 else 0.02)
        return params, opt_state, {"loss": 0.0}

    sup = fault.TrainSupervisor(
        step_fn,
        lambda step: step,
        str(tmp_path),
        ckpt_every=100,
        straggler_monitor=mon,
    )
    params, opt_state, metrics = sup.run({"w": jnp.zeros(2)}, {}, 12)
    assert len(metrics) == 12
    assert flagged == [8]
    assert mon.flagged[0]["step"] == 8
