"""repro.tracing: the off path, span nesting, the counter registry, and the
engine's counters and spans on small sampler calls."""

import jax
import numpy as np
import pytest

from repro import tracing
from repro.api import KPGMSampler, MAGMSampler, SamplerConfig
from repro.core import balldrop, kpgm, magm, quilt, transfer


@pytest.fixture
def clean():
    tracing.disable()
    tracing.reset()
    yield
    tracing.disable()
    tracing.reset()


def test_off_path_records_nothing_and_returns_the_shared_object(clean):
    first = tracing.span("a")
    assert tracing.span("b", bytes=3, faults=True) is first
    with first:
        with tracing.span("c"):
            pass
    calls = []

    @tracing.traced("d")
    def f(x):
        calls.append(x)
        return x + 1

    assert f(1) == 2 and calls == [1]
    assert tracing.records() == []


def test_spans_nest_with_parent_and_call_id(clean):
    tracing.enable()
    with tracing.span("root", k=1):
        with tracing.span("child"):
            with tracing.span("leaf", faults=True, bytes=8):
                pass
        traced = tracing.traced("fn")(lambda: None)
        traced()
    with tracing.span("next"):
        pass
    recs = {r.name: r for r in tracing.records()}
    assert [r.name for r in tracing.records()] == ["leaf", "child", "fn", "root", "next"]
    assert recs["root"].parent is None and recs["root"].attrs == {"k": 1}
    assert recs["child"].parent == "root" and recs["leaf"].parent == "child"
    assert recs["fn"].parent == "root"
    ids = {recs[n].call_id for n in ("root", "child", "leaf", "fn")}
    assert len(ids) == 1 and recs["next"].call_id not in ids
    assert set(recs["leaf"].attrs) == {"bytes", "minflt", "majflt"}
    assert recs["leaf"].attrs["minflt"] >= 0 and recs["leaf"].attrs["majflt"] >= 0
    for r in tracing.records():
        assert r.t0_ns <= r.t1_ns
    assert recs["root"].t0_ns <= recs["leaf"].t0_ns <= recs["leaf"].t1_ns <= recs["root"].t1_ns
    tracing.reset()
    assert tracing.records() == []


def test_a_suspended_generator_span_closes_out_of_order(clean):
    tracing.enable()

    def gen():
        with tracing.span("stream"):
            yield 1
            yield 2

    g = iter(gen())
    next(g)
    with tracing.span("consumer"):
        pass
    g.close()
    with tracing.span("after"):
        pass
    recs = {r.name: r for r in tracing.records()}
    assert recs["consumer"].parent == "stream"
    assert recs["after"].parent is None
    assert recs["after"].call_id != recs["stream"].call_id


def test_the_registry_holds_the_module_counter_dicts_by_identity():
    assert tracing.COUNTERS["quilt.dispatch"] is quilt.DISPATCH_COUNTERS
    assert tracing.COUNTERS["balldrop.dispatch"] is balldrop.DISPATCH_COUNTERS
    assert tracing.COUNTERS["quilt.plan"] is quilt.PLAN_STATS
    assert tracing.COUNTERS["quilt"] is transfer.ENGINE_COUNTERS is quilt.ENGINE_COUNTERS
    snap = tracing.snapshot()
    for k, v in quilt.DISPATCH_COUNTERS.items():
        assert snap[f"quilt.dispatch.{k}"] == v
    for k in ("candidate_slots", "kept_edges", "d2h_bytes"):
        assert f"quilt.{k}" in snap
    before = snap["quilt.plan.plan_hits"]
    quilt.PLAN_STATS["plan_hits"] += 1
    try:
        assert tracing.snapshot()["quilt.plan.plan_hits"] == before + 1
    finally:
        quilt.PLAN_STATS["plan_hits"] -= 1


THETA = np.array([[0.15, 0.7], [0.7, 0.85]], np.float32)
GRAPH500 = np.array([[0.57, 0.19], [0.19, 0.05]], np.float32)
# both buffers pass the stream's 32,768-row window, so the stream copies
# its windows on top of the whole-buffer copies of the keep mask
KPGM_EDGES = 34000


@pytest.fixture(scope="module")
def samplers():
    return {
        "magm": MAGMSampler(
            SamplerConfig(params=magm.make_params(THETA, mu=0.5, d=9), num_nodes=512)
        ),
        "kpgm": KPGMSampler(SamplerConfig(params=kpgm.make_params(GRAPH500, d=12))),
    }


def _call(sampler, kind, call, key):
    extra = {"num_edges": KPGM_EDGES} if kind == "kpgm" else {}
    if call == "sample":
        return [sampler.sample(key, **extra).edges]
    return list(sampler.sample_stream(key, chunk_edges=4096, **extra))


def _delta(before):
    return {k: v - before[k] for k, v in tracing.snapshot().items()}


@pytest.mark.parametrize("kind", ["magm", "kpgm"])
def test_engine_counters_and_spans_on_sample_and_stream(samplers, kind, clean):
    sampler = samplers[kind]
    key = jax.random.PRNGKey(5)
    targets = np.array([KPGM_EDGES]) if kind == "kpgm" else None
    run = sampler._run(key, targets=targets) if kind == "kpgm" else sampler._run(key)
    (part,) = run.parts
    assert part.snode.shape[0] > 32768
    slots = run.plan.num_graphs * run.slots_per_graph
    buffers = part.keep.nbytes + part.snode.nbytes + part.dnode.nbytes
    _call(sampler, kind, "sample", key)  # compile every shape first

    moved = {}
    for call in ("sample", "sample_stream"):
        tracing.reset()
        before = tracing.snapshot()
        tracing.enable()
        pieces = _call(sampler, kind, call, key)
        tracing.disable()
        d = _delta(before)
        recs = tracing.records()
        rows = sum(p.shape[0] for p in pieces)
        assert rows > 0
        assert d["quilt.candidate_slots"] == slots
        assert d["quilt.kept_edges"] == rows
        copies = [r for r in recs if r.name == "quilt.copy"]
        assert d["quilt.d2h_bytes"] == sum(r.attrs["bytes"] for r in copies)
        masked = [r for r in copies if r.parent == "quilt.mask"]
        assert sum(r.attrs["bytes"] for r in masked) == buffers
        moved[call] = d["quilt.d2h_bytes"]
        root = "sampler.sample" if call == "sample" else "sampler.stream"
        roots = [r for r in recs if r.parent is None]
        assert [r.name for r in roots] == [root]
        assert {r.call_id for r in recs} == {roots[0].call_id}
        names = {r.name for r in recs}
        assert {"quilt.run", "quilt.round", "quilt.round_wait", "quilt.mask"} <= names
        rounds = [r for r in recs if r.name == "quilt.round"]
        assert rounds[-1].attrs["slots"] == slots
        # the per-round counts are the engine's only other copy
        counts = [r for r in copies if r.parent == "quilt.run"]
        assert len(counts) == len(rounds)
        assert all(0 < r.attrs["bytes"] <= 4 * 1024 for r in counts)
        if call == "sample":
            emitted = [r for r in copies if r.parent == "quilt.emit"]
            # snode and dnode again, served from their host copies
            assert len(emitted) == 2 and all(r.attrs["bytes"] == 0 for r in emitted)
            assert len(copies) == len(counts) + len(masked) + 2
            assert d["quilt.d2h_bytes"] == buffers + sum(r.attrs["bytes"] for r in counts)
        else:
            assert {"stream.window", "stream.rechunk"} <= names
            windows = [r for r in copies if r.parent == "stream.window"]
            assert len(copies) == len(counts) + len(masked) + len(windows)
    assert moved["sample_stream"] > moved["sample"]


def test_counters_count_with_tracing_off(samplers, clean):
    sampler = samplers["magm"]
    key = jax.random.PRNGKey(6)
    before = tracing.snapshot()
    gs = sampler.sample(key)
    d = _delta(before)
    assert tracing.records() == []
    assert d["quilt.kept_edges"] == gs.num_edges
    assert d["quilt.d2h_bytes"] > 0 and d["quilt.candidate_slots"] > 0
    assert d["quilt.dispatch.device_rounds"] == 1


def test_balldrop_counts_its_slots_and_copies_its_buffers_once(clean):
    sampler = MAGMSampler(
        SamplerConfig(
            params=magm.make_params(THETA, mu=0.5, d=7), num_nodes=128,
            backend="balldrop",
        )
    )
    key = jax.random.PRNGKey(7)
    run = sampler._run(key)
    assert run.sampler == "balldrop"
    before = tracing.snapshot()
    tracing.enable()
    gs = sampler.sample(key)
    tracing.disable()
    d = _delta(before)
    copies = [r for r in tracing.records() if r.name == "quilt.copy"]
    counts = [r for r in copies if r.parent == "quilt.run"]
    # balldrop's mask is its take; snode and dnode reach the host in edges()
    assert d["quilt.candidate_slots"] == run.num_samples * run.slots_per_graph
    assert d["quilt.kept_edges"] == gs.num_edges > 0
    (part,) = run.parts
    assert d["quilt.d2h_bytes"] == (
        part.keep.nbytes + part.snode.nbytes + part.dnode.nbytes
        + sum(r.attrs["bytes"] for r in counts)
    )
    assert d["balldrop.dispatch.device_rounds"] >= 1


def test_to_host_counts_the_bytes_it_moves(clean):
    x = jax.numpy.arange(10, dtype=jax.numpy.int32)
    before = tracing.snapshot()
    tracing.enable()
    np.testing.assert_array_equal(transfer.to_host(x), np.arange(10))
    transfer.to_host(x, moves=False)  # served from the host copy
    transfer.to_host(np.arange(4))  # already on the host
    tracing.disable()
    assert _delta(before)["quilt.d2h_bytes"] == x.nbytes == 40
    copies = tracing.records()
    assert [r.name for r in copies] == ["quilt.copy"] * 3
    assert [r.attrs["bytes"] for r in copies] == [40, 0, 0]


@pytest.mark.parametrize("engine", ["quilt", "balldrop"])
def test_a_host_top_up_counts_each_buffer_once(engine, clean):
    # one round on a collision-heavy law falls short, so the engine fetches
    # its buffers for the host top-up before emission fetches them again
    params = magm.make_params(np.full((2, 2), 0.95, np.float32), 0.5, 3)
    F = np.asarray(magm.sample_attributes(jax.random.PRNGKey(1), 16, params.mu))
    plan = quilt.get_quilt_plan(F, params.thetas)
    engine_run = quilt.quilt_run if engine == "quilt" else balldrop.balldrop_run
    before = tracing.snapshot()
    tracing.enable()
    with pytest.warns(RuntimeWarning, match="host"):
        run = engine_run(jax.random.PRNGKey(5), plan, max_rounds=1, exact_cells=False)
    run.edges()
    tracing.disable()
    assert run.nodes_fetched and run.tail
    copies = [r for r in tracing.records() if r.name == "quilt.copy"]
    emitted = [r for r in copies if r.parent == "quilt.emit"]
    assert len(emitted) == 2 and all(r.attrs["bytes"] == 0 for r in emitted)
    moved = [r.attrs["bytes"] for r in copies if r.attrs["bytes"]]
    assert _delta(before)["quilt.d2h_bytes"] == sum(moved)
    # each array counts once, whoever fetched it first: the drawn targets
    # (float32) and the round's counts (int32), one per graph; take, snode
    # and dnode; and, for the quilt's top-up, its configuration buffers
    per_graph = 2 * 4 * run.targets.size
    (part,) = run.parts
    buffers = part.keep.size + part.snode.nbytes + part.dnode.nbytes
    configs = 2 * part.snode.nbytes if engine == "quilt" else 0
    assert sum(moved) == per_graph + buffers + configs


def test_a_root_is_the_shared_no_op_when_off_and_nothing_is_captured(clean):
    assert tracing.root("sampler.sample") is tracing.span("x")
    with tracing.root("sampler.sample"):
        pass
    assert tracing.records() == []


@pytest.mark.parametrize("call", ["sample", "sample_stream"])
def test_a_root_holds_the_rise_of_the_counters_over_its_call(samplers, call, clean):
    sampler = samplers["magm"]
    key = jax.random.PRNGKey(8)
    _call(sampler, "magm", call, key)
    before = tracing.snapshot()
    tracing.enable()
    pieces = _call(sampler, "magm", call, key)
    tracing.disable()
    (root,) = [r for r in tracing.records() if r.parent is None]
    assert "capture" not in root.attrs
    moved = {k: v for k, v in _delta(before).items() if v}
    assert root.attrs["counters"] == moved
    assert moved["quilt.kept_edges"] == sum(p.shape[0] for p in pieces)


@pytest.mark.parametrize("call", ["sample", "sample_stream"])
def test_a_call_under_a_profiler_capture_is_recorded_with_tracing_off(
    samplers, call, clean, tmp_path
):
    sampler = samplers["magm"]
    key = jax.random.PRNGKey(9)
    _call(sampler, "magm", call, key)
    assert tracing._capture() is None
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert tracing._capture() == str(tmp_path)
        pieces = _call(sampler, "magm", call, key)
        # between calls nothing records, as with tracing off
        with tracing.span("between"):
            pass
    finally:
        jax.profiler.stop_trace()
    recs = tracing.records()
    roots = [r for r in recs if r.parent is None]
    assert [r.attrs["capture"] for r in roots] == [str(tmp_path)]
    assert roots[0].attrs["counters"]["quilt.kept_edges"] == sum(
        p.shape[0] for p in pieces
    )
    assert {"quilt.run", "quilt.copy", "quilt.mask"} <= {r.name for r in recs}
    assert "between" not in {r.name for r in recs}
    _call(sampler, "magm", call, key)
    assert tracing.records() == recs
