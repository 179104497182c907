"""The trace reduction on known numbers."""

import pathlib

import pytest

from bench import trace_reduce as tr


def test_merge_unions_overlaps_and_keeps_gaps():
    assert tr.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_gaps_cover_the_window_outside_busy():
    busy = tr.merge(tr.clip([(0, 2), (5, 8), (12, 20)], 1, 15))
    assert busy == [(1, 2), (5, 8), (12, 15)]
    assert tr.gaps(busy, 1, 15) == [(2, 5), (8, 12)]
    assert tr.gaps([], 0, 4) == [(0, 4)]


# op names as the v5e trace prints them (the HLO text of each op)
SORT = ("%sort.14 = (u32[80576000]{0:T(1024)}, s32[80576000]{0:T(1024)}) "
        "sort(u32[80576000]{0:T(1024)} %fusion.19, s32[80576000]{0:T(1024)} "
        "%iota.7), dimensions={0}, is_stable=true, to_apply=%region_2.15")
GATHER = ("%fusion.1 = s32[80576000]{0:T(1024)} fusion(s32[524288]{0:T(1024)S(1)} "
          "%bitcast.377, s32[80576512]{0:T(1024)} %pad_clamp_fusion), "
          "kind=kCustom, calls=%fused_computation.1")
DESCENT = ("%descent_prng.1 = (s32[630784,128]{1,0:T(8,128)}, s32[630784,128]"
           "{1,0:T(8,128)}) custom-call(s32[64]{0:T(128)S(1)} %copy.12), "
           'custom_call_target="tpu_custom_call", frontend_attributes={}')
LOOP = ("%while.22 = (u32[]{:T(128)}, u32[1,64,1259000]{2,1,0:T(8,128)}, "
        "/*index=5*/u32[], u32[]) while((u32[]{:T(128)}, u32[]) %tuple.524), "
        "condition=%wide.cond.9, body=%wide.body.9.sunk")
ELEMENTWISE = ("%or_or_fusion = (u32[9437184]{0:T(1024)S(1)}, u32[9437184]) "
               "fusion(s32[73728,128]{1,0:T(8,128)S(1)} %pallas_call.2), "
               "kind=kLoop, calls=%fused_computation.15")
COPY = ("%copy.11 = s32[73728,128]{1,0:T(8,128)} copy(s32[73728,128]"
        "{0,1:T(8,128)S(1)} %broadcast_add_fusion)")
SLICE = ("%dynamic-slice.11 = s32[80576000]{0:T(1024)} dynamic-slice("
         "s32[80576000]{0:T(1024)} %get-tuple-element.3, s32[] %c)")


@pytest.mark.parametrize(
    "name,op,cls",
    [
        (SORT, "sort", "sort"),
        (GATHER, "fusion", "gather"),
        (DESCENT, "custom-call", "descent"),
        (LOOP, "while", "other"),
        (ELEMENTWISE, "fusion", "other"),
        (COPY, "copy", "copy"),
        (SLICE, "dynamic-slice", "copy"),
        ("no HLO text", "", "other"),
    ],
)
def test_classify(name, op, cls):
    assert tr.opcode(name) == op
    assert tr.classify(name) == cls


def test_timeline_takes_the_innermost_open_event():
    events = [("bench.window", 0, 100), ("bench.graph", 10, 40),
              ("np.asarray(jax.Array)", 20, 30), ("PjitFunction(f)", 12, 15)]
    timeline = tr.Timeline(events)
    assert timeline.label(25) == "np.asarray(jax.Array)"
    assert timeline.label(17) == "bench.graph"
    assert timeline.label(13) == "PjitFunction(f)"
    assert timeline.label(50) == "bench.window"
    assert timeline.label(150) == "outside"


def test_reduce_on_known_events():
    # window 0..100 ns on two devices; ops in ns
    ops = {
        "/device:TPU:0": [
            (SORT, 0, 30),
            (GATHER, 20, 40),  # overlaps the sort: busy 0..40
            (DESCENT, 60, 70),
        ],
        "/device:TPU:1": [(ELEMENTWISE, 90, 130)],  # clipped to 90..100
    }
    host = [("bench.window", 0, 100), ("bench.graph", 35, 65)]
    out = tr.reduce(ops, host, (0, 100))
    assert out["devices"] == 2
    assert out["window_s"] == pytest.approx(100e-9)
    # busy: device 0 40 + 10 = 50 ns, device 1 10 ns; mean 30 ns
    assert out["busy_s"] == pytest.approx(30e-9)
    assert out["class_s"] == pytest.approx(
        {"sort": 15e-9, "gather": 10e-9, "descent": 5e-9, "other": 5e-9}
    )
    assert out["top_ops"][0] == ["%sort.14 (sort)", pytest.approx(15e-9)]
    # gaps cut at the host events: device 0 40..60 (graph), 70..100
    # (window); device 1 0..90 is 0..35 (window), 35..65 (graph), 65..90
    # (window)
    assert out["idle_gaps"] == [
        ["bench.window", pytest.approx(35e-9)],
        ["bench.window", pytest.approx(30e-9)],
        ["bench.graph", pytest.approx(30e-9)],
        ["bench.window", pytest.approx(25e-9)],
        ["bench.graph", pytest.approx(20e-9)],
    ]
    assert out["idle_by_span_s"] == pytest.approx(
        {"bench.graph": 25e-9, "bench.window": 45e-9}
    )


def test_a_gap_over_a_copy_and_the_host_code_after_it_is_shared():
    # one idle stretch 10..80: the graph's own code, a device-to-host copy
    # (20..50), then the graph's code again (the filter); a midpoint rule
    # would give all 70 ns to the copy
    ops = {"/device:TPU:0": [(SORT, 0, 10), (GATHER, 80, 100)]}
    host = [("bench.window", 0, 100), ("bench.graph", 10, 90),
            ("np.asarray(jax.Array)", 20, 50)]
    out = tr.reduce(ops, host, (0, 100))
    assert out["idle_by_span_s"] == pytest.approx(
        {"bench.graph": 40e-9, "np.asarray(jax.Array)": 30e-9}
    )
    assert out["idle_gaps"] == [
        ["np.asarray(jax.Array)", pytest.approx(30e-9)],
        ["bench.graph", pytest.approx(30e-9)],
        ["bench.graph", pytest.approx(10e-9)],
    ]


def test_timeline_split_joins_pieces_of_one_event_and_covers_the_span():
    events = [("bench.window", 0, 100), ("bench.graph", 10, 40),
              ("np.asarray(jax.Array)", 20, 30), ("PjitFunction(f)", 50, 60)]
    timeline = tr.Timeline(events)
    assert timeline.split(45, 70) == [
        ("bench.window", 5), ("PjitFunction(f)", 10), ("bench.window", 10)
    ]
    assert timeline.split(12, 18) == [("bench.graph", 6)]
    pieces = timeline.split(-10, 120)
    assert sum(d for _, d in pieces) == 130
    assert [n for n, _ in pieces] == [
        "outside", "bench.window", "bench.graph", "np.asarray(jax.Array)",
        "bench.graph", "bench.window", "PjitFunction(f)", "bench.window",
        "outside",
    ]


# A trace recorded on one TPU v5e by bench/tests/record_trace.py: a window
# span holding two graph spans, each running the Pallas descent kernel, a
# sort and a gather; GAP_S of host sleep between the graphs (inside the
# window span only) and at the start of the second graph.
RECORDED = pathlib.Path(__file__).parent / "data" / "small.xplane.pb"
GAP_S = 0.2


@pytest.fixture(scope="module")
def recorded():
    return tr.read(str(RECORDED))


def test_recorded_trace_has_one_device_and_the_benchmark_spans(recorded):
    device_ops, host, window = recorded
    assert list(device_ops) == ["/device:TPU:0"]
    names = [n for n, _, _ in host]
    assert names.count("bench.window") == 1 and names.count("bench.graph") == 2
    (w,) = [(s, e) for n, s, e in host if n == "bench.window"]
    assert window == w and (w[1] - w[0]) * 1e-9 >= 2 * GAP_S


def test_recorded_busy_time_is_the_union_of_the_ops(recorded):
    device_ops, host, (lo, hi) = recorded
    ops = device_ops["/device:TPU:0"]
    # the union by a sweep over the op boundaries, apart from tr.merge
    edges = sorted([(max(s, lo), 1) for _, s, e in ops if e > lo and s < hi]
                   + [(min(e, hi), -1) for _, s, e in ops if e > lo and s < hi])
    busy, depth, since = 0.0, 0, None
    for t, step in edges:
        if depth == 0 and step == 1:
            since = t
        depth += step
        if depth == 0:
            busy += t - since
    out = tr.reduce(device_ops, host, (lo, hi))
    assert out["busy_s"] == pytest.approx(busy * 1e-9, rel=1e-9)
    assert 0.0 < out["busy_s"] < out["window_s"] - 2 * GAP_S
    assert sum(out["class_s"].values()) >= out["busy_s"] * (1 - 1e-9)


def test_recorded_ops_fall_in_the_classes_the_metrics_read(recorded):
    out = tr.reduce(*recorded)
    for cls in ("descent", "sort", "gather"):
        assert out["class_s"].get(cls, 0.0) > 0.0, (cls, out["top_ops"])


def test_recorded_gaps_go_to_the_span_open_at_the_time(recorded):
    device_ops, host, (lo, hi) = recorded
    busy = tr.merge(tr.clip(((s, e) for _, s, e in device_ops["/device:TPU:0"]),
                            lo, hi))
    # the two sleeps are the two longest gaps; each is cut at the host
    # events and its longest piece goes to the span the sleep ran in
    longest = sorted(tr.gaps(busy, lo, hi), key=lambda g: g[0] - g[1])[:2]
    timeline = tr.Timeline(host)
    owners = set()
    for s, e in longest:
        assert (e - s) * 1e-9 >= GAP_S
        pieces = timeline.split(s, e)
        assert sum(d for _, d in pieces) == pytest.approx(e - s, rel=1e-12)
        name, d = max(pieces, key=lambda p: p[1])
        owners.add(name)
        # the host and device clocks of the trace agree to well under 1 ms
        assert d * 1e-9 >= GAP_S - 1e-3
    assert owners == {"bench.window", "bench.graph"}
    out = tr.reduce(*recorded)
    assert {name for name, _ in out["idle_gaps"][:2]} == owners
    assert out["idle_by_span_s"]["bench.window"] >= GAP_S - 1e-3
    assert out["idle_by_span_s"]["bench.graph"] >= GAP_S - 1e-3
