"""The program's spans laid over a trace: the attribution on known numbers,
the window's records and counters, and a traced run's result line."""

import io
import json

import pytest

from bench import instruments, run
from bench import program_trace as pt
from bench import trace_reduce as tr
from bench.tests.test_benchmark_spec import CELLS, _tiny
from bench.tests.test_trace_reduce import GATHER, SORT

# Program spans on a host clock 5 s ahead of nothing in particular; the
# trace's window opens at 1,000 ns.  Layout, in ns from the window's start:
# device busy 0..6 and 94..100; bench.graph 7..93; JAX copy events at
# 30..40 (inside a copy span), 60..62 (1 ns past its copy span) and 85..86
# (under no copy span), and one after the window.
BASE_TRACE = 1000
BASE_HOST_S = 5.0
PROGRAM = [
    ("sampler.sample", 8, 92),
    ("quilt.run", 8, 50),
    ("quilt.round", 10, 14),
    ("quilt.mask", 20, 45),
    ("quilt.copy", 29, 41),
    ("quilt.emit", 55, 80),
    ("quilt.copy", 60, 61),
]


def _program_case():
    from repro import tracing

    host_ns = round(BASE_HOST_S * 1e9)
    records = [
        tracing.Record(n, host_ns + s, host_ns + e, None, 1, {}) for n, s, e in PROGRAM
    ]
    bounds = (BASE_HOST_S, BASE_HOST_S + 100e-9)
    t = BASE_TRACE
    ops = {"/device:TPU:0": [(SORT, t + 0, t + 6), (GATHER, t + 94, t + 100)]}
    host = [("bench.window", t, t + 100), ("bench.graph", t + 7, t + 93)] + [
        (pt.JAX_COPY, t + s, t + e) for s, e in ((30, 40), (60, 62), (85, 86), (150, 160))
    ]
    return records, bounds, ops, host, (t, t + 100)


def test_attribute_puts_idle_down_to_the_innermost_program_span():
    records, bounds, ops, host, window = _program_case()
    mapped, drift = pt.map_spans(records, bounds, window)
    assert [(n, s - BASE_TRACE, e - BASE_TRACE) for n, s, e in mapped] == PROGRAM
    assert abs(drift) < 1.0
    out = pt.attribute(ops, host, window, mapped)
    # idle 6..94; 6..7 and 93..94 lie outside the graph span and are left out
    assert out["idle_by_program_span_s"] == pytest.approx({
        "quilt.run": 13e-9,  # 8..10, 14..20, 45..50
        "quilt.round": 4e-9,
        "quilt.mask": 13e-9,  # 20..29, 41..45
        "quilt.copy": 13e-9,  # 29..41, 60..61
        "quilt.emit": 24e-9,  # 55..60, 61..80
        "sampler.sample": 17e-9,  # 50..55, 80..92
        pt.UNCOVERED: 2e-9,  # 7..8, 92..93
    })
    assert out["graph_idle_s"] == pytest.approx(86e-9)
    assert out["copy_events"] == 3
    assert out["copy_events_uncovered"] == 1
    assert out["copy_misalignment_s"] == pytest.approx(1e-9)
    line = pt.program_line(out, 1, drift)
    assert "quilt.emit 0.0" in line
    assert "uncovered 22.09% (none 2.33%, sampler.* itself 19.77%) of 0.0 ms" in line


def test_attribute_averages_over_devices_and_leaves_reduce_alone():
    records, bounds, ops, host, window = _program_case()
    mapped, _ = pt.map_spans(records, bounds, window)
    before = tr.reduce(ops, host, window)
    ops2 = dict(ops, **{"/device:TPU:1": [(SORT, window[0], window[1])]})
    out = pt.attribute(ops2, host, window, mapped)
    # the second device is never idle: every class halves
    assert out["graph_idle_s"] == pytest.approx(43e-9)
    assert out["idle_by_program_span_s"]["quilt.emit"] == pytest.approx(12e-9)
    assert tr.reduce(ops, host, window) == before


def test_the_window_keeps_its_own_records_and_sums_its_roots_counters(monkeypatch):
    from repro import tracing

    def rec(name, t0_s, t1_s, parent=None, **attrs):
        return tracing.Record(name, round(t0_s * 1e9), round(t1_s * 1e9),
                              parent, 1, attrs)

    recs = [
        rec("sampler.sample", 1.0, 1.5, counters={"quilt.d2h_bytes": 7}),
        rec("quilt.copy", 2.1, 2.2, "quilt.mask"),
        rec("sampler.sample", 2.0, 2.5, counters={"quilt.d2h_bytes": 5,
                                                  "quilt.kept_edges": 2}),
        rec("sampler.stream", 2.6, 2.9, counters={"quilt.d2h_bytes": 1}),
        rec("sampler.sample", 3.5, 3.6, counters={"quilt.d2h_bytes": 100}),
    ]
    monkeypatch.setattr(tracing, "records", lambda: list(recs))
    spans = instruments.Spans()
    spans.records += [("warmup", 0.5, 1.6), ("window", 2.0, 3.0)]
    inside = pt.window_records(spans)
    assert [r.t0_ns for r in inside] == [2_100_000_000, 2_000_000_000, 2_600_000_000]
    assert pt.counters(inside) == {"quilt.d2h_bytes": 6, "quilt.kept_edges": 2}
    assert pt.trace_of(inside) is None
    spans.records[-1] = ("window", 4.0, 5.0)
    assert pt.window_records(spans) is None


def test_a_program_without_tracing_has_no_window(monkeypatch):
    import sys

    import repro

    # the parent program: no repro.tracing to import
    monkeypatch.delattr(repro, "tracing", raising=False)
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    spans = instruments.Spans()
    spans.records.append(("window", 0.0, 1e9))
    assert pt.window_records(spans) is None
    assert pt.window({"spans": spans, "records": []}) is None


PROGRAM_SOURCES = ("program_counter", "program_span")


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_a_traced_run_reports_each_program_metric_of_its_cell(cell, trace, tmp_path):
    cfg, mix, e2e, layer = _tiny(cell)
    res = run.run(cfg, mix, e2e, layer, seed=2**33 + 5, seconds=0.2, trace=trace,
                  require_tpu=False, trace_dir=tmp_path / "trace",
                  log=io.StringIO())
    line = json.loads(json.dumps(res))
    assert line["correct"] is True
    mine = [m["name"] for m in layer if m["source"] in PROGRAM_SOURCES]
    assert mine
    metrics = line["metrics"]
    if not trace:
        assert not set(mine) & set(metrics)
        return
    for name in mine:
        assert name in metrics, name
        assert metrics[name]["value"] >= 0
    by_base = {n.split(".")[0]: metrics[n]["value"] for n in mine}
    assert by_base["copy_mb_per_graph"] > 0 and by_base["copy_gbps"] > 0
    if "slot_yield_pct" in by_base:
        assert 0 < by_base["slot_yield_pct"] <= 100
