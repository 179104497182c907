"""The readers of the program's own counters and spans, on a known window."""

import pytest

from bench import program_trace, run

NEW = ("copy_mb_per_graph", "copy_gbps", "slot_yield_pct",
       "engine_idle_ms_per_graph", "mask_idle_ms_per_graph",
       "emit_idle_ms_per_graph")


def _window():
    from repro import tracing

    def rec(name, ms):
        return tracing.Record(name, 0, int(ms * 1e6), "quilt.run", 1, {})

    return {
        "counters": {
            "quilt.d2h_bytes": 4 * 725_184_576,
            "quilt.kept_edges": 4 * 1_219_734,
            "quilt.candidate_slots": 4 * 80_576_064,
        },
        # 2,900 ms of copies over the four graphs
        "records": [rec("quilt.copy", 1000), rec("quilt.copy", 1900),
                    rec("quilt.mask", 5000)],
        "attribution": {"idle_by_program_span_s": {
            "quilt.run": 0.1, "quilt.round": 0.02, "quilt.round_wait": 0.08,
            "quilt.mask": 0.6, "quilt.emit": 1.0, "stream.window": 0.2,
            "stream.rechunk": 0.04, "quilt.copy": 1.2, "none": 0.01,
        }},
    }


CTX = {"records": [{}] * 4, "spans": None}


@pytest.fixture
def window(monkeypatch):
    """The window the readers see, in place of the program's records."""
    box = {"window": _window()}
    monkeypatch.setattr(program_trace, "window", lambda ctx: box["window"])
    return box


@pytest.mark.parametrize("name,value", [
    ("copy_mb_per_graph", 725.184576),
    ("copy_gbps", 4 * 725_184_576 / 2.9 / 1e9),
    ("slot_yield_pct", 100.0 * 1_219_734 / 80_576_064),
    ("engine_idle_ms_per_graph", 50.0),
    ("mask_idle_ms_per_graph", 150.0),
    ("emit_idle_ms_per_graph", 310.0),
])
def test_reader_on_a_known_context(name, value, window):
    assert run.reader(name).read(CTX) == pytest.approx(value)
    if name != "slot_yield_pct":
        assert run.reader(name + ".stream").read(CTX) == pytest.approx(value)


@pytest.mark.parametrize("name", NEW)
def test_reader_of_a_program_without_tracing_reads_nothing(name, window):
    window["window"] = None
    assert run.reader(name).read(CTX) is None
    window["window"] = dict(_window(), attribution=None)
    if name.endswith("idle_ms_per_graph"):
        assert run.reader(name).read(CTX) is None
    if name.endswith("per_graph"):
        window["window"] = _window()
        assert run.reader(name).read(dict(CTX, records=[])) is None
