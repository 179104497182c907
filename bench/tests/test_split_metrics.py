"""The readers of ``split_fill_pct`` and ``split_idle_ms_per_graph`` on a
known window, and on a program without the split's counter or span."""

import pytest

from bench import program_trace, run
from repro import tracing
from repro.core import transfer

G = 7_173_333  # exact-cell proposals a block pair at n = 2^18
CTX = {"records": [{}] * 4, "spans": None}


def _rec(name, parent):
    return tracing.Record(name, 0, 1_000_000, parent, 1, {})


def _window(dispatched_graphs, split=True):
    records = [_rec("quilt.round", "quilt.split" if split else "quilt.run")]
    if split:
        records.append(_rec("quilt.split", "quilt.run"))
    return {
        "counters": {
            "quilt.candidate_slots": 4 * 64 * G,
            "quilt.dispatched_slots": 4 * dispatched_graphs * G,
        },
        "records": records,
        "attribution": {"idle_by_program_span_s": {
            "quilt.split": 0.2, "quilt.run": 0.1, "quilt.round": 0.05,
            "quilt.mask": 1.6,
        }},
    }


@pytest.fixture
def window(monkeypatch):
    """The window the readers see, in place of the program's records."""
    box = {"window": _window(65)}
    monkeypatch.setattr(program_trace, "window", lambda ctx: box["window"])
    return box


@pytest.mark.parametrize("graphs,value", [(65, 100.0 * 64 / 65), (64, 100.0)])
def test_fill_on_a_split_and_on_a_single_round(graphs, value, window):
    """Five sub-rounds of 13 block pairs, 64 real; one unpadded round."""
    window["window"] = _window(graphs, split=graphs != 64)
    assert run.reader("split_fill_pct").read(CTX) == pytest.approx(value)


def test_fill_reads_nothing_without_the_counter_or_the_window(window, monkeypatch):
    reader = run.reader("split_fill_pct")
    with monkeypatch.context() as m:
        m.delitem(transfer.ENGINE_COUNTERS, "dispatched_slots")
        assert reader.read(CTX) is None
    del window["window"]["counters"]["quilt.dispatched_slots"]
    assert reader.read(CTX) is None
    window["window"] = None
    assert reader.read(CTX) is None


def test_split_idle_reads_only_the_idle_under_the_split(window, monkeypatch):
    # 0.2 s of the four graphs' idle lay under quilt.split itself
    reader = run.reader("split_idle_ms_per_graph")
    assert reader.read(CTX) == pytest.approx(50.0)
    unsplit = _window(64, split=False)
    del unsplit["attribution"]["idle_by_program_span_s"]["quilt.split"]
    window["window"] = unsplit
    assert reader.read(CTX) == 0.0
    with monkeypatch.context() as m:
        m.delitem(transfer.ENGINE_COUNTERS, "dispatched_slots")
        window["window"] = _window(65)
        assert reader.read(CTX) is None
    window["window"] = dict(_window(65), attribution=None)
    assert reader.read(CTX) is None
    window["window"] = None
    assert reader.read(CTX) is None
