"""The reader of ``kernel_lookup_pct`` (and its stream twin), on a known
window, and on a program that counts no lookups in the kernel."""

import pytest

from bench import program_trace, run
from repro.core import transfer

SLOTS = 4 * 80_576_064


@pytest.fixture
def window(monkeypatch):
    """The window the reader sees, in place of the program's records."""
    box = {"window": {"counters": {"quilt.candidate_slots": SLOTS}}}
    monkeypatch.setattr(program_trace, "window", lambda ctx: box["window"])
    return box


CTX = {"records": [{}] * 4, "spans": None}


NAMES = ["kernel_lookup_pct", "kernel_lookup_pct.stream"]


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize(
    "looked_up,value", [(SLOTS, 100.0), (SLOTS // 4, 25.0), (None, 0.0)]
)
def test_reader_on_a_known_context(name, looked_up, value, window):
    """Every slot, a quarter, or none (a counter that did not rise is left
    out of the window's counters) looked up in the kernel."""
    if looked_up is not None:
        window["window"]["counters"]["quilt.kernel_lookup_slots"] = looked_up
    assert run.reader(name).read(CTX) == pytest.approx(value)


@pytest.mark.parametrize("name", NAMES)
def test_reader_reads_nothing_without_the_counter_or_the_window(
    name, window, monkeypatch
):
    reader = run.reader(name)
    with monkeypatch.context() as m:
        m.delitem(transfer.ENGINE_COUNTERS, "kernel_lookup_slots")
        assert reader.read(CTX) is None
    window["window"]["counters"]["quilt.kernel_lookup_slots"] = SLOTS
    assert reader.read(CTX) == pytest.approx(100.0)
    window["window"] = None
    assert reader.read(CTX) is None
    window["window"] = {"counters": {"quilt.candidate_slots": 0}}
    assert reader.read(CTX) is None
