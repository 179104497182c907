"""The check that decides ``correct``: its reference agrees with the
program, its control does not, and a run whose timed path is broken reads
not correct.  Small sizes on the CPU; the chip readings are in PERF.md."""

import io

import numpy as np
import pytest

from bench import control, reference, run
from repro.core import quilt

CELLS = ["magm_theta1_n16.whole", "graph500_s20.whole", "magm_theta1_n16.stream"]


def _tiny(cell):
    wl, cfg, mix, e2e, layer = run.load_cell(cell)
    if cfg["law"] == "magm_exact_cells":
        cfg = dict(cfg, log2_nodes=8, blocks=5)
    else:
        cfg = dict(cfg, scale=11)
    if "chunk_edges" in mix:
        mix = dict(mix, chunk_edges=128)
    return cfg, mix, e2e, layer


@pytest.mark.parametrize("cell", CELLS[:2])
def test_program_agrees_and_control_does_not(cell):
    cfg, mix, _, _ = _tiny(cell)
    rows = control.readings(cfg, mix, seeds=[3, 2**36 + 1, 77], graphs=1)
    assert len(rows) == 3 and not any(r["failed"] for r in rows)
    for r in rows:
        assert r["program"] == {"edges_off": 0, "order_off": 0}
        # the bfloat16 control fails the exact comparison
        assert r["control"]["edges_off"] > reference.LIMITS["edges_off"]
    s = control.summary(rows)
    assert s["edges_off"]["program_max"] == 0
    assert s["edges_off"]["control_min"] > 0


def _alter_one(edges):
    edges = np.array(edges, copy=True)
    if edges.shape[0]:
        edges[edges.shape[0] // 2, 1] += 1
    return edges


def _drop_half(edges):
    return edges[: edges.shape[0] // 2]


def _stale():
    """Every call hands back the first graph the run made: a sampler whose
    state no longer moves with the key."""
    first = []

    def fault(edges):
        if not first:
            first.append(edges)
        return first[0]

    return fault


@pytest.mark.parametrize("fault", [lambda: _alter_one, lambda: _drop_half, _stale],
                         ids=["edge_altered", "half_left_out", "stale_graph"])
@pytest.mark.parametrize("cell", CELLS)
def test_broken_timed_path_reads_not_correct(cell, fault, monkeypatch, tmp_path):
    fault = fault()
    edges, chunks = quilt.QuiltRun.edges, quilt.QuiltRun.iter_chunks

    def broken_edges(self):
        return fault(edges(self))

    def broken_chunks(self, chunk_edges):
        whole = np.concatenate(list(chunks(self, chunk_edges)))
        return iter([fault(whole)])

    monkeypatch.setattr(quilt.QuiltRun, "edges", broken_edges)
    monkeypatch.setattr(quilt.QuiltRun, "iter_chunks", broken_chunks)
    cfg, mix, e2e, layer = _tiny(cell)
    res = run.run(cfg, mix, e2e, layer, seed=2**35 + 3, seconds=0.2, trace=0,
                  require_tpu=False, trace_dir=tmp_path, log=io.StringIO())
    assert res["correct"] is False
    assert res["checks"]["edges_off"]["value"] > 0


@pytest.mark.parametrize("sizes,expected", [
    ([5, 5, 5, 5, 5], 1),  # a fixed edge count: the drawn graph is a largest
    ([3, 9, 4, 2, 1], 2),  # the largest is checked besides the drawn one
])
def test_check_sample_holds_a_largest_graph(sizes, expected):
    sample = run.CheckSample(1, seed=2**33 + 5)
    for i, n in enumerate(sizes):
        sample(i, {"failed": False, "pieces": [], "num_edges": n})
    sample(len(sizes), {"failed": True, "pieces": [], "num_edges": 99})
    chosen = sample.graphs()
    assert len(chosen) == expected
    assert max(g["num_edges"] for _, g in chosen) == max(sizes)
