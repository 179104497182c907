"""BENCHMARK.json against the benchmark's format rules, every cell resolvable
by name, the result line's format, and the refusals."""

import io
import json
import pathlib
import re
import shutil
import subprocess
import sys

import pytest

from bench import generator, run

ROOT = pathlib.Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys_and_limits():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024


def test_names_units_and_lines():
    named = SPEC["configs"] + SPEC["workloads"] + SPEC["end_to_end"] + SPEC["per_layer"]
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in SPEC[kind]]
        assert len(names) == len(set(names)), kind
    for e in named:
        assert NAME.match(e["name"]), e["name"]
        for key in ("why", "layer", "source"):
            if key in e:
                assert 1 <= len(e[key]) <= 200 and "\n" not in e[key] and "\t" not in e[key]
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")


def test_end_to_end_bounds_and_sources():
    names = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in names
    for m in SPEC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_per_layer_metrics_move_a_metric_their_cells_report():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", CELLS):
            assert cell in CELLS
            assert cell in moved.get("workloads", CELLS)
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_configs_are_used_and_their_files_lie_under_paths():
    used = {w["config"] for w in SPEC["workloads"]}
    files = [c["file"] for c in SPEC["configs"]]
    assert len(files) == len(set(files))
    for c in SPEC["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("bench/")
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert not any(k.endswith(("_dim", "_rank")) for k in c["reduced"])
        for key in c["reduced"]:
            assert cfg[key] != cfg["published"][key]


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves_by_name(cell):
    wl, cfg, mix, e2e, layer = run.load_cell(cell)
    assert wl["chips"] in (1, 4)
    generator.validate(mix)
    assert cfg["law"] in ("magm_exact_cells", "kpgm_first_distinct")
    reported = {m["name"] for m in e2e}
    assert "setup_s" in reported and len(reported) >= 2 and layer
    for m in layer:
        assert callable(run.reader(m["name"]).read)


def test_four_chip_cells_stay_within_half():
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(len(CELLS) // 2, 1)


def test_cli_refuses_a_cpu_device(monkeypatch, capsys):
    monkeypatch.setattr(sys, "path", list(sys.path))
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == ""
    assert "TPU" in out.err


def test_cli_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0 and proc.stdout == ""


def _tiny(cell):
    wl, cfg, mix, e2e, layer = run.load_cell(cell)
    if cfg["law"] == "magm_exact_cells":
        cfg = dict(cfg, log2_nodes=8, blocks=5)
    else:
        cfg = dict(cfg, scale=11)
    if "chunk_edges" in mix:
        mix = dict(mix, chunk_edges=128)
    return cfg, mix, e2e, layer


@pytest.mark.parametrize("trace", [0, 1])
def test_result_line_has_the_contract_keys(trace, tmp_path):
    cfg, mix, e2e, layer = _tiny("magm_theta1_n16.stream")
    res = run.run(cfg, mix, e2e, layer, seed=2**40 + 9, seconds=0.3, trace=trace,
                  require_tpu=False, trace_dir=tmp_path / "trace",
                  log=io.StringIO())
    line = json.loads(json.dumps(res))
    keys = list(line)
    assert keys[:3] == ["correct", "attempted", "failed"] and keys[-1] == "checks"
    assert {"metrics", "device"} <= set(keys)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    for v in line["checks"].values():
        assert set(v) == {"value", "limit"}
    for name, m in line["metrics"].items():
        assert set(m) == {"value", "unit"}
    dev = line["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    if trace:
        assert {"busy_s", "window_s"} <= set(dev)
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        assert set(line["metrics"]) <= {m["name"] for m in layer}
        (compiles,) = [n for n in line["metrics"] if n.startswith("window_compiles")]
        assert line["metrics"][compiles]["value"] == 0
    else:
        assert set(line["metrics"]) == {m["name"] for m in e2e}
