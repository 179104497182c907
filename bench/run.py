#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <config>.<mix> --seed N --seconds S --trace 0|1

Everything a cell needs is found by name: ``BENCHMARK.json`` names its
configuration file (``bench/configs/``) and its traffic mix
(``bench/traffic/<mix>.json``, read by ``bench/generator.py``), and each
per-layer metric is read by ``bench/metrics/<metric>.py``.

A run builds the sampler from the configuration and ``--seed``, warms up
with one whole graph (set-up ends there), then drives graphs back to back
for ``--seconds``.  After the window it reads the chip's peak memory,
frees the program's state, and checks a sample of the window's graphs,
drawn from the seed with the largest among them, against the plain
reference (``bench/reference.py``).  With ``--trace 1`` the window is
traced and the per-layer metrics are printed instead of the end-to-end
ones.  The last line of stdout is one JSON object; the numbers compared
by the check come last in it, and last on stderr.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
TRACE_DIR = ROOT / ".bench_trace"
# graphs of the window checked against the reference, besides the largest
CHECK_GRAPHS = 1
# graph index of the warm-up key; window graphs count up from 0
WARM_INDEX = 0xFFFFFFFF


class Failure(RuntimeError):
    """The run cannot produce a result (no chip, bad cell)."""


def load_cell(workload: str):
    """(workload entry, configuration, mix, end-to-end and per-layer metric
    entries) of one cell of ``BENCHMARK.json``."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise Failure(f"no workload {workload!r} in BENCHMARK.json")
    wl = cells[workload]
    entry = {c["name"]: c for c in spec["configs"]}[wl["config"]]
    cfg = json.loads((ROOT / entry["file"]).read_text())
    mix = json.loads((ROOT / "bench" / "traffic" / f"{wl['traffic']}.json").read_text())

    def mine(m):
        return workload in m.get("workloads", [workload])

    return (wl, cfg, mix, [m for m in spec["end_to_end"] if mine(m)],
            [m for m in spec["per_layer"] if mine(m)])


def enable_cache() -> str:
    """The program's persistent compilation cache (``JAX_COMPILATION_CACHE_DIR``
    when set, else the checkout's fixed ``.jax_cache``), keeping every
    program however quick its compile."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return where


class CheckSample:
    """The window graphs kept for the check: ``k`` drawn uniformly by
    reservoir from the seed, and one with the most edges."""

    def __init__(self, k: int, seed: int):
        import numpy as np

        self.k = k
        self.rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 0xC4EC])
        self.slots = []  # (index, {"pieces", "num_edges"})
        self.seen = 0
        self.largest = None

    def __call__(self, index: int, rec: dict) -> None:
        if rec["failed"]:
            return
        item = (index, {"pieces": rec["pieces"], "num_edges": rec["num_edges"]})
        if self.largest is None or rec["num_edges"] > self.largest[1]["num_edges"]:
            self.largest = item
        if len(self.slots) < self.k:
            self.slots.append(item)
        else:
            j = int(self.rng.integers(0, self.seen + 1))
            if j < self.k:
                self.slots[j] = item
        self.seen += 1

    def graphs(self):
        """The drawn graphs, and the largest unless one of them is as
        large (graphs of a fixed edge count are then checked once)."""
        chosen = dict(self.slots)
        most = max((g["num_edges"] for g in chosen.values()), default=-1)
        if self.largest is not None and self.largest[1]["num_edges"] > most:
            chosen[self.largest[0]] = self.largest[1]
        return sorted(chosen.items())


def chip_devices(chips: int):
    """The first ``chips`` TPU devices; no TPU, or too few, is a Failure."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise Failure(
            f"needs {chips} TPU chip(s); JAX sees {len(devices)} "
            f"{devices[0].platform} device(s)"
        )
    return devices[:chips]


def device_info(devices) -> dict:
    """Platform, kind, count, and the peak memory of the fullest chip: the
    peak of allocated buffers plus the peak of the memory XLA reserves for
    program temporaries, which ``peak_bytes_in_use`` leaves out."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0))
                     + int(stats.get("peak_bytes_reserved", 0)))
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": max(peaks),
    }


def end_to_end(records, t_open, t_close, setup_s, peak_bytes) -> dict:
    import statistics

    ok = [r for r in records if not r["failed"]]
    rate = sum(r["num_edges"] for r in ok) / (t_close - t_open)
    values = {
        "setup_s": setup_s,
        "edges_per_s": rate,
        "edges_per_s.stream": rate,
        "peak_hbm_gib": peak_bytes / 2**30,
    }
    if ok:
        values["first_chunk_s"] = statistics.fmean(r["t_first"] - r["t0"] for r in ok)
    return values


def reader(name: str):
    """The reader of one per-layer metric: ``bench/metrics/<name>.py``
    (a name may hold a ``.``, as ``descent_ms_per_graph.stream`` does)."""
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench.metrics.{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def per_layer(entries, ctx) -> dict:
    out = {}
    for m in entries:
        value = reader(m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def start_trace(path: pathlib.Path) -> None:
    import jax

    shutil.rmtree(path, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(path), profiler_options=opts)


def trace_file(path: pathlib.Path) -> str:
    found = sorted(path.glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise Failure(f"the profiler wrote no trace under {path}")
    return str(found[-1])


def free(target) -> None:
    """Drop every reference to the program's sampler and its device state."""
    target.sampler = target.sample = target.stream = None
    gc.collect()


def run(cfg, mix, e2e, layer, *, seed, seconds, trace, chips=1,
        require_tpu=True, trace_dir=TRACE_DIR, t_process=T_PROCESS,
        log=sys.stderr) -> dict:
    """One run of a cell; returns the result object (not yet printed)."""
    import jax
    import jax.numpy as jnp

    from bench import generator, instruments, reference, samplers
    from bench import trace_reduce, workcount

    t_run = time.perf_counter()

    generator.validate(mix)
    devices = chip_devices(chips) if require_tpu else jax.devices()[:chips]
    peak = workcount.peak(devices[0].device_kind) if require_tpu else None
    compiles = instruments.CompileLog()
    spans = instruments.Spans(annotate=bool(trace))

    with spans("plan_build"):
        target = samplers.build(cfg, seed)
    base = samplers.base_key(seed)
    with spans("warmup"):
        warm = generator.one_graph(
            target, mix, samplers.graph_key(base, WARM_INDEX), spans
        )
    warm["pieces"] = None
    setup_compiles = compiles.since_mark()
    if warm["failed"]:
        print(f"warm-up graph failed: {warm['error']}", file=log)

    from repro.core import quilt

    dispatch0 = dict(quilt.DISPATCH_COUNTERS)
    check = CheckSample(CHECK_GRAPHS, seed)
    if trace:
        start_trace(trace_dir)
    compiles.mark()
    setup_s = time.perf_counter() - t_process
    print(f"set-up parts: start {t_run - t_process:.3f} s (imports, chip, "
          f"cache), plan {spans.total('plan_build'):.3f} s, warm-up "
          f"{spans.total('warmup'):.3f} s, of {setup_s:.3f} s", file=log)
    with spans("window"):
        records, t_open, t_close = generator.drive(
            target, mix, lambda i: samplers.graph_key(base, i), seconds,
            spans, check,
        )
    if trace:
        jax.profiler.stop_trace()
    window_compiles = compiles.since_mark()["compiles"]
    dispatch = {k: v - dispatch0[k] for k, v in quilt.DISPATCH_COUNTERS.items()}
    device = device_info(devices)
    print(f"memory_stats {devices[0].memory_stats()}", file=log)
    free(target)
    for r in records:
        if r["error"]:
            print(f"graph failed: {r['error']}", file=log)

    checked = []
    for index, rec in check.graphs():
        ref, _ = target.check(samplers.graph_key(base, index), jnp.float32)
        checked.append(reference.compare(generator.edges(rec), ref))
    checks = {
        name: {"value": sum(c[name] for c in checked), "limit": limit}
        for name, limit in reference.LIMITS.items()
    }
    correct = bool(checked) and all(
        v["value"] <= v["limit"] for v in checks.values()
    )

    result = {
        "correct": correct,
        "attempted": len(records),
        "failed": sum(r["failed"] for r in records),
    }
    if trace:
        summary = trace_reduce.reduce_file(trace_file(trace_dir))
        ctx = {
            "records": records,
            "spans": spans,
            "dispatch": dispatch,
            "window_compiles": window_compiles,
            "trace": summary,
            "work": {"descent_bytes_per_graph": workcount.descent_bytes(
                workcount.candidates_per_graph(cfg))},
            "peak": peak,
        }
        result["metrics"] = per_layer(layer, ctx)
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        result["device"] = device
        result["breakdown"] = {
            "device_ops": summary["top_ops"],
            "idle_gaps": summary["idle_gaps"],
        }
    else:
        values = end_to_end(records, t_open, t_close, setup_s,
                            device["memory_peak_bytes"])
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in e2e
            if m["name"] in values
        }
        result["device"] = device
    print(
        f"set-up compiles {setup_compiles['compiles']} "
        f"(cache hits {setup_compiles['cache_hits']}, misses "
        f"{setup_compiles['cache_misses']}); window compiles "
        f"{window_compiles}; graphs checked {len(checked)}",
        file=log,
    )
    for name, v in checks.items():
        print(f"check {name} {v['value']} limit {v['limit']}", file=log)
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program under {ROOT / 'src'}", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    try:
        wl, cfg, mix, e2e, layer = load_cell(args.workload)
        chip_devices(int(wl["chips"]))
        enable_cache()
        result = run(cfg, mix, e2e, layer, seed=args.seed,
                     seconds=args.seconds, trace=args.trace,
                     chips=int(wl["chips"]))
    except Failure as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
