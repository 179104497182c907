#!/usr/bin/env python3
"""Readings that set the check's limits, for one cell, on the chip.

    python3 bench/control.py --workload <cell> --seeds S1 S2 ... [--graphs N]

For each seed it builds the cell's sampler, warms it up, and samples
``--graphs`` graphs through the cell's traffic call.  Each graph is read
twice by the check's own comparison (``bench.reference.compare``):

- program: the program's edges against the float32 reference (the lower
  reading: what sound runs give);
- control: the same reference computed in bfloat16, put in the program's
  place, against the float32 reference (the upper reading: what the step
  below the configuration's precision gives).

One JSON line per graph, then a summary line with the largest program
reading and the smallest control reading of each compared number.  The
benchmark's own runs do not run this.
"""

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def readings(cfg: dict, mix: dict, seeds, graphs: int):
    """Per-graph (seed, graph, program reading, control reading)."""
    import jax.numpy as jnp

    from bench import generator, instruments, reference, run, samplers

    spans = instruments.Spans()
    out = []
    for seed in seeds:
        target = samplers.build(cfg, seed)
        base = samplers.base_key(seed)
        generator.one_graph(target, mix, samplers.graph_key(base, run.WARM_INDEX), spans)
        for g in range(graphs):
            key = samplers.graph_key(base, g)
            rec = generator.one_graph(target, mix, key, spans)
            ref, _ = target.check(key, jnp.float32)
            low, _ = target.check(key, jnp.bfloat16)
            out.append({
                "seed": seed, "graph": g, "failed": rec["failed"],
                "edges": rec["num_edges"],
                "program": reference.compare(generator.edges(rec), ref),
                "control": reference.compare(low, ref),
            })
        run.free(target)
    return out


def summary(rows) -> dict:
    from bench import reference

    ok = [r for r in rows if not r["failed"]]
    return {
        name: {
            "program_max": max((r["program"][name] for r in ok), default=None),
            "control_min": min((r["control"][name] for r in rows), default=None),
        }
        for name in reference.LIMITS
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--graphs", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import run

    wl, cfg, mix, _, _ = run.load_cell(args.workload)
    run.chip_devices(int(wl["chips"]))
    run.enable_cache()
    rows = readings(cfg, mix, args.seeds, args.graphs)
    for r in rows:
        print(json.dumps(r), flush=True)
    print(json.dumps({"summary": summary(rows)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
