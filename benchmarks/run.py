"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--fast] [--json BENCH_<tag>.json]

Emits ``name,us_per_call,derived`` CSV rows (benchmarks/common.emit) and,
with ``--json OUT``, writes the same rows as a JSON trajectory point so the
perf history accumulates across PRs (CI runs ``--fast --json``).
Figure map: bench_partition (Figs 5-7), bench_properties (Figs 8-9),
bench_scalability (Figs 10-11), bench_mu (Figs 12-13), bench_d (Fig 14),
bench_kernels (Pallas kernel rooflines), bench_serve (GraphServer
throughput / tail latency / overload shedding), bench_fit (MAGFIT E-step
cost per edge + EM iterations-to-converge).
"""

import argparse
import json
import platform
import subprocess
import sys
import time


def _git_rev():
    """HEAD's commit, asked before JAX loads: a process that holds the
    chip starts no children."""
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"],
            capture_output=True,
            text=True,
            timeout=30,
        ).stdout.strip() or None
    except (OSError, subprocess.SubprocessError):
        return None


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true", help="smaller sweeps")
    ap.add_argument("--only", default=None, help="run a single bench module")
    ap.add_argument(
        "--json",
        default=None,
        metavar="OUT",
        help="also write the CSV rows as a JSON trajectory file",
    )
    args = ap.parse_args()
    git_rev = _git_rev() if args.json else None

    from benchmarks import (
        bench_d,
        bench_fit,
        bench_kernels,
        bench_mu,
        bench_partition,
        bench_properties,
        bench_scalability,
        bench_serve,
        common,
    )
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    print("name,us_per_call,derived")
    suites = {
        "partition": lambda: bench_partition.run(max_d=12 if args.fast else 16),
        "properties": lambda: bench_properties.run(max_d=11 if args.fast else 13),
        "scalability": lambda: bench_scalability.run(max_d=11 if args.fast else 13),
        "mu": lambda: bench_mu.run(ds=(10,) if args.fast else (10, 12)),
        "d": lambda: bench_d.run(log_n=10 if args.fast else 12),
        "kernels": bench_kernels.run,
        "serve": lambda: bench_serve.run(
            d=8 if args.fast else 10, requests=8 if args.fast else 16
        ),
        "fit": lambda: bench_fit.run(log_n=10 if args.fast else 12),
    }
    t0 = time.time()
    for name, fn in suites.items():
        if args.only and name != args.only:
            continue
        print(f"# --- {name} ---", file=sys.stderr, flush=True)
        fn()

    if args.json:
        import jax

        record = {
            "schema": "qkg-bench-v1",
            "fast": args.fast,
            "only": args.only,
            "unix_time": t0,
            "wall_s": time.time() - t0,
            "platform": platform.platform(),
            "jax": jax.__version__,
            "backend": jax.default_backend(),
            "git_rev": git_rev,
            "rows": common.ROWS,
        }
        with open(args.json, "w") as f:
            json.dump(record, f, indent=1)
        print(
            f"# wrote {len(common.ROWS)} rows to {args.json}",
            file=sys.stderr,
            flush=True,
        )


if __name__ == "__main__":
    main()
