#!/usr/bin/env python3
"""Chip smoke test: drive the MAGM quilt sampler's main path on a TPU.

    python chip_smoke.py             # one chip, every phase below
    python chip_smoke.py --chips 4   # mesh="auto" over four chips vs one

One process owns the chip for the whole run and starts no child process.
Phases (one line each; any failed check exits non-zero before the result):

- device:   the first JAX device must be a TPU — there is no CPU fallback;
- main:     ``MAGMSampler`` on the paper's main line (Theta_1, mu=0.5,
            d = log2 n, attributes from ``--seed``) at n = 2^16, sampled
            cold and warm; checks the edge-count z-score against the exact
            conditional moments (``kron.edge_count_moments``), that no
            fallback counter moved and that the round program holds the
            Pallas kernel (``tpu_custom_call``);
- parity:   the kernel path equals the jnp twin (``use_kernel=False``) bit
            for bit, and the concatenated ``sample_stream`` equals
            ``sample()``;
- serve:    a ``GraphServer`` answers 4 requests, all ``ok``;
- balldrop, kpgm: the other two device backends sample once each.

With ``--chips 4`` only the main sampler runs, sharded with ``mesh="auto"``,
and its edges must equal the single-device run bit for bit with graphs
placed on all four devices.  The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Compile and sample seconds are printed as set-up and smoke timings; they
are not benchmark metrics.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent

# log2 n of each phase.  The main line is the paper's n = 2^16.  Balldrop
# runs at 2^14: at 2^16 one sample's exact-cell budget (B^2 x 1,259,001 =
# 8.06e7 slots) is past the counter-PRNG limit of 2^26 slots per graph
# (kernels.quadrant_descent.PRNG_SLOT_LIMIT), so it would take the counted
# exact_fallbacks path that the no-fallback check refuses.
MAIN_LOG_N = 16
BALLDROP_LOG_N = 14
KPGM_LOG_N = 16


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def counters_clean(quilt, balldrop) -> dict:
    """Every fallback counter of both engines (all must be 0)."""
    return {
        f"{name}.{k}": mod.DISPATCH_COUNTERS[k]
        for name, mod in (("quilt", quilt), ("balldrop", balldrop))
        for k in quilt.FALLBACK_COUNTERS
    }


def assert_no_fallbacks(phase, quilt, balldrop) -> None:
    c = counters_clean(quilt, balldrop)
    moved = {k: v for k, v in c.items() if v}
    check(not moved, f"fallback counters moved: {moved}")
    say(phase, f"fallback counters all 0 ({len(c)} checked)")


def main_config(seed, magm, SamplerConfig, jax, d=MAIN_LOG_N, **kw):
    from repro.configs.magm_paper import DEFAULT_MU, THETA_1

    return SamplerConfig(
        params=magm.make_params(THETA_1, mu=DEFAULT_MU, d=d),
        num_nodes=1 << d,
        attribute_key=jax.random.PRNGKey(seed),
        **kw,
    )


def round_program_text(jax, quilt, plan, key) -> str:
    """Compiled text of the exact-cell round the main phase ran (the
    persistent compilation cache serves the compile)."""
    tables = quilt.device_lookup(plan)
    budget = plan.exact_budget
    fn = quilt._compiled_round(
        None, (), (budget,), plan.B, True, len(tables), True
    )
    gids, tpad = quilt._pad_inputs(
        plan.num_graphs, plan.num_graphs, [budget] * plan.num_graphs
    )
    with jax.enable_x64(True):
        lowered = fn.lower(key, gids, tpad, plan.cum, plan.thetas, tables)
        return lowered.compile().as_text()


def run_one_chip(args, jax, np) -> None:
    from repro.api import KPGMSampler, MAGMSampler, SamplerConfig
    from repro.core import balldrop, kpgm, magm, quilt
    from repro.launch.serve import GraphServer

    # -- main ----------------------------------------------------------
    cfg = main_config(args.seed, magm, SamplerConfig, jax)
    t0 = time.perf_counter()
    sampler = MAGMSampler(cfg)
    t_plan = time.perf_counter() - t0
    plan = sampler.plan
    budget = plan.exact_budget
    check(budget is not None, "no exact-cell budget at this size")
    check(plan.inv is not None, "no dense inverse: parity needs plan.inv")
    say(
        "main",
        f"n={plan.n} d={plan.d} B={plan.B} G={plan.num_graphs} "
        f"proposals={plan.num_graphs * budget} "
        f"(plan build {t_plan:.3f}s, set-up)",
    )
    key = jax.random.PRNGKey(args.seed + 1)
    t0 = time.perf_counter()
    cold = sampler.sample(key)
    t_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    warm = sampler.sample(key)
    t_warm = time.perf_counter() - t0
    edges = warm.edges
    check(np.array_equal(cold.edges, edges), "cold and warm samples differ")
    e = int(edges.shape[0])
    z = (e - plan.bd_mean) / plan.bd_std
    say(
        "main",
        f"edges={e} E|E|={plan.bd_mean:.1f} sd={plan.bd_std:.1f} z={z:+.3f}",
    )
    check(abs(z) <= 4.0, f"edge-count z-score {z:+.3f} outside +-4")
    check(
        quilt.DISPATCH_COUNTERS["device_rounds"] >= 2,
        f"device rounds did not run: {quilt.DISPATCH_COUNTERS}",
    )
    assert_no_fallbacks("main", quilt, balldrop)
    t0 = time.perf_counter()
    text = round_program_text(jax, quilt, plan, key)
    t_text = time.perf_counter() - t0
    check("tpu_custom_call" in text, "round program has no tpu_custom_call")
    say("main", f"round program holds tpu_custom_call ({t_text:.1f}s to fetch)")
    say(
        "main",
        f"set-up: cold sample() {t_cold:.3f}s (compile included); "
        f"smoke timing: warm sample() {t_warm:.3f}s — not a metric",
    )

    # -- parity --------------------------------------------------------
    twin = MAGMSampler(
        main_config(args.seed, magm, SamplerConfig, jax, use_kernel=False)
    ).sample(key)
    check(
        np.array_equal(twin.edges, edges),
        f"kernel ({e}) and jnp twin ({twin.edges.shape[0]}) edges differ",
    )
    say("parity", f"kernel == jnp twin, bit-identical ({e} edges)")
    chunks = list(sampler.sample_stream(key, chunk_edges=1 << 16))
    streamed = np.concatenate(chunks) if chunks else np.zeros((0, 2))
    check(np.array_equal(streamed, edges), "stream concatenation != sample()")
    say("parity", f"sample_stream concat == sample() ({len(chunks)} chunks)")

    # -- serve ---------------------------------------------------------
    with GraphServer(sampler, chunk_edges=1 << 16) as server:
        futs = [
            server.submit(key=jax.random.PRNGKey(args.seed + 10 + i))
            for i in range(4)
        ]
        resps = [f.result() for f in futs]
    ok = sum(r.ok for r in resps)
    for i, r in enumerate(resps):
        n_e = -1 if r.edges is None else int(r.edges.shape[0])
        say(
            "serve",
            f"request {i}: {r.status} edges={n_e} "
            f"service {r.service_s:.3f}s (smoke timing)",
        )
    check(ok == 4, f"{ok}/4 serve requests ok: {[r.message for r in resps]}")
    say("serve", "4/4 requests ok")
    assert_no_fallbacks("serve", quilt, balldrop)

    # -- balldrop ------------------------------------------------------
    bd_cfg = main_config(
        args.seed, magm, SamplerConfig, jax, d=BALLDROP_LOG_N, backend="balldrop"
    )
    bd = MAGMSampler(bd_cfg)
    rounds0 = balldrop.DISPATCH_COUNTERS["device_rounds"]
    t0 = time.perf_counter()
    gs = bd.sample(key)
    t_bd = time.perf_counter() - t0
    zb = (gs.num_edges - bd.plan.bd_mean) / bd.plan.bd_std
    say(
        "balldrop",
        f"n={bd.n} edges={gs.num_edges} z={zb:+.3f} "
        f"({t_bd:.3f}s incl. compile, set-up)",
    )
    check(abs(zb) <= 4.0, f"balldrop z-score {zb:+.3f} outside +-4")
    check(
        balldrop.DISPATCH_COUNTERS["device_rounds"] > rounds0,
        "balldrop ran no device round",
    )
    assert_no_fallbacks("balldrop", quilt, balldrop)

    # -- kpgm ----------------------------------------------------------
    from repro.configs.magm_paper import THETA_1

    kp = KPGMSampler(
        SamplerConfig(params=kpgm.make_params(THETA_1, d=KPGM_LOG_N))
    )
    rounds0 = quilt.DISPATCH_COUNTERS["device_rounds"]
    t0 = time.perf_counter()
    gk = kp.sample(key)
    t_kp = time.perf_counter() - t0
    flat = gk.edges[:, 0].astype(np.int64) * kp.n + gk.edges[:, 1]
    check(np.unique(flat).size == flat.size, "KPGM edges not deduped")
    check(
        gk.stats is not None and gk.num_edges == gk.stats.target_edges,
        f"KPGM sampled {gk.num_edges} edges, target {gk.stats}",
    )
    check(
        quilt.DISPATCH_COUNTERS["device_rounds"] > rounds0,
        "KPGM ran no device round",
    )
    say(
        "kpgm",
        f"n={kp.n} edges={gk.num_edges} == target "
        f"({t_kp:.3f}s incl. compile, set-up)",
    )
    assert_no_fallbacks("kpgm", quilt, balldrop)


def run_four_chips(args, jax, np) -> None:
    from repro.api import MAGMSampler, SamplerConfig
    from repro.core import balldrop, magm, quilt

    check(len(jax.devices()) >= 4, f"need 4 devices, have {jax.devices()}")
    key = jax.random.PRNGKey(args.seed + 1)
    sharded = MAGMSampler(main_config(args.seed, magm, SamplerConfig, jax, mesh="auto"))
    plan = sharded.plan
    say(
        "mesh",
        f"n={plan.n} B={plan.B} G={plan.num_graphs} mesh="
        f"{dict(sharded.mesh.shape)}",
    )
    t0 = time.perf_counter()
    run = quilt.quilt_run(key, plan, mesh=sharded.mesh)
    t_run = time.perf_counter() - t0
    mesh_edges = run.edges()
    per_dev = {}
    (part,) = run.parts
    for shard in part.snode.addressable_shards:
        sl = shard.index[0]
        rows = int(shard.data.shape[0])
        graphs = rows // run.slots_per_graph
        per_dev[shard.device.id] = (rows, int(part.keep[sl].sum()))
        say(
            "mesh",
            f"device {shard.device.id} ({shard.device.device_kind}): "
            f"{graphs} graphs, {rows} candidate rows, "
            f"{per_dev[shard.device.id][1]} kept edges",
        )
    check(len(per_dev) == 4, f"round placed on {len(per_dev)} devices")
    check(
        all(rows > 0 for rows, _ in per_dev.values()),
        f"a device holds no graph rows: {per_dev}",
    )
    gs = sharded.sample(key)
    check(np.array_equal(gs.edges, mesh_edges), "session != engine on mesh")
    single = MAGMSampler(main_config(args.seed, magm, SamplerConfig, jax)).sample(key)
    check(
        np.array_equal(single.edges, mesh_edges),
        f"4-chip ({mesh_edges.shape[0]}) and 1-chip "
        f"({single.edges.shape[0]}) edges differ",
    )
    say(
        "mesh",
        f"4-chip edges == 1-chip edges, bit-identical "
        f"({mesh_edges.shape[0]} edges; first sharded run {t_run:.3f}s "
        "incl. compile, set-up)",
    )
    assert_no_fallbacks("mesh", quilt, balldrop)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    src = HERE / "src"
    if not (src / "repro").is_dir():
        print(f"chip_smoke: no repro package under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))

    import jax
    import numpy as np

    devices = jax.devices()
    dev0 = devices[0]
    say(
        "device",
        f"platform={dev0.platform} kind={dev0.device_kind} "
        f"count={len(devices)} jax={jax.__version__}",
    )
    if dev0.platform != "tpu":
        print("chip_smoke: no TPU found; refusing to run", file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache

    say("device", f"compilation cache: {enable_compile_cache()}")
    try:
        if args.chips == 4:
            run_four_chips(args, jax, np)
        else:
            run_one_chip(args, jax, np)
    except SmokeFailure as exc:
        print(f"chip_smoke: FAILED: {exc}", file=sys.stderr)
        return 1
    print(
        json.dumps(
            {
                "ok": True,
                "device": {
                    "platform": dev0.platform,
                    "kind": dev0.device_kind,
                    "count": len(devices),
                },
            }
        )
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
