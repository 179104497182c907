"""The chip path's Pallas kernels compile for a TPU v5e, at chip-smoke sizes.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
casts it has no lowering for, gathers that are not 2-D, 64-bit block
indices, more VMEM than a kernel may use.  These tests hand the kernels to
the TPU compiler for a described (not attached) ``v5e:2x2`` topology, with
``interpret=False``, at the shapes ``chip_smoke.py`` drives: the paper's
main line at n = 2^16 (d = 16, B = 8, 64 block-pair graphs of 1,259,001
exact-cell proposals each).  Nothing runs; each kernel compile takes a
second or two.  The topology is described inside a fixture, never at
import, so every test worker collects the same tests.

The whole device round of every engine that reads
``kpgm.DEVICE_MAX_CANDIDATES`` is compiled at that cap too, and its
``memory_analysis()`` must leave headroom in one v5e's 16 GiB of HBM.
Those compiles hold the int64 dedup sorts and take 1-3 minutes each.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import balldrop, kpgm, quilt
from repro.kernels import magm_logprob as ml
from repro.kernels import quadrant_descent as qd

D = 16  # d = log2 n at n = 2^16
GRAPHS = 64  # B^2 with B = 8
A_TOT = 1_259_001  # exact-cell proposals per graph (plan.exact_budget)
B = 8
# one round's device memory must stay under this share of a v5e's HBM: the
# plan, the attribute tables and the previous round's outputs live there too
HBM_BYTES = 16 << 30
ROUND_HBM_SHARE = 0.85


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, x64=False):
    with jax.enable_x64(x64):
        return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize(
    "ranks,gc,a_tot,lookup",
    [(False, GRAPHS, A_TOT, False), (True, 1, 7_990_894, False),
     (False, GRAPHS, A_TOT, True)],
    ids=["quilt", "balldrop", "quilt_lookup"],
)
def test_descent_kernel_compiles_for_v5e(one_chip, ranks, gc, a_tot, lookup):
    """The counter-PRNG descent kernel of the quilt round (64 graphs), of
    the ball-dropping round (one sample, rank channels on) and of the quilt
    round that looks configs up in the kernel, in the (B, 2^d) inverse."""

    def fn(seed, gids, cum, *inv):
        return qd.descent_prng(
            seed, gids, cum, a_tot=a_tot, num_blocks=B, ranks=ranks,
            interpret=False, inv=inv[0] if inv else None,
        )

    inv = (_spec(one_chip, (B, 1 << D), jnp.int32),) if lookup else ()
    # the engines dispatch their rounds under the x64 context
    compiled = _compile(
        fn,
        _spec(one_chip, (1, 2), jnp.int32),
        _spec(one_chip, (gc,), jnp.int32),
        _spec(one_chip, (D, 4), jnp.float32),
        *inv,
        x64=True,
    )
    assert "tpu_custom_call" in compiled.as_text()
    out = compiled.memory_analysis().output_size_in_bytes
    assert out >= (4 if ranks or lookup else 2) * gc * a_tot * 4


def test_single_graph_descent_compiles_for_v5e(one_chip):
    """``quadrant_descent_prng`` — the KPGM batch sampler's kernel — in both
    its counter-hash and its hardware-PRNG form."""
    for native in (False, True):
        compiled = _compile(
            lambda seed, cum, native=native: qd.quadrant_descent_prng(
                seed, cum, num_slots=1 << 20, interpret=False,
                tpu_native=native,
            ),
            _spec(one_chip, (1, 2), jnp.int32),
            _spec(one_chip, (D, 4), jnp.float32),
        )
        assert "tpu_custom_call" in compiled.as_text()


def test_magm_logprob_compiles_for_v5e(one_chip):
    """The bilinear log-Q tile kernel at d padded to 128 lanes."""
    m = n = 4096
    compiled = _compile(
        lambda fs, ft, u, v, w, c0: ml.magm_logprob(
            fs, ft, u, v, w, c0, interpret=False
        ),
        _spec(one_chip, (m, 128), jnp.float32),
        _spec(one_chip, (n, 128), jnp.float32),
        _spec(one_chip, (1, 128), jnp.float32),
        _spec(one_chip, (1, 128), jnp.float32),
        _spec(one_chip, (1, 128), jnp.float32),
        _spec(one_chip, (1, 1), jnp.float32),
    )
    assert "tpu_custom_call" in compiled.as_text()


def _round_at_cap(family, spec):
    """(program, argument specs, candidates) of one engine's device round
    holding ``kpgm.DEVICE_MAX_CANDIDATES`` candidates at n = 2^16 widths,
    or, for the one-graph split round, as many as the counter-PRNG slot
    limit allows."""
    cap = kpgm.DEVICE_MAX_CANDIDATES
    key = spec((2,), jnp.uint32)
    cum = spec((D, 4), jnp.float32)
    thetas = spec((D, 2, 2), jnp.float32)
    inv = (spec((B, 1 << D), jnp.int32),)
    if family in ("quilt", "balldrop"):
        # 64 graphs: the quilt's block pairs, or a fused batch of 64
        # ball-dropping samples (more graphs per round need more memory
        # per candidate than one large graph does)
        a_tot = cap // GRAPHS
        if family == "balldrop":
            fn = balldrop._compiled_bd_round(
                None, (), (a_tot,), B, D, True, 1, True
            )
        else:
            fn = quilt._compiled_round(None, (), (a_tot,), B, True, 1, True)
        g = spec((GRAPHS,), jnp.int32)
        return fn, (key, g, g, cum, thetas, inv), GRAPHS * a_tot
    if family == "split_heavy":
        budget = min(cap, qd.PRNG_SLOT_LIMIT)
        blocks = 4096
        i32 = spec((blocks,), jnp.int32)
        fn = quilt._compiled_split_heavy(budget, D)
        args = (
            key, spec((1 << D,), jnp.int32), i32, i32, i32, i32,
            spec((blocks,), jnp.float32), spec((blocks,), jnp.float64),
        )
        return fn, args, budget
    assert family == "kpgm_many"
    fn = jax.jit(functools.partial(kpgm._many_round, num_candidates=cap))
    g = spec((GRAPHS,), jnp.int32)
    return fn, (key, thetas, g, g), cap


@pytest.mark.parametrize(
    "family", ["quilt", "balldrop", "split_heavy", "kpgm_many"]
)
def test_round_at_the_cap_fits_one_v5e(one_chip, family):
    """Every device round sized by ``kpgm.DEVICE_MAX_CANDIDATES`` (the
    exact-cell quilt round, the fused ball-dropping round, the split
    sampler's heavy round and ``kpgm_sample_many``'s fused round) fits one
    v5e at the cap, by the compiler's own account."""
    fn, args, cand = _round_at_cap(
        family, functools.partial(_spec, one_chip)
    )
    with jax.enable_x64(True):
        mem = fn.lower(*args).compile().memory_analysis()
    total = (
        mem.temp_size_in_bytes
        + mem.output_size_in_bytes
        + mem.argument_size_in_bytes
    )
    assert cand >= min(kpgm.DEVICE_MAX_CANDIDATES, qd.PRNG_SLOT_LIMIT)
    assert total <= ROUND_HBM_SHARE * HBM_BYTES, (
        f"{family}: {total} B for {cand} candidates "
        f"({total / cand:.1f} B each)"
    )
