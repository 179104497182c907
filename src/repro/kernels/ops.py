"""Public jit'd wrappers around the Pallas kernels.

These handle shape padding (edge-axis to TILE, attribute-axis to the 128-lane
MXU width, tile axes to (BM, BN)), parameter packing for the bilinear form,
and the interpret-mode switch (``INTERPRET`` is False exactly when JAX's
default backend is a TPU).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import magm
from repro.kernels import bernoulli_tile as _bt
from repro.kernels import magm_logprob as _ml
from repro.kernels import quadrant_descent as _qd

# Pallas kernels compile natively only on a TPU; every other platform runs
# them in interpret mode (and the engines default to the jnp twins there).
INTERPRET = jax.default_backend() != "tpu"

# Opt-in for the hardware-PRNG kernel variant (pltpu.prng_random_bits) on a
# real TPU; the default counter-hash kernels are portable AND bit-identical
# to the jnp fallback, so they stay the default even on TPU.
TPU_NATIVE_PRNG = False

# counter-PRNG derivation helpers, re-exported for the core engines so the
# jnp fallback paths share the kernels' exact integer math (bit-identity)
PRNG_CHANNELS = _qd.PRNG_CHANNELS
PRNG_SLOT_LIMIT = _qd.PRNG_SLOT_LIMIT
counter_seed = _qd.counter_seed
counter_hash = _qd.counter_hash
counter_u01 = _qd.counter_u01
counter_rank = _qd.counter_rank
descent_uniforms = _qd.descent_uniforms
rank_pair = _qd.rank_pair
KERNEL_LOOKUP_MAX_ROW = _qd.KERNEL_LOOKUP_MAX_ROW


def _pad_to(x: jax.Array, axis: int, mult: int, value=0.0) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def sample_edge_batch_pallas(
    key: jax.Array, thetas: jax.Array, num_edges: int
) -> Tuple[jax.Array, jax.Array]:
    """Pallas-accelerated Algorithm-1 batch (drop-in for kpgm.sample_edge_batch)."""
    d = thetas.shape[0]
    flat = thetas.reshape(-1, 4)
    cum = jnp.cumsum(flat / jnp.sum(flat, axis=1, keepdims=True), axis=1)
    padded = num_edges + ((-num_edges) % _qd.TILE)
    u = jax.random.uniform(key, (padded, d))
    src, dst = _qd.quadrant_descent(u, cum, interpret=INTERPRET)
    return src[:num_edges], dst[:num_edges]


def sample_edge_batch_prng(
    key: jax.Array,
    thetas: jax.Array,
    num_edges: int,
    *,
    tpu_native: bool = None,
) -> Tuple[jax.Array, jax.Array]:
    """Counter-PRNG Algorithm-1 batch: no HBM uniforms operand at all.

    Same law as :func:`sample_edge_batch_pallas` (chi-square + 3-sigma
    validated, NOT bit-compatible with the threefry uniform stream).
    ``tpu_native=None`` follows the module flag ``TPU_NATIVE_PRNG``;
    explicitly passing True on a CPU backend raises (no interpret lowering
    for pltpu.prng_random_bits).
    """
    d = thetas.shape[0]
    flat = thetas.reshape(-1, 4)
    cum = jnp.cumsum(flat / jnp.sum(flat, axis=1, keepdims=True), axis=1)
    padded = num_edges + ((-num_edges) % _qd.TILE)
    if tpu_native is None:
        tpu_native = TPU_NATIVE_PRNG and not INTERPRET
    src, dst = _qd.quadrant_descent_prng(
        _qd.counter_seed(key),
        cum,
        num_slots=padded,
        interpret=INTERPRET,
        tpu_native=tpu_native,
    )
    return src[:num_edges], dst[:num_edges]


def quilt_descent_lookup_pallas(
    uniforms: jax.Array,
    cumprobs: jax.Array,
    kb: jax.Array,
    lb: jax.Array,
    table_cfg: jax.Array,
    table_node: jax.Array,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fused descent + block lookup (drop-in device step of quilt_sample).

    Pads the candidate axis to TILE (padding candidates search block 0 and
    are sliced off) and flips interpret mode per backend.  Note the CPU
    interpret path is for validation-scale inputs: the quilt hot loop calls
    the kernel only when a real TPU backend is present and otherwise uses the
    jnp dense-inverse lookup (core/quilt.py), exactly as kpgm.sample_edge_batch
    does for the plain descent kernel.
    """
    n = uniforms.shape[0]
    u = _pad_to(uniforms, 0, _qd.TILE)
    kb2 = _pad_to(kb.reshape(-1, 1).astype(jnp.int32), 0, _qd.TILE)
    lb2 = _pad_to(lb.reshape(-1, 1).astype(jnp.int32), 0, _qd.TILE)
    scfg, dcfg, snode, dnode = _qd.quilt_descent_lookup(
        u, cumprobs, kb2, lb2, table_cfg, table_node, interpret=INTERPRET
    )
    return scfg[:n], dcfg[:n], snode[:n], dnode[:n]


def descent_prng_pallas(
    seed: jax.Array,
    gids: jax.Array,
    cumprobs: jax.Array,
    *,
    a_tot: int,
    num_blocks: int = 1,
    ranks: bool = False,
    inv: Optional[jax.Array] = None,
) -> Tuple[jax.Array, ...]:
    """Counter-PRNG descent kernel of the quilt/balldrop/KPGM rounds.

    The kernel derives (graph, slot, uniforms[, block ranks]) from its grid
    position, the (1, 2) seed and the (gc,) graph ids, and returns
    ``(src_cfg, dst_cfg[, kb, lb])`` per candidate.  Given the plan's dense
    (B, W) inverse ``inv``, W at most :data:`KERNEL_LOOKUP_MAX_ROW`, it
    looks the configs up itself, in each graph's two block rows held in
    VMEM, and returns ``(src_cfg, dst_cfg, src_node, dst_node)``; without
    it the engines map configs to nodes with an XLA gather.  Bit-identical
    to the jnp twin assembled from :func:`descent_uniforms` /
    :func:`rank_pair` and that gather (the kernel path/jnp path parity
    tests rely on it).
    """
    return _qd.descent_prng(
        seed,
        gids,
        cumprobs,
        a_tot=a_tot,
        num_blocks=num_blocks,
        ranks=ranks,
        interpret=INTERPRET,
        inv=inv,
    )


def _packed_bilinear(thetas: jax.Array, d_pad: int):
    bl = magm.bilinear_decompose(thetas)
    u = _pad_to(bl.u[None, :], 1, d_pad)
    v = _pad_to(bl.v[None, :], 1, d_pad)
    w = _pad_to(bl.w[None, :], 1, d_pad)
    c0 = bl.c0.reshape(1, 1)
    return u, v, w, c0


def magm_logprob_pallas(
    F_src: jax.Array, F_dst: jax.Array, thetas: jax.Array
) -> jax.Array:
    """(ns, d), (nt, d) attributes -> (ns, nt) log Q via the MXU tile kernel."""
    ns, nt = F_src.shape[0], F_dst.shape[0]
    fs = _pad_to(_pad_to(F_src.astype(jnp.float32), 0, _ml.BM), 1, 128)
    ft = _pad_to(_pad_to(F_dst.astype(jnp.float32), 0, _ml.BN), 1, 128)
    u, v, w, c0 = _packed_bilinear(thetas, 128)
    out = _ml.magm_logprob(fs, ft, u, v, w, c0, interpret=INTERPRET)
    return out[:ns, :nt]


def bernoulli_sample_pallas(
    key: jax.Array, F_src: jax.Array, F_dst: jax.Array, thetas: jax.Array
) -> jax.Array:
    """Fused naive-baseline tile: int8 adjacency block sampled from Q."""
    ns, nt = F_src.shape[0], F_dst.shape[0]
    fs = _pad_to(_pad_to(F_src.astype(jnp.float32), 0, _bt.BM), 1, 128)
    ft = _pad_to(_pad_to(F_dst.astype(jnp.float32), 0, _bt.BN), 1, 128)
    u, v, w, c0 = _packed_bilinear(thetas, 128)
    logu = jnp.log(
        jax.random.uniform(
            key, (fs.shape[0], ft.shape[0]), minval=1e-38, maxval=1.0
        )
    )
    out = _bt.bernoulli_tile(fs, ft, u, v, w, c0, logu, interpret=INTERPRET)
    return out[:ns, :nt]
