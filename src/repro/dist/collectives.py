"""Compressed cross-pod collectives.

The inter-pod links are ~10x slower than in-pod ICI, and the inter-pod
gradient all-reduce is pure DP traffic (identical tree structure on every
pod), so it tolerates lossy compression: gradients are quantised to int8
with STOCHASTIC rounding (unbiased: E[q * scale] = x, so momentum averages
out the quantisation noise instead of accumulating bias).

The reduction is an all-gather of the int8 payload plus one f32 scale per
device, followed by a local dequantise-and-mean: the wire carries 1 byte per
element per peer instead of the ~4 bytes per element a f32 ring all-reduce
moves, and the inter-pod axis is tiny (2 pods), so allgather(int8) is the
cheaper collective AND keeps per-device scales exact (no shared-scale
clipping).

``compressed_psum_mean`` is the per-device primitive — call it INSIDE an
existing shard_map / jitted step where each device holds its own gradient
values.  ``compressed_grad_allreduce`` is the eager single-controller entry:
it wraps the primitive in one shard_map over the whole (flattened) tree, so
a replicated host-side tree is reduced with ONE traced program regardless of
leaf count.  Note that an eager replicated input is by construction
identical on every device; per-device-distinct gradients only exist inside
a sharded step, which is where the primitive belongs (ROADMAP: wire into
the train step across real pods).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P



def _stochastic_round_int8(x: jax.Array, key: jax.Array):
    """Quantise to int8 with an unbiased stochastic round.

    Returns (q int8, scale f32) with E[q * scale] = x.  The scale is the
    per-leaf absmax / 127 so the representable range is never clipped.
    """
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf))
    scale = jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.float32)
    y = xf / scale
    lo = jnp.floor(y)
    frac = y - lo
    u = jax.random.uniform(key, x.shape)
    q = lo + (u < frac).astype(jnp.float32)
    return jnp.clip(q, -127.0, 127.0).astype(jnp.int8), scale


def compressed_psum_mean(
    leaf: jax.Array, key: jax.Array, axis: str, axis_size: int
) -> jax.Array:
    """Per-device primitive: int8-compressed mean of ``leaf`` over ``axis``.

    Must run inside shard_map / jit with ``axis`` bound.  The key is folded
    with the device's axis index so rounding noise is uncorrelated across
    the reduction; only the int8 payload and one f32 scale per device cross
    the link.
    """
    k = jax.random.fold_in(key, jax.lax.axis_index(axis))
    q, scale = _stochastic_round_int8(leaf, k)
    q_all = jax.lax.all_gather(q, axis)  # (n, ...) int8 on the wire
    scale_all = jax.lax.all_gather(scale, axis)  # (n,) f32
    deq = q_all.astype(jnp.float32) * scale_all.reshape(
        (axis_size,) + (1,) * leaf.ndim
    )
    return jnp.sum(deq, axis=0) / axis_size


def compressed_grad_allreduce(
    grads: Any, key: jax.Array, mesh, axis: str = "pod"
) -> Any:
    """Mean of a (replicated) gradient tree over ``axis`` via int8 payloads.

    One shard_map over the flattened tree: a single traced program per
    treedef, not per leaf.
    """
    n = mesh.shape[axis]
    leaves, treedef = jax.tree.flatten(grads)
    keys = tuple(jax.random.split(key, max(len(leaves), 1)))

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(), P()),
        out_specs=P(),
        check_vma=False,
    )
    def reduce_all(leaf_tuple, key_tuple):
        return tuple(
            compressed_psum_mean(leaf, k, axis, n)
            for leaf, k in zip(leaf_tuple, key_tuple)
        )

    out = reduce_all(tuple(leaves), keys)
    out = [r.astype(leaf.dtype) for r, leaf in zip(out, leaves)]
    return treedef.unflatten(out)
