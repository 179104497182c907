"""Distributed MAGM/KPGM sampling with shard_map.

Two axes of parallelism, both embarrassingly parallel (DESIGN.md section 3.3):

1. *Edge-budget sharding*: Algorithm 1's X candidate edges are independent, so
   each device draws X/ndev edges with a folded key.  One all-gather of the
   fixed-shape (src, dst) buffers at the end.
2. *Block sharding*: Algorithm 2's B^2 KPGM draws are independent graphs; the
   (k, l) block list is round-robin assigned to devices.

On the production mesh this runs over the flattened (pod, data, model) axes —
sampling has no model-parallel structure, so every chip contributes pure
throughput.  The same code runs on 1 CPU device in tests.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import kpgm


def _device_sample(
    key: jax.Array, thetas: jax.Array, per_device: int
) -> Tuple[jax.Array, jax.Array]:
    """Per-device body: fold in the device index, draw a fixed-shape batch."""
    axis = jax.lax.axis_index("dev")
    key = jax.random.fold_in(key, axis)
    return kpgm.sample_edge_batch(key, thetas, per_device)


@functools.partial(jax.jit, static_argnames=("per_device", "mesh"))
def sample_edges_sharded(
    key: jax.Array, thetas: jax.Array, per_device: int, mesh: Mesh
) -> Tuple[jax.Array, jax.Array]:
    """Draw ndev * per_device edge candidates, one shard per device.

    Returns globally-sharded (src, dst) arrays of shape (ndev * per_device,);
    the caller (host) dedupes and tops up exactly as in kpgm.kpgm_sample.
    """
    flat_mesh = Mesh(
        np.asarray(mesh.devices).reshape(-1), axis_names=("dev",)
    )
    body = jax.shard_map(
        functools.partial(_device_sample, per_device=per_device),
        mesh=flat_mesh,
        in_specs=(P(), P()),
        out_specs=P("dev"),
    )
    src, dst = body(key, thetas)
    return src, dst


def kpgm_sample_distributed(
    key: jax.Array,
    params: kpgm.KPGMParams,
    mesh: Mesh,
    *,
    max_rounds: int = 8,
    oversample: float = 1.05,
) -> np.ndarray:
    """Distributed variant of kpgm.kpgm_sample: devices produce candidates,
    the host owns dedup/top-up (identical output distribution)."""
    thetas = params.thetas
    n = params.num_nodes
    ndev = int(np.prod(np.asarray(mesh.devices).shape))
    key, sub = jax.random.split(key)
    target = int(kpgm.sample_num_edges(sub, thetas))
    target = min(target, n * n)
    if target == 0:
        return np.zeros((0, 2), dtype=np.int64)

    seen = np.empty((0,), dtype=np.int64)
    for _ in range(max_rounds):
        need = target - seen.size
        if need <= 0:
            break
        key, sub = jax.random.split(key)
        per_device = max((int(need * oversample) + ndev - 1) // ndev, 8)
        src, dst = sample_edges_sharded(sub, thetas, per_device, mesh)
        flat = np.asarray(src) * n + np.asarray(dst)
        seen = np.unique(np.concatenate([seen, flat]))
    seen = seen[:target]
    return np.stack([seen // n, seen % n], axis=1)
