"""Device mesh construction and sharding helpers.

The reference scales via engine-internal NCCL plus router-level replicas
(SURVEY.md §2.10). TPU-native scaling is declarative: build a
``jax.sharding.Mesh`` over the slice, annotate shardings, and let XLA
lower collectives onto ICI. Axes:

- ``dp``   — data parallel (replica within one engine process; router-level
             replicas are separate processes as in the reference)
- ``tp``   — tensor parallel (heads / ffn)
- ``sp``   — sequence/context parallel (ring attention, long context)
- ``ep``   — expert parallel (MoE models)
"""

from __future__ import annotations

from typing import Sequence

import jax
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = [
    "build_mesh",
    "single_device_mesh",
    "shard",
    "shard_pytree",
    "largest_tp",
    "shard_map",
]


def build_mesh(
    tp: int = 1,
    dp: int = 1,
    sp: int = 1,
    ep: int = 1,
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """Mesh with axes (dp, sp, ep, tp); tp innermost so it rides the
    fastest ICI links."""
    devs = list(devices if devices is not None else jax.devices())
    need = tp * dp * sp * ep
    if need > len(devs):
        raise ValueError(f"mesh needs {need} devices, have {len(devs)}")
    arr = np.array(devs[:need]).reshape(dp, sp, ep, tp)
    return Mesh(arr, ("dp", "sp", "ep", "tp"))


def single_device_mesh(device: jax.Device | None = None) -> Mesh:
    dev = device or jax.devices()[0]
    return Mesh(np.array([dev]).reshape(1, 1, 1, 1), ("dp", "sp", "ep", "tp"))


def shard(mesh: Mesh, spec: PartitionSpec) -> NamedSharding:
    return NamedSharding(mesh, spec)


def shard_pytree(mesh: Mesh, tree, spec_tree):
    """Map a PartitionSpec pytree onto NamedShardings and device_put."""
    shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, PartitionSpec),
    )
    return jax.device_put(tree, shardings), shardings


def largest_tp(n_devices: int, num_kv_heads: int) -> int:
    """Biggest power-of-two tp degree dividing both devices and kv heads."""
    tp = 1
    while tp * 2 <= n_devices and num_kv_heads % (tp * 2) == 0:
        tp *= 2
    return tp
