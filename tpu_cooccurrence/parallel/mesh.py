"""Device mesh construction for the item-sharded co-occurrence state.

The reference scales out by hash-partitioning keyed state over Flink
subtasks and broadcasting row sums (``FlinkCooccurrences.java:89-117,
162-167``). The TPU analogue (SURVEY §2.6): a 1-D ``jax.sharding.Mesh``
over the ``items`` axis; co-occurrence rows are sharded, the row-sum
vector is replicated (the broadcast analogue), and partial row-sum
reductions ride ICI via ``psum`` inside ``shard_map``.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import Mesh

ITEM_AXIS = "items"


def make_mesh(num_shards: Optional[int] = None, devices: Optional[Sequence] = None) -> Mesh:
    """1-D mesh over ``num_shards`` devices (default: all available)."""
    if devices is None:
        devices = jax.devices()
    if num_shards is None:
        num_shards = len(devices)
    if num_shards > len(devices):
        raise ValueError(
            f"requested {num_shards} shards but only {len(devices)} devices")
    import numpy as np

    return Mesh(np.asarray(devices[:num_shards]), (ITEM_AXIS,))


def pad_to_multiple(n: int, multiple: int) -> int:
    return ((n + multiple - 1) // multiple) * multiple


def shard_map_maybe_relaxed(f, mesh, in_specs, out_specs, relaxed: bool):
    """shard_map, with the varying-mesh-axis check disabled when the body
    contains a pallas_call (its ShapeDtypeStruct outputs carry no vma
    annotation, which ``check_vma=True`` — the default — rejects).
    XLA-only programs keep the full check."""
    from jax import shard_map

    return shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                     check_vma=not relaxed)
