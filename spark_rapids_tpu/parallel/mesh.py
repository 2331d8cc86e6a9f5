"""Device mesh construction and batch sharding.

The unit of distribution is a `ColumnBatch` shard per mesh slot along a
named axis (default ``"data"``) — the TPU analog of one Spark task's
partition living on one executor's GPU (reference
sql-plugin/.../GpuShuffleExchangeExec.scala + RapidsShuffleManager).

A *sharded batch* is an ordinary `ColumnBatch` pytree whose every leaf has
a leading device axis P (``num_rows`` is ``int32[P]``), placed with a
`NamedSharding` so that leaf axis 0 maps onto the mesh axis.  Inside
`shard_map` each device sees leading extent 1; `_local_view` squeezes that
away to recover a plain per-device `ColumnBatch`.
"""
from __future__ import annotations

from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from spark_rapids_tpu.columnar.batch import ColumnBatch

shard_map = jax.shard_map

__all__ = ["make_mesh", "shard_batches", "split_shards",
           "local_view", "stacked_spec", "shard_map"]


def make_mesh(n_devices: int | None = None, axis_name: str = "data",
              devices: Sequence | None = None) -> Mesh:
    """1-D mesh over the first ``n_devices`` devices (all by default)."""
    devs = list(devices) if devices is not None else jax.devices()
    if n_devices is not None:
        if len(devs) < n_devices:
            raise ValueError(
                f"requested {n_devices} devices, have {len(devs)}")
        devs = devs[:n_devices]
    return Mesh(np.asarray(devs), (axis_name,))


def stacked_spec(axis_name: str = "data") -> P:
    """PartitionSpec prefix for every leaf of a stacked batch."""
    return P(axis_name)


def shard_batches(batches: Sequence[ColumnBatch], mesh: Mesh,
                  axis_name: str = "data") -> ColumnBatch:
    """Stack P per-device batches (same schema+capacity) into one sharded
    batch pytree with leading device axis P placed along ``axis_name``."""
    p = mesh.shape[axis_name]
    if len(batches) != p:
        raise ValueError(f"need {p} shards, got {len(batches)}")
    schema = batches[0].schema
    sharding = NamedSharding(mesh, P(axis_name))
    devs = list(mesh.devices.flat)

    def place(*leaves):
        # build the global array from per-device shards: each leaf is
        # device_put straight to ITS mesh device (a no-op when the
        # shard — e.g. MeshJoinExec probe output — already lives there);
        # a central jnp.stack would both error on mixed committed
        # devices and funnel every shard through one device
        shards = [jax.device_put(leaf[None], d)
                  for leaf, d in zip(leaves, devs)]
        global_shape = (p,) + leaves[0].shape
        return jax.make_array_from_single_device_arrays(
            global_shape, sharding, shards)

    stacked = jax.tree_util.tree_map(place, *batches)
    # tree_map over ColumnBatch pytrees rebuilds a ColumnBatch (schema aux
    # is shared); its num_rows leaf is now int32[P].
    assert isinstance(stacked, ColumnBatch)
    assert stacked.schema == schema
    return stacked


def local_view(stacked: ColumnBatch) -> ColumnBatch:
    """Inside shard_map: squeeze the leading extent-1 device axis."""
    return jax.tree_util.tree_map(lambda x: x[0], stacked)


def restack(local: ColumnBatch) -> ColumnBatch:
    """Inside shard_map: re-add the leading device axis before returning."""
    return jax.tree_util.tree_map(lambda x: x[None], local)


def split_shards(stacked: ColumnBatch) -> list[ColumnBatch]:
    """Split a sharded batch into P per-device ColumnBatches WITHOUT a
    host round trip: each shard's arrays stay committed to the mesh
    device that produced them.  This is the region-boundary exit path —
    a device_get + re-upload would funnel every mesh output through the
    default device, re-serializing the distributed pipeline at each
    island boundary.  Downstream per-batch
    operators dispatch on the shard's own device; ``place_shards``
    device affinity keeps re-sharded batches where they already live."""
    leaves, treedef = jax.tree_util.tree_flatten(stacked)
    per_dev: list[list] = []
    for leaf in leaves:
        shards = sorted(leaf.addressable_shards, key=lambda s: s.index[0].start)
        # s.data has the leading extent-1 device axis; [0] squeezes it
        # ON the shard's device (jax keeps slicing on the operand's
        # device, and the result stays committed there)
        per_dev.append([s.data[0] for s in shards])
    p = len(per_dev[0]) if per_dev else 1
    return [jax.tree_util.tree_unflatten(treedef,
                                         [col[i] for col in per_dev])
            for i in range(p)]
