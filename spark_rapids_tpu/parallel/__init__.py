"""Distributed execution over TPU device meshes.

TPU-native replacement for the reference's distribution machinery
(SURVEY.md §2.10): Spark-task data parallelism + all-to-all shuffle over
UCX/RDMA (reference shuffle-plugin/src/main/scala/.../UCX.scala) becomes
data-parallel shards over a `jax.sharding.Mesh` with the exchange lowered
to XLA `all_to_all` collectives riding ICI (DCN across slices, handled by
the same collective via the mesh topology).
"""
from spark_rapids_tpu.parallel.mesh import make_mesh, shard_batches
from spark_rapids_tpu.parallel.mesh_shuffle import partition_ids_for_keys

__all__ = ["make_mesh", "shard_batches", "partition_ids_for_keys"]
