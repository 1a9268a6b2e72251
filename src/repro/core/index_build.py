"""Distributed index creation (paper §2.3), SPMD.

Map: every shard assigns its descriptor rows to tree leaves in *waves*
(microbatched tiles — the map-wave analog; wave size is the HDFS-chunk-size
analog, studied in benchmarks/block_size.py). Shuffle: rows are routed to
the shard owning their leaf range via capacity-padded counting sort +
``all_to_all``. Reduce: each shard sorts its received rows by leaf and
builds CSR offsets — the "index files which contain clustered
high-dimensional descriptors".

Everything is one jittable function of (vecs, ids, tree) so the multi-pod
dry-run lowers it directly.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import route as route_lib
from repro.core.tree import VocabTree, tree_assign
from repro.distributed.meshutil import batch_axes, data_axis_size, round_up


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DistributedIndex:
    """Cluster-sorted descriptor shards + per-shard CSR offsets."""

    vecs: jax.Array  # (S*R, d) rows sharded over data axes; leaf-sorted per shard
    ids: jax.Array  # (S*R,) global descriptor ids (-1 padding)
    leaves: jax.Array  # (S*R,) leaf ids (SENTINEL padding)
    offsets: jax.Array  # (S, leaves_per_shard+1) CSR per shard
    n_valid: jax.Array  # (S,) valid rows per shard
    overflow: jax.Array  # () rows dropped in routing (0 in healthy runs)
    n_leaves: int = dataclasses.field(metadata=dict(static=True), default=0)

    def tree_flatten(self):
        children = (self.vecs, self.ids, self.leaves, self.offsets,
                    self.n_valid, self.overflow)
        return children, self.n_leaves

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, n_leaves=aux)

    @property
    def rows(self) -> int:
        return self.vecs.shape[0]

    @property
    def leaves_per_shard(self) -> int:
        return self.offsets.shape[1] - 1


def routing_capacity(rows_per_shard: int, n_shards: int,
                     capacity_factor: float) -> int:
    """Send capacity per (source shard, destination shard) pair."""
    expected = rows_per_shard / n_shards
    return round_up(max(8, int(math.ceil(expected * capacity_factor))), 8)


def _assign_in_waves(tree: VocabTree, vecs: jax.Array, wave_rows: int) -> jax.Array:
    """Map phase: leaf assignment microbatched into waves (bounds the
    gather working set of deep tree levels, the VMEM analog of the paper's
    block-at-a-time map input)."""
    n = vecs.shape[0]
    if n % wave_rows != 0:
        raise ValueError(f"shard rows {n} not divisible by wave_rows {wave_rows}")
    waves = vecs.reshape(n // wave_rows, wave_rows, vecs.shape[1])
    leaves = jax.lax.map(lambda w: tree_assign(tree, w), waves)
    return leaves.reshape(n)


def build_index_fn(
    mesh: Mesh,
    *,
    n_leaves: int,
    rows_per_shard: int,
    wave_rows: int,
    capacity_factor: float = 2.0,
    wire_dtype=jnp.bfloat16,
    axes=None,
):
    """Return the jittable (vecs, ids, tree) -> DistributedIndex pipeline.

    ``axes``: mesh axes the descriptor rows shard over. The paper's cluster
    is flat — an index job has no model-parallel dimension — so production
    cells pass *every* mesh axis (leaving the model axis out replicates the
    whole job per model column: §Perf hillclimb, index_wave).
    """
    import math as _math

    axes = tuple(axes) if axes else batch_axes(mesh)
    n_shards = _math.prod(mesh.shape[a] for a in axes)
    if n_leaves % n_shards != 0:
        raise ValueError(f"n_leaves {n_leaves} must divide over {n_shards} shards")
    leaves_per_shard = n_leaves // n_shards
    capacity = routing_capacity(rows_per_shard, n_shards, capacity_factor)

    def shard_fn(vecs, ids, tree):
        # --- map: assignment in waves --------------------------------------
        leaves = _assign_in_waves(tree, vecs[0], wave_rows)
        # --- shuffle: route to owner shards --------------------------------
        routed = route_lib.route_by_leaf(
            vecs[0],
            ids[0],
            leaves,
            axis_name=axes,
            n_shards=n_shards,
            leaves_per_shard=leaves_per_shard,
            capacity=capacity,
            wire_dtype=wire_dtype,
        )
        # --- reduce: cluster sort + CSR ------------------------------------
        shard_id = jnp.int32(0)
        for a in axes:
            shard_id = shard_id * mesh.shape[a] + jax.lax.axis_index(a)
        leaf_base = shard_id * leaves_per_shard
        svecs, sids, sleaves, offsets, n_valid = route_lib.cluster_sort(
            routed, leaf_base=leaf_base, leaves_per_shard=leaves_per_shard
        )
        return (
            svecs[None],
            sids[None],
            sleaves[None],
            offsets[None],
            n_valid[None],
            routed.overflow,
        )

    row_spec = P(axes, None)
    flat_spec = P(axes)

    def pipeline(vecs, ids, tree):
        # keep a leading per-shard axis so shard row counts are explicit
        vecs = vecs.reshape(n_shards, rows_per_shard, vecs.shape[-1])
        ids = ids.reshape(n_shards, rows_per_shard)
        tree_specs = jax.tree.map(lambda _: P(), tree)
        out = jax.shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=(row_spec, flat_spec, tree_specs),
            out_specs=(row_spec, flat_spec, flat_spec, flat_spec, flat_spec, P()),
        )(vecs, ids, tree)
        svecs, sids, sleaves, offsets, n_valid, overflow = out
        return DistributedIndex(
            vecs=svecs.reshape(-1, svecs.shape[-1]),
            ids=sids.reshape(-1),
            leaves=sleaves.reshape(-1),
            offsets=offsets,
            n_valid=n_valid,
            overflow=overflow,
            n_leaves=n_leaves,
        )

    return pipeline


def build_index(
    vecs: jax.Array,
    tree: VocabTree,
    mesh: Mesh,
    *,
    ids: jax.Array | None = None,
    wave_rows: int | None = None,
    capacity_factor: float = 2.0,
    wire_dtype=jnp.bfloat16,
) -> DistributedIndex:
    """Eager convenience wrapper (pads rows to the shard grid, jits, runs)."""
    n, d = vecs.shape
    n_shards = data_axis_size(mesh)
    n_pad = round_up(n, n_shards)
    if ids is None:
        ids = jnp.arange(n, dtype=jnp.int32)
    if n_pad != n:
        vecs = jnp.concatenate([vecs, jnp.zeros((n_pad - n, d), vecs.dtype)])
        # padding rows get id -1 and will be routed but never matched
        ids = jnp.concatenate([ids, jnp.full((n_pad - n,), -1, jnp.int32)])
    rows_per_shard = n_pad // n_shards
    from repro.core.engine.plan import largest_divisor_leq

    # snap to the largest divisor of rows_per_shard <= requested
    wave_rows = largest_divisor_leq(rows_per_shard, wave_rows or 4096)
    fn = build_index_fn(
        mesh,
        n_leaves=tree.n_leaves,
        rows_per_shard=rows_per_shard,
        wave_rows=wave_rows,
        capacity_factor=capacity_factor,
        wire_dtype=wire_dtype,
    )
    sharded = NamedSharding(mesh, P(batch_axes(mesh), None))
    vecs = jax.device_put(vecs, sharded)
    ids = jax.device_put(ids, NamedSharding(mesh, P(batch_axes(mesh))))
    return jax.jit(fn)(vecs, ids, tree)
