"""Mesh helpers shared by the library and the launchers.

The production meshes (see ``repro.launch.mesh``) use axis names:

  * ``pod``   -- pod axis (multi-pod only); batch/data parallel across pods
  * ``data``  -- intra-pod data axis; descriptor rows / batch shards
  * ``model`` -- model axis; weights / embedding tables / experts / vocab

Library code never hardcodes sizes: everything is derived from the mesh that
is current (or passed explicitly), so the same program runs on the 1-device
CPU mesh used in tests and the 512-chip multi-pod mesh used in the dry-run.
"""

from __future__ import annotations

import math
from typing import Sequence

import jax
from jax.sharding import AxisType, Mesh, PartitionSpec as P


def local_mesh(axes: Sequence[str] = ("data", "model")) -> Mesh:
    """A degenerate mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    shape = [1] * len(axes)
    shape[0] = n
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def abstract_mesh(shape: Sequence[int], axes: Sequence[str]):
    """``AbstractMesh`` of the given sizes: tests and dry-runs use this so
    they never need real devices."""
    from jax.sharding import AbstractMesh

    return AbstractMesh(tuple(shape), tuple(axes))


def pcast_varying(x, axes):
    """Mark ``x`` as varying over ``axes`` inside shard_map.

    A replicated value that becomes per-shard state (a loop carry, say)
    needs the cast; axes ``x`` already varies over are left alone, since
    ``pcast`` refuses a varying-to-varying cast.
    """
    missing = tuple(a for a in axes if a not in jax.typeof(x).vma)
    return jax.lax.pcast(x, missing, to="varying")


def match_varying(*xs):
    """Cast ``xs`` to vary over the union of their manual mesh axes.

    Returns ``(xs, vma)``. Inside shard_map a Pallas kernel's operands
    must agree on the axes they vary over, and its outputs carry ``vma``;
    outside shard_map ``vma`` is empty and ``xs`` come back unchanged.
    """
    vma = frozenset().union(*(jax.typeof(x).vma for x in xs))
    return tuple(pcast_varying(x, tuple(sorted(vma))) for x in xs), vma


def mesh_axis_size(mesh: Mesh, name: str) -> int:
    if name not in mesh.axis_names:
        return 1
    return mesh.shape[name]


def batch_axes(mesh: Mesh) -> tuple[str, ...]:
    """Mesh axes over which batch-like (row) dimensions shard."""
    if "pod" in mesh.axis_names:
        return ("pod", "data")
    return ("data",)


def batch_spec(mesh: Mesh, *trailing) -> P:
    """PartitionSpec sharding dim 0 over the batch axes."""
    return P(batch_axes(mesh), *trailing)


def data_axis_size(mesh: Mesh) -> int:
    """Total number of row shards (pod*data)."""
    return math.prod(mesh_axis_size(mesh, a) for a in batch_axes(mesh))


def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def shard_submeshes(mesh: Mesh, n_shards: int) -> tuple[Mesh, ...]:
    """Per-shard meshes for scatter-gather serving (one entry per shard).

    When the mesh's devices split evenly over ``n_shards`` (and there is
    more than one device), each shard gets its own disjoint device group —
    shard scans then run on separate hardware. Otherwise every shard
    shares ``mesh`` unchanged: the sequential-but-isolated fallback, where
    shard scans run one after another on the same devices with identical
    numerics (the bit-identity tests run in this regime).
    """
    if n_shards < 1:
        raise ValueError(f"{n_shards=} must be >= 1")
    if n_shards == 1:
        return (mesh,)
    devs = mesh.devices  # shaped (axis0, axis1, ...) in axis_names order
    rows = devs.shape[0]
    per = rows // n_shards
    if per < 1 or rows % n_shards or devs.size == 1:
        return (mesh,) * n_shards
    # slice along the leading (batch) axis only: every other axis — e.g.
    # a model axis — keeps its devices and its meaning inside each shard
    return tuple(
        Mesh(devs[s * per:(s + 1) * per], mesh.axis_names)
        for s in range(n_shards)
    )
