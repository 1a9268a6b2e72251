"""Immutable index segments.

A segment is one cluster-sorted :class:`~repro.core.index_build.
DistributedIndex` — the output of one ``append`` wave batch (or of a
compaction) — persisted as a single CheckpointManager checkpoint
(mesh-free on disk, crc-checked, atomic). Segments are written once and
never mutated; deletions are expressed as tombstones in the manifest and
applied as an id mask at search time (a masked row behaves exactly like the
pipeline's own padding rows: routed, scanned, never matched).
"""

from __future__ import annotations

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.index_build import DistributedIndex
from repro.core.sentinels import LEAF_SENTINEL
from repro.distributed.checkpoint import CheckpointManager
from repro.distributed.meshutil import batch_axes, data_axis_size, round_up

_SEGMENT_RE = re.compile(r"^seg_(\d{6})$")


def segment_name(seq: int) -> str:
    return f"seg_{seq:06d}"


def next_seq(segments_dir: str) -> int:
    """1 + the highest segment sequence number present on disk — committed
    or orphaned. Orphans (crash between append and commit) keep their name
    reserved so a retried append never collides with them."""
    if not os.path.isdir(segments_dir):
        return 1
    seqs = [
        int(m.group(1))
        for name in os.listdir(segments_dir)
        if (m := _SEGMENT_RE.match(name))
    ]
    return max(seqs, default=0) + 1


def _index_shardings(mesh: Mesh):
    ax = batch_axes(mesh)
    rows = NamedSharding(mesh, P(ax, None))
    flat = NamedSharding(mesh, P(ax))
    rep = NamedSharding(mesh, P())
    return {
        "index": DistributedIndex(
            vecs=rows, ids=flat, leaves=flat, offsets=rows, n_valid=flat,
            overflow=rep,
        )
    }


def place_on(index: DistributedIndex, mesh: Mesh) -> DistributedIndex:
    """``index``'s rows laid out for ``mesh``'s shard count, on ``mesh``.

    Each shard of a built index holds one contiguous leaf range, sorted,
    so its real rows in shard order are leaf-sorted globally. Cutting
    them at the new shards' leaf boundaries (and padding each shard to a
    common row count) gives the layout a build on ``mesh`` would hold,
    with every leaf's rows in the same order.
    """
    n_shards = data_axis_size(mesh)
    if index.offsets.shape[0] != n_shards:
        leaves = np.asarray(index.leaves)
        real = leaves != LEAF_SENTINEL
        leaves = leaves[real]
        vecs = np.asarray(index.vecs)[real]
        ids = np.asarray(index.ids)[real]
        lps = index.n_leaves // n_shards
        cuts = np.searchsorted(leaves, np.arange(n_shards + 1) * lps)
        rows = round_up(max(int(np.diff(cuts).max()), 1), 8)
        out_v = np.zeros((n_shards, rows, vecs.shape[1]), vecs.dtype)
        out_i = np.full((n_shards, rows), -1, np.int32)
        out_l = np.full((n_shards, rows), LEAF_SENTINEL, np.int32)
        offsets = np.empty((n_shards, lps + 1), np.int32)
        for s in range(n_shards):
            a, b = cuts[s], cuts[s + 1]
            out_v[s, :b - a] = vecs[a:b]
            out_i[s, :b - a] = ids[a:b]
            out_l[s, :b - a] = leaves[a:b]
            offsets[s] = np.searchsorted(leaves[a:b] - s * lps,
                                         np.arange(lps + 1))
        index = DistributedIndex(
            vecs=out_v.reshape(n_shards * rows, -1),
            ids=out_i.reshape(-1),
            leaves=out_l.reshape(-1),
            offsets=offsets,
            n_valid=np.diff(cuts).astype(np.int32),
            overflow=index.overflow,
            n_leaves=index.n_leaves,
        )
    shardings = dataclasses.replace(_index_shardings(mesh)["index"],
                                    n_leaves=index.n_leaves)
    return jax.device_put(index, shardings)


@dataclasses.dataclass
class Segment:
    """One immutable segment plus its static stats."""

    name: str
    index: DistributedIndex
    rows: int  # padded row count (index.rows)
    valid_rows: int  # rows with a real descriptor id
    min_id: int  # -1 when empty
    max_id: int  # -1 when empty
    # L2 norm range of the *valid* rows — the dense-tier pruning bound
    # (docs/dynamicity.md). -1.0 = unknown (segment written before these
    # stats existed, or empty); pruning is skipped for such segments.
    min_norm: float = -1.0
    max_norm: float = -1.0
    _ids_np: object = dataclasses.field(default=None, repr=False,
                                        compare=False)
    _id_index: object = dataclasses.field(default=None, repr=False,
                                          compare=False)
    _vecs_np: object = dataclasses.field(default=None, repr=False,
                                         compare=False)

    def host_ids(self) -> np.ndarray:
        """Host copy of the segment's id column (cached — segments are
        immutable). ``-1`` padding rows included, callers filter."""
        if self._ids_np is None:
            self._ids_np = np.asarray(self.index.ids).astype(np.int64)
        return self._ids_np

    def id_index(self) -> tuple[np.ndarray, np.ndarray]:
        """Cached ``(sorted_ids, row_order)`` for id->row probes. Padding
        ``-1`` ids sort first and never match a probed (non-negative) id."""
        if self._id_index is None:
            ids = self.host_ids()
            order = np.argsort(ids, kind="stable")
            self._id_index = (ids[order], order)
        return self._id_index

    def host_vecs(self) -> np.ndarray:
        """Host copy of the stored vectors (cached — on an accelerator
        backend the device-to-host transfer must not repeat per read)."""
        if self._vecs_np is None:
            self._vecs_np = np.asarray(self.index.vecs, np.float32)
        return self._vecs_np

    def overlaps(self, ids: np.ndarray) -> bool:
        """Can any of ``ids`` (non-empty) live in this segment?"""
        return (
            self.valid_rows > 0
            and int(ids.min()) <= self.max_id
            and int(ids.max()) >= self.min_id
        )

    @classmethod
    def from_built(cls, name: str, index: DistributedIndex) -> "Segment":
        ids = np.asarray(index.ids)
        valid = ids >= 0
        real = ids[valid]
        if real.size:
            norms = np.linalg.norm(
                np.asarray(index.vecs, np.float32)[valid].astype(np.float64),
                axis=1,
            )
            min_norm, max_norm = float(norms.min()), float(norms.max())
        else:
            min_norm = max_norm = -1.0
        return cls(
            name=name,
            index=index,
            rows=int(index.rows),
            valid_rows=int(real.size),
            min_id=int(real.min()) if real.size else -1,
            max_id=int(real.max()) if real.size else -1,
            min_norm=min_norm,
            max_norm=max_norm,
        )

    @property
    def n_shards(self) -> int:
        return int(self.index.offsets.shape[0])

    def stats(self) -> dict:
        return {
            "name": self.name,
            "rows": self.rows,
            "valid_rows": self.valid_rows,
            "min_id": self.min_id,
            "max_id": self.max_id,
            "min_norm": self.min_norm,
            "max_norm": self.max_norm,
            "n_shards": self.n_shards,
        }

    # -- persistence --------------------------------------------------------
    def save(self, segments_dir: str) -> str:
        mgr = CheckpointManager(os.path.join(segments_dir, self.name), keep=1)
        return mgr.save(
            0,
            {"index": self.index},
            extra=dict(
                self.stats(),
                n_leaves=int(self.index.n_leaves),
                dim=int(self.index.vecs.shape[-1]),
            ),
        )

    @classmethod
    def load(cls, segments_dir: str, name: str, mesh: Mesh) -> "Segment":
        mgr = CheckpointManager(os.path.join(segments_dir, name), keep=1)
        step = mgr.latest_step()
        if step is None:
            raise FileNotFoundError(
                f"segment {name} has no complete checkpoint under "
                f"{segments_dir}"
            )
        meta = mgr.read_manifest(step)["extra"]
        skeleton = {
            "index": DistributedIndex(
                vecs=0.0, ids=0, leaves=0, offsets=0, n_valid=0, overflow=0,
                n_leaves=int(meta["n_leaves"]),
            )
        }
        tree_out, _ = mgr.restore(skeleton, step,
                                  shardings=_index_shardings(mesh))
        index = tree_out["index"]
        index = place_on(DistributedIndex(
            vecs=index.vecs,
            ids=jnp.asarray(index.ids, jnp.int32),
            leaves=jnp.asarray(index.leaves, jnp.int32),
            offsets=jnp.asarray(index.offsets, jnp.int32),
            n_valid=jnp.asarray(index.n_valid, jnp.int32),
            overflow=jnp.asarray(index.overflow, jnp.int32),
            n_leaves=int(meta["n_leaves"]),
        ), mesh)
        return cls(
            name=name,
            index=index,
            rows=int(index.rows),
            valid_rows=int(meta["valid_rows"]),
            min_id=int(meta.get("min_id", -1)),
            max_id=int(meta.get("max_id", -1)),
            min_norm=float(meta.get("min_norm", -1.0)),
            max_norm=float(meta.get("max_norm", -1.0)),
        )


def dead_counts(segments, tombstones: np.ndarray) -> np.ndarray:
    """Per-segment count of valid rows killed by ``tombstones`` (a sorted
    array of unique ids — each id lives in exactly one segment, so the
    counts partition the tombstone set). Feeds the compaction policy's
    tombstone-ratio trigger and the search-time zero-live-segment prune.
    """
    out = np.zeros(len(segments), np.int64)
    ts = np.asarray(tombstones, np.int64)
    if ts.size == 0:
        return out
    for i, seg in enumerate(segments):
        if not seg.overlaps(ts):
            continue
        sorted_ids, _ = seg.id_index()
        pos = np.searchsorted(sorted_ids, ts)
        hit = (pos < sorted_ids.size) & (
            sorted_ids[np.minimum(pos, sorted_ids.size - 1)] == ts
        )
        out[i] = int(hit.sum())
    return out


# Tombstoned rows keep their leaf (CSR offsets stay valid) but get this
# magnitude written into every vector lane: the partial distance
# ||p||^2 - 2 p.q becomes ~1e30f — finite (no inf/nan propagation into the
# fused scan) yet astronomically above any real candidate, so a dead row
# can never displace a live neighbour from a tile's top-k. Its id is -1, so
# even when it *is* selected (a leaf with fewer than k live rows) scan_tile
# masks it to INVALID_ID/inf — exactly a padding row's fate.
TOMBSTONE_VEC = 1e15


def masked_view(segment: Segment, tombstones: np.ndarray) -> DistributedIndex:
    """The segment's index with tombstoned rows masked out of every scan.

    Bit-identical to rebuilding without the dead rows: live rows'
    distances are untouched, dead rows sort behind every live candidate,
    and a selected dead row degenerates to the ``-1``/``inf`` slot an
    absent row would have produced.
    """
    if tombstones.size == 0 or segment.valid_rows == 0:
        return segment.index
    lo = np.searchsorted(tombstones, segment.min_id)
    hi = np.searchsorted(tombstones, segment.max_id, side="right")
    if lo == hi:
        return segment.index  # no tombstone inside this segment's id range
    ids = segment.index.ids
    vecs = segment.index.vecs
    ts = jnp.asarray(tombstones, jnp.int32)
    pos = jnp.searchsorted(ts, ids)
    hit = (pos < ts.shape[0]) & (ts[jnp.clip(pos, 0, ts.shape[0] - 1)] == ids)
    return dataclasses.replace(
        segment.index,
        ids=jnp.where(hit, jnp.int32(-1), ids),
        vecs=jnp.where(hit[:, None], jnp.asarray(TOMBSTONE_VEC, vecs.dtype),
                       vecs),
    )
