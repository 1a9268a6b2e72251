"""The segment-based index lifecycle facade (paper §2.2–2.3 as an API).

The paper's collection *grows between runs*: 30B descriptors are indexed in
grid-sized batches, and every search job runs against whatever index files
exist so far. :class:`Index` is that workflow as one object:

  ``Index.create(tree, dir)``   new index bound to a vocabulary tree
  ``Index.open(dir)``           restore the last committed state
  ``idx.append(vecs, ids)``     wave-based assignment (``build_index_fn``
                                under the eager wrapper) into a new
                                immutable, durably-written *segment*
  ``idx.commit()``              atomic manifest bump — the only operation
                                that makes appends/deletes visible to a
                                later ``open`` (crash-safe, idempotent)
  ``idx.delete(ids)``           tombstones (masked at search, dropped at
                                compaction)
  ``idx.compact()``             merge all segments into one, dropping
                                tombstoned rows; commits atomically
  ``idx.search(queries, ...)``  engine executors per segment over one
                                shared lookup build, merged across segments

Search over N segments is *bit-identical* to a one-shot ``build_index`` +
``batch_search`` over the concatenated rows (and after ``compact()`` the
index arrays themselves match a from-scratch rebuild): per-pair distances
depend only on the (point, query) vectors, tombstone masking reuses the
pipeline's own padding semantics, and the cross-segment merge applies the
same ascending-distance fold the executors use internally.

A handle sees its own uncommitted writes (staged segments and staged
tombstones); a fresh ``open`` sees only the last committed manifest.
"""

from __future__ import annotations

import dataclasses
import os
import shutil
import threading
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.codes import CODES_FORMAT, ProductQuantizer, rerank_exact
from repro.core.engine import (
    CalibrationStore,
    SearchPlan,
    plan as make_plan,
)
from repro.core.engine.executors import SearchResult
from repro.core.index_build import DistributedIndex, build_index
from repro.core.search import jit_build_lookup, search_with_lookup
from repro.core.tree import VocabTree
from repro.distributed.checkpoint import CheckpointManager
from repro.distributed.meshutil import data_axis_size, local_mesh
from repro.index import manifest as manifest_lib
from repro.index.manifest import Manifest
from repro.index.segment import (
    Segment,
    dead_counts,
    masked_view,
    next_seq,
    segment_name,
)
from repro.index.sharding import ShardPlan
from repro.obs import get_registry, get_tracer


# the pre-segment serving.persist format (one monolithic checkpoint);
# detected only to fail/warn actionably — there is no in-place migration
LEGACY_CKPT_SUBDIR = "index_ckpt"


def has_legacy_index(directory: str) -> bool:
    return bool(directory) and os.path.isdir(
        os.path.join(directory, LEGACY_CKPT_SUBDIR)
    )


def has_index(directory: str) -> bool:
    """True when ``directory`` holds at least one committed manifest."""
    return bool(directory) and manifest_lib.latest(directory) is not None


def _save_tree(directory: str, tree: VocabTree, meta: dict) -> None:
    mgr = CheckpointManager(
        os.path.join(directory, manifest_lib.TREE_SUBDIR), keep=1
    )
    mgr.save(0, {"tree": tree}, extra=meta)


def _load_tree(directory: str, mesh) -> tuple[VocabTree, dict]:
    from jax.sharding import NamedSharding, PartitionSpec as P

    mgr = CheckpointManager(
        os.path.join(directory, manifest_lib.TREE_SUBDIR), keep=1
    )
    step = mgr.latest_step()
    if step is None:
        raise FileNotFoundError(f"no index tree checkpoint under {directory}")
    meta = mgr.read_manifest(step)["extra"]
    rep = NamedSharding(mesh, P())
    n_levels = int(meta["n_levels"])
    skeleton = {"tree": VocabTree(levels=tuple(0.0 for _ in range(n_levels)))}
    shardings = {
        "tree": VocabTree(levels=tuple(rep for _ in range(n_levels)))
    }
    out, _ = mgr.restore(skeleton, step, shardings=shardings)
    return out["tree"], meta


@dataclasses.dataclass(frozen=True)
class CompactionPolicy:
    """When an *incremental* compaction step merges which segments.

    ``Index.compact(incremental=True)`` asks the policy for one batch of
    victims per call instead of merging everything:

      1. **Tombstone reclamation first** — any segment whose dead/valid
         ratio is at least ``tombstone_ratio`` is rewritten now; a
         delete-heavy segment is reclaimed within one step regardless of
         its size tier.
      2. **Smallest size tier** — otherwise the segments whose live-row
         counts sit within ``size_tier_factor`` of the smallest one are
         merged (classic size-tiered compaction: many small segments fold
         into one medium one, medium ones later fold into a big one, so
         total merge work stays O(n log n) rows instead of O(n^2)).

    A tier smaller than ``min_tier_segments`` is left alone — a fully
    compacted index is a fixed point and the step publishes nothing.
    ``max_segments_per_step`` bounds the rows any single step rewrites,
    which bounds the stall a serving session could observe.
    """

    size_tier_factor: float = 4.0
    min_tier_segments: int = 2
    tombstone_ratio: float = 0.25
    max_segments_per_step: int = 8

    def select(
        self, segments: Sequence[Segment], tombstones: np.ndarray
    ) -> list[Segment]:
        """The victims of one incremental step, in index order (possibly
        empty). Pure function of committed state — callers may dry-run it."""
        segments = list(segments)
        if not segments:
            return []
        dead = dead_counts(segments, tombstones)
        heavy = {
            s.name
            for s, d in zip(segments, dead)
            if s.valid_rows and d / s.valid_rows >= self.tombstone_ratio
        }
        if heavy:
            victims = [s for s in segments if s.name in heavy]
            return victims[: self.max_segments_per_step]
        live = {
            s.name: int(s.valid_rows - d) for s, d in zip(segments, dead)
        }
        order = sorted(segments, key=lambda s: (live[s.name], s.name))
        tier = [order[0]]
        for s in order[1:]:
            if live[s.name] <= self.size_tier_factor * max(
                1, live[tier[0].name]
            ):
                tier.append(s)
            else:
                break
        if len(tier) < self.min_tier_segments:
            return []
        chosen = {s.name for s in tier[: self.max_segments_per_step]}
        return [s for s in segments if s.name in chosen]


@dataclasses.dataclass(frozen=True)
class IndexSnapshot:
    """One consistent, immutable cut of an :class:`Index`'s state.

    Serving sessions pin a snapshot and keep answering from it while the
    writer appends/deletes/compacts underneath — every array here is
    either immutable (segments, views) or a private copy (tombstones),
    so a pinned reader never observes a half-applied mutation. ``stamp``
    is the index's monotone mutation counter: equal stamps mean nothing
    changed, which is how ``maybe_refresh()`` stays O(1) when idle.
    """

    stamp: int
    version: int
    segments: tuple[Segment, ...]
    views: tuple[DistributedIndex, ...]
    tombstones: np.ndarray
    shard_plan: ShardPlan | None
    quantizer: ProductQuantizer | None
    codes: dict


class Index:
    """Segment-based distributed index with a durable lifecycle."""

    def __init__(
        self,
        directory: str | None,
        tree: VocabTree,
        mesh=None,
        *,
        segments: Sequence[Segment] = (),
        tombstones: np.ndarray | None = None,
        version: int = 0,
        next_id: int = 0,
        meta: dict | None = None,
        wire_dtype=jnp.float32,
        shard_plan: ShardPlan | None = None,
        calibration: CalibrationStore | None = None,
        quantizer: ProductQuantizer | None = None,
        codes: dict | None = None,
        codes_paths: dict | None = None,
    ):
        self.directory = directory
        self.tree = tree
        self._mesh = mesh
        self.wire_dtype = wire_dtype
        self._committed: list[Segment] = list(segments)
        self._staged: list[Segment] = []
        self._shard_plan = shard_plan
        self._shard_plan_dirty = False
        # compressed-codes tier: the PQ quantizer (manifest-persisted like
        # shard_plan/calibration), per-segment (rows, m) uint8 code arrays,
        # and the relative paths of already-published code files
        self.quantizer = quantizer
        self._codes: dict[str, np.ndarray] = dict(codes or {})
        self._codes_paths: dict[str, str] = dict(codes_paths or {})
        self._codes_dirty = False
        # index-scoped cost-model calibration: measured ms/image per plan
        # signature, persisted in the manifest (its own dirty flag drives
        # commit), consulted by search()/serving via plan(model="auto")
        self.calibration = (
            calibration if calibration is not None else CalibrationStore()
        )
        self._tombstones = (
            np.sort(np.asarray(tombstones, np.int64))
            if tombstones is not None and len(tombstones)
            else np.empty((0,), np.int64)
        )
        self._tombstones_dirty = False
        self._version = version
        self._next_id = int(next_id)
        self._user_meta = dict(meta or {})
        self._meta_dirty = False
        self._views: tuple[DistributedIndex, ...] | None = None
        self._mem_seq = 0  # segment naming for ephemeral (dir-less) indexes
        # single-writer / many-pinned-reader support: the lock guards the
        # (cheap) memory-state swaps, never the expensive builds; the stamp
        # is bumped by every mutation so snapshot holders can detect
        # staleness in O(1) (see IndexSnapshot / SearchSession.maybe_refresh)
        self._lock = threading.RLock()
        self._stamp = 0

    # -- construction -------------------------------------------------------
    @classmethod
    def create(
        cls,
        tree: VocabTree,
        directory: str | None = None,
        *,
        mesh=None,
        wire_dtype=jnp.float32,
        extra: dict | None = None,
        overwrite: bool = False,
    ) -> "Index":
        """New empty index bound to ``tree``.

        Args:
          tree: the vocabulary :class:`~repro.core.tree.VocabTree` every
            later append/search routes through.
          directory: durable home of the index; ``None`` gives an
            *ephemeral* index (same API, nothing on disk) — the adapter
            the legacy in-memory paths wrap themselves in.
          mesh: device mesh (default: ``meshutil.local_mesh()``).
          wire_dtype: routed-shuffle payload dtype for appends (float32
            keeps grown indexes bit-identical to one-shot rebuilds).
          extra: user metadata carried in every manifest.
          overwrite: clear a previous index's artifacts (manifests,
            segments, tree, tombstones) — unrelated files (e.g. a
            ``corpus/`` store) are left alone.

        Returns:
          The new handle. With a ``directory``, the tree checkpoint and
          an empty manifest are written immediately, so even an index
          that crashes before its first commit reopens cleanly.

        Raises:
          FileExistsError: ``directory`` already holds an index and
            ``overwrite`` is False.
        """
        idx = cls(directory, tree, mesh, wire_dtype=wire_dtype, meta=extra)
        if directory:
            if has_index(directory) and not overwrite:
                raise FileExistsError(
                    f"{directory} already holds an index; use Index.open "
                    "or create(..., overwrite=True)"
                )
            if overwrite and os.path.isdir(directory):
                for v in manifest_lib.list_versions(directory):
                    os.remove(manifest_lib.manifest_path(directory, v))
                for sub in (
                    manifest_lib.SEGMENTS_SUBDIR,
                    manifest_lib.TOMBSTONES_SUBDIR,
                    manifest_lib.TREE_SUBDIR,
                ):
                    shutil.rmtree(os.path.join(directory, sub),
                                  ignore_errors=True)
            os.makedirs(directory, exist_ok=True)
            _save_tree(directory, tree, idx._tree_meta())
            manifest_lib.write(directory, idx._manifest())
        return idx

    @classmethod
    def open(cls, directory: str, mesh=None) -> "Index":
        """Restore the last *committed* state from ``directory``.

        Args:
          directory: an index home previously written by :meth:`create` +
            :meth:`commit`.
          mesh: device mesh to place segments on (default: local mesh).

        Returns:
          An :class:`Index` at the highest complete manifest version —
          orphan segments from an interrupted append (no manifest
          references them) are ignored.

        Raises:
          FileNotFoundError: no committed manifest (including the
            pre-segment legacy ``index_ckpt/`` format, reported
            actionably).
        """
        m = manifest_lib.latest(directory)
        if m is None:
            if has_legacy_index(directory):
                raise FileNotFoundError(
                    f"{directory} holds a pre-segment-format index "
                    f"({LEGACY_CKPT_SUBDIR}/), which this version no longer "
                    "reads — rebuild it (e.g. serve --rebuild, or "
                    "Index.create + append + commit)"
                )
            raise FileNotFoundError(f"no index manifest under {directory}")
        mesh = mesh if mesh is not None else local_mesh()
        tree, tree_meta = _load_tree(directory, mesh)
        seg_dir = os.path.join(directory, manifest_lib.SEGMENTS_SUBDIR)
        # segments built on another device count are re-cut for this mesh
        segments = [Segment.load(seg_dir, name, mesh) for name in m.segments]
        wire = jnp.dtype(tree_meta.get("wire_dtype", "float32"))
        quantizer, codes, codes_paths = None, {}, {}
        if m.codes:
            quantizer = ProductQuantizer.from_json(m.codes["quantizer"])
            codes_paths = dict(m.codes.get("segments", {}))
            codes = {
                name: manifest_lib.read_codes(directory, rel)
                for name, rel in codes_paths.items()
                if name in m.segments
            }
        return cls(
            directory,
            tree,
            mesh,
            segments=segments,
            tombstones=manifest_lib.read_tombstones(directory, m.tombstones),
            version=m.version,
            next_id=m.next_id,
            meta=m.meta,
            wire_dtype=wire,
            shard_plan=(
                ShardPlan.from_json(m.shard_plan) if m.shard_plan else None
            ),
            calibration=(
                CalibrationStore.from_json(m.calibration)
                if m.calibration else None
            ),
            quantizer=quantizer,
            codes=codes,
            codes_paths=codes_paths,
        )

    @classmethod
    def from_built(
        cls,
        built: DistributedIndex,
        tree: VocabTree,
        *,
        mesh=None,
        extra: dict | None = None,
    ) -> "Index":
        """Ephemeral single-segment wrapper around an already-built
        ``DistributedIndex`` — the legacy-constructor adapter."""
        idx = cls.create(tree, None, mesh=mesh, extra=extra)
        idx.append_built(built)
        idx.commit()
        return idx

    # -- basic accessors ----------------------------------------------------
    @property
    def mesh(self):
        if self._mesh is None:
            self._mesh = local_mesh()
        return self._mesh

    @property
    def n_leaves(self) -> int:
        return self.tree.n_leaves

    @property
    def dim(self) -> int:
        return self.tree.dim

    @property
    def version(self) -> int:
        return self._version

    @property
    def next_id(self) -> int:
        """Next auto-assigned descriptor id (the id-space high-water mark)."""
        return self._next_id

    @property
    def stamp(self) -> int:
        """Monotone mutation counter: bumped by every append / delete /
        meta / plan / codes / commit / compact on this handle. Two equal
        stamps mean the index state is unchanged between them."""
        return self._stamp

    def snapshot(self) -> "IndexSnapshot":
        """A consistent :class:`IndexSnapshot` of the current state (this
        handle's view: committed + staged). Taken under the writer lock,
        so a concurrent mutator can never hand out a torn cut."""
        with self._lock:
            segs = self.segments
            return IndexSnapshot(
                stamp=self._stamp,
                version=self._version,
                segments=segs,
                views=self.segment_views(),
                tombstones=self._tombstones.copy(),
                shard_plan=self._shard_plan,
                quantizer=self.quantizer,
                codes=(
                    {s.name: self._codes[s.name] for s in segs}
                    if self.quantizer is not None else {}
                ),
            )

    @property
    def segments(self) -> tuple[Segment, ...]:
        """Committed + staged segments, in append order."""
        return tuple(self._committed) + tuple(self._staged)

    @property
    def n_segments(self) -> int:
        return len(self._committed) + len(self._staged)

    @property
    def staged_segments(self) -> tuple[str, ...]:
        return tuple(s.name for s in self._staged)

    @property
    def tombstones(self) -> np.ndarray:
        return self._tombstones.copy()

    @property
    def shard_plan(self) -> ShardPlan | None:
        """The scatter-gather :class:`~repro.index.sharding.ShardPlan`
        bound to this index (persisted in the manifest), or ``None``."""
        return self._shard_plan

    def set_shard_plan(self, plan: ShardPlan | None) -> None:
        """Stage a shard plan (or clear with ``None``); durable in the
        manifest at the next :meth:`commit`.

        Raises ``ValueError`` when ``plan`` does not assign exactly this
        index's current segments — derive one with
        ``ShardPlan.for_index(index, n_shards, strategy)``.
        """
        if plan is not None and not plan.covers(
            [s.name for s in self.segments]
        ):
            raise ValueError(
                "shard plan does not cover the index's current segments; "
                "derive one with ShardPlan.for_index"
            )
        with self._lock:
            self._shard_plan = plan
            self._shard_plan_dirty = True
            self._stamp += 1

    # -- compressed-codes tier ----------------------------------------------
    def enable_codes(
        self,
        *,
        m: int = 8,
        bits: int = 8,
        sample: int = 65_536,
        iters: int = 16,
        seed: int = 0,
    ) -> ProductQuantizer:
        """Train a :class:`~repro.codes.ProductQuantizer` on this index's
        live rows and encode every segment (staged; durable after
        :meth:`commit`, versioned in the manifest like ``shard_plan``).

        Once enabled, later appends and compactions re-encode their new
        segments automatically, and ``search(layout="auto")`` may pick the
        ``scan_codes`` layout (ADC scan + exact rerank) when the cost model
        prices it cheaper — ``search(layout="scan_codes")`` forces it.

        Raises:
          ValueError: no live rows to train on, or ``dim`` is not
            divisible by ``m``.
        """
        segs = self.segments
        parts = []
        for seg in segs:
            ids = seg.host_ids()
            parts.append(seg.host_vecs()[ids >= 0])
        train = (
            np.concatenate(parts) if parts
            else np.empty((0, self.dim), np.float32)
        )
        if train.shape[0] == 0:
            raise ValueError("enable_codes needs at least one indexed row")
        with get_tracer().span("index.enable_codes", rows=train.shape[0],
                               m=m, bits=bits):
            pq = ProductQuantizer.train(
                train, m=m, bits=bits, seed=seed, sample=sample, iters=iters
            )
            codes = {seg.name: pq.encode(seg.host_vecs()) for seg in segs}
        with self._lock:
            self.quantizer = pq
            self._codes = codes
            self._codes_paths = {}
            self._codes_dirty = True
            self._stamp += 1
        return self.quantizer

    def codes_stats(self) -> dict | None:
        """Footprint of the compressed tier, or ``None`` when disabled."""
        pq = self.quantizer
        if pq is None:
            return None
        return {
            "code_m": pq.m,
            "code_bits": pq.bits,
            "bytes_per_row": pq.bytes_per_row,
            "raw_bytes_per_row": 4 * self.dim,
            "compression_ratio": pq.compression_ratio(),
            "codebook_bytes": pq.codebook_bytes,
        }

    @property
    def rows(self) -> int:
        """Live (searchable) descriptor rows: valid minus tombstoned."""
        return sum(s.valid_rows for s in self.segments) - len(self._tombstones)

    @property
    def meta(self) -> dict:
        """User extra merged with the derived structure/stats keys the old
        ``persist.load_index`` manifest carried."""
        out = dict(self._user_meta)
        out.update(self._tree_meta())
        out.update(
            rows=sum(s.rows for s in self.segments),
            valid_rows=sum(s.valid_rows for s in self.segments),
            live_rows=self.rows,
            n_shards=data_axis_size(self.mesh),
            n_segments=self.n_segments,
            n_tombstones=int(len(self._tombstones)),
            next_id=self._next_id,
            version=self._version,
        )
        return out

    def stats(self) -> dict:
        return dict(
            self.meta,
            segments=[s.stats() for s in self.segments],
            staged=list(self.staged_segments),
        )

    def _tree_meta(self) -> dict:
        return {
            "n_leaves": int(self.tree.n_leaves),
            "n_levels": len(self.tree.levels),
            "fanouts": [int(f) for f in self.tree.fanouts],
            "dim": int(self.tree.dim),
            "wire_dtype": str(jnp.dtype(self.wire_dtype)),
        }

    def _manifest(
        self,
        tombstones_rel: str | None = None,
        *,
        version: int | None = None,
        segments: Sequence[Segment] | None = None,
        shard_plan: ShardPlan | None = None,
        codes_paths: dict | None = None,
    ) -> Manifest:
        segs = self._committed if segments is None else segments
        return Manifest(
            version=self._version if version is None else version,
            segments=[s.name for s in segs],
            tombstones=tombstones_rel,
            next_id=self._next_id,
            meta=self._user_meta,
            shard_plan=shard_plan.to_json() if shard_plan else None,
            calibration=(
                self.calibration.to_json() if len(self.calibration) else None
            ),
            codes=self._codes_payload(segs, codes_paths),
        )

    def _codes_payload(
        self, segments: Sequence[Segment], paths: dict | None = None
    ) -> dict | None:
        if self.quantizer is None:
            return None
        paths = self._codes_paths if paths is None else paths
        return {
            "format": CODES_FORMAT,
            "quantizer": self.quantizer.to_json(),
            "segments": {
                s.name: paths[s.name] for s in segments if s.name in paths
            },
        }

    def _plan_for(self, segments: Sequence[Segment]) -> ShardPlan | None:
        """The bound shard plan updated to ``segments``: unchanged when it
        still covers them, re-derived (same strategy, same shard count)
        after an append/compact changed the segment set. Explicit plans
        cannot follow a changed set and are dropped."""
        p = self._shard_plan
        if p is None:
            return None
        names = [s.name for s in segments]
        if p.covers(names):
            return p
        if p.strategy == "round_robin":
            return ShardPlan.round_robin(names, p.n_shards)
        if p.strategy == "balanced":
            return ShardPlan.balanced(
                names, [s.valid_rows for s in segments], p.n_shards
            )
        return None

    # -- write path ---------------------------------------------------------
    def _segments_dir(self) -> str:
        return os.path.join(self.directory, manifest_lib.SEGMENTS_SUBDIR)

    def _next_name(self) -> str:
        if self.directory:
            return segment_name(next_seq(self._segments_dir()))
        self._mem_seq += 1
        return segment_name(self._mem_seq)

    def _existing_ids(self, within: np.ndarray | None = None) -> np.ndarray:
        """Indexed descriptor ids, pruned to segments whose [min_id,
        max_id] range can overlap ``within`` — membership probes (delete,
        collision checks) skip segments that cannot possibly match."""
        segs = self.segments
        if within is not None and within.size:
            segs = [s for s in segs if s.overlaps(within)]
        parts = [s.host_ids() for s in segs]
        if not parts:
            return np.empty((0,), np.int64)
        ids = np.concatenate(parts)
        return ids[ids >= 0]

    def append(
        self,
        vecs,
        ids=None,
        *,
        wave_rows: int | None = None,
        capacity_factor: float = 2.0,
    ) -> str:
        """Assign + route + cluster-sort ``vecs`` into a new immutable
        segment (staged; durable after :meth:`commit`).

        Assignment runs in waves through ``build_index_fn`` exactly like a
        one-shot build, so an index grown by appends is the same index a
        monolithic job would have produced.

        Args:
          vecs: ``(n, dim)`` descriptor rows (cast to float32).
          ids: explicit non-negative descriptor ids; default is the next
            contiguous range of the global id space.
          wave_rows: assignment wave size (default: auto-snapped).
          capacity_factor: routing headroom for skewed leaves.

        Returns:
          The staged segment's name.

        Raises:
          ValueError: wrong shape, zero rows, negative/duplicate/
            colliding ids, or an id past the int32 id space.
        """
        vecs = np.asarray(vecs, np.float32)
        if vecs.ndim != 2 or vecs.shape[1] != self.dim:
            raise ValueError(
                f"append expects (n, {self.dim}) rows; got {vecs.shape}"
            )
        n = vecs.shape[0]
        if n == 0:
            raise ValueError("append of zero rows")
        if ids is None:
            ids = np.arange(self._next_id, self._next_id + n, dtype=np.int64)
        else:
            ids = np.asarray(ids, np.int64)
            if ids.shape != (n,):
                raise ValueError(f"ids shape {ids.shape} != ({n},)")
            if ids.size and ids.min() < 0:
                raise ValueError("descriptor ids must be non-negative")
            if len(np.unique(ids)) != n:
                raise ValueError("duplicate ids within the appended batch")
            if ids.min() < self._next_id and np.isin(
                ids, self._existing_ids(within=ids)
            ).any():
                raise ValueError("appended ids collide with indexed ids")
        if int(ids.max()) > np.iinfo(np.int32).max:
            # the engine carries ids as int32; a wrapped id would silently
            # become padding (-1 family) and the row would vanish
            raise ValueError(
                f"descriptor id {int(ids.max())} exceeds int32 — the id "
                "space is full; compact() after deletes or re-id the corpus"
            )
        with get_tracer().span("index.append", rows=n):
            built = build_index(
                jnp.asarray(vecs),
                self.tree,
                self.mesh,
                ids=jnp.asarray(ids.astype(np.int32)),
                wave_rows=wave_rows,
                capacity_factor=capacity_factor,
                wire_dtype=self.wire_dtype,
            )
            jax.block_until_ready(built.vecs)
            name = self.append_built(built)
        reg = get_registry()
        reg.counter("index.appends").inc()
        reg.counter("index.rows_appended").inc(n)
        return name

    def append_built(self, built: DistributedIndex, *, name=None) -> str:
        """Adopt an already-built ``DistributedIndex`` as a staged segment
        (the ``save_index`` shim and the legacy session path use this)."""
        if int(built.n_leaves) != self.n_leaves:
            raise ValueError(
                f"built index has {built.n_leaves} leaves; tree has "
                f"{self.n_leaves}"
            )
        if self.segments and built.offsets.shape[0] != self.segments[0].n_shards:
            raise ValueError(
                f"built index has {built.offsets.shape[0]} shards; index "
                f"segments have {self.segments[0].n_shards}"
            )
        seg = Segment.from_built(name or self._next_name(), built)
        if self.directory:
            seg.save(self._segments_dir())  # durable *before* it is staged
        new_codes = None
        if self.quantizer is not None:
            # the codes tier follows every append: encode the new segment's
            # padded rows (pad rows carry the LEAF_SENTINEL and never match)
            new_codes = self.quantizer.encode(seg.host_vecs())
        with self._lock:
            self._staged.append(seg)
            if new_codes is not None:
                self._codes[seg.name] = new_codes
                self._codes_dirty = True
            self._next_id = max(self._next_id, seg.max_id + 1)
            self._views = None
            self._stamp += 1
        return seg.name

    def update_meta(self, **kw) -> None:
        """Stage user-metadata updates (e.g. an ingest cursor); durable at
        the next :meth:`commit` alongside whatever else is staged."""
        with self._lock:
            self._user_meta.update(kw)
            self._meta_dirty = True
            self._stamp += 1

    def delete(self, ids) -> int:
        """Tombstone descriptor ids (staged; durable after :meth:`commit`).

        Args:
          ids: descriptor ids to delete; absent or already-deleted ids
            are ignored (idempotent).

        Returns:
          How many ids were *newly* tombstoned. Tombstoned rows stop
          matching immediately for this handle and are physically
          dropped at the next :meth:`compact`.
        """
        ids = np.unique(np.asarray(ids, np.int64))
        ids = ids[~np.isin(ids, self._tombstones)]
        if ids.size:
            ids = ids[np.isin(ids, self._existing_ids(within=ids))]
        if ids.size == 0:
            return 0
        with self._lock:
            self._tombstones = np.sort(
                np.concatenate([self._tombstones, ids])
            )
            self._tombstones_dirty = True
            self._views = None
            self._stamp += 1
        reg = get_registry()
        reg.counter("index.tombstoned").inc(int(ids.size))
        reg.gauge("index.tombstones_live").set(int(self._tombstones.size))
        return int(ids.size)

    def commit(self) -> int:
        """Publish staged segments + tombstones + metadata + shard plan +
        cost-model calibration: one atomic manifest bump.

        Idempotent — committing with nothing staged returns the current
        version without writing. A crash *before* the manifest rename
        leaves the previous committed state fully intact (staged segment
        checkpoints become ignorable orphans); a crash *after* it leaves
        the new state fully committed. There is no in-between. A bound
        shard plan that no longer covers the staged segment set is
        re-derived (same strategy) in the same bump.

        Returns:
          The committed manifest version.

        Raises:
          FileExistsError: another handle committed this version
            concurrently (exclusive publication) — reopen and retry.
          OSError: the durable write failed; the handle stays staged so
            a retried ``commit()`` re-attempts publication.
        """
        if not (self._staged or self._tombstones_dirty or self._meta_dirty
                or self._shard_plan_dirty or self._codes_dirty
                or self.calibration.dirty):
            return self._version
        # durable writes FIRST, memory state only after they succeed — a
        # failed write leaves the handle still-staged, so a retried
        # commit() re-attempts the publication instead of no-opping
        version = self._version + 1
        segments = self._committed + self._staged
        plan = self._plan_for(segments)
        with get_tracer().span("index.commit", version=version,
                               staged=len(self._staged)):
            if self.directory:
                rel = None
                if len(self._tombstones):
                    rel = manifest_lib.write_tombstones(
                        self.directory, version, self._tombstones
                    )
                if self.quantizer is not None:
                    # code files are durable *before* the manifest that
                    # references them, same as segments and tombstones
                    for seg in segments:
                        if seg.name not in self._codes_paths:
                            self._codes_paths[seg.name] = (
                                manifest_lib.write_codes(
                                    self.directory, seg.name,
                                    self._codes[seg.name],
                                )
                            )
                manifest_lib.write(
                    self.directory,
                    self._manifest(rel, version=version, segments=segments,
                                   shard_plan=plan),
                )
        get_registry().counter("index.commits").inc()
        with self._lock:
            self._version = version
            self._committed = segments
            self._staged = []
            self._shard_plan = plan
            self._tombstones_dirty = False
            self._meta_dirty = False
            self._shard_plan_dirty = False
            self._codes_dirty = False
            self.calibration.mark_clean()
            self._stamp += 1
        return version

    def compact(
        self,
        incremental: bool = False,
        policy: CompactionPolicy | None = None,
    ) -> str | None:
        """Merge segments into one, dropping their tombstoned rows.

        ``compact()`` merges *every* segment (the stop-the-world full
        merge); ``compact(incremental=True)`` asks the
        :class:`CompactionPolicy` for one tier of small or
        tombstone-heavy segments and merges only those — surviving
        segments, their codes files, and the tombstones that belong to
        them are carried through untouched, so each step is a small,
        bounded unit of work that can run between serving refreshes.
        Either way the step publishes through the same stage-then-publish
        manifest path an append commit uses, and search results are
        bit-identical before and after (victims' live rows reappear,
        id-sorted, in the merged segment at the first victim's position;
        masking already made their dead rows unmatchable).

        Commits atomically; victim segment checkpoints are
        garbage-collected only after the manifest bump; a bound derivable
        shard plan is re-derived over the new segment set (explicit plans
        are dropped).

        Args:
          incremental: merge only the policy-selected tier instead of
            everything.
          policy: the :class:`CompactionPolicy` an incremental step
            consults (default: ``CompactionPolicy()``); ignored for a
            full compact.

        Returns:
          The new merged segment's name; ``None`` when no merged segment
          was produced — the victims had no live rows (their space is
          still reclaimed and a version published), or, for an
          incremental step, no tier crossed the policy's thresholds (a
          fixed point: nothing is published at all).

        Raises:
          FileExistsError: a concurrent commit won the version race.
          Exception: a failed rebuild/write propagates with segments AND
            tombstones exactly as committed (no resurrection, no loss).
        """
        tr = get_tracer()
        t_start = tr.now() if tr.enabled else 0.0
        old = self.segments
        if incremental:
            pol = policy if policy is not None else CompactionPolicy()
            victims = pol.select(old, self._tombstones)
            if not victims:
                return None
        else:
            victims = list(old)
        victim_names = {s.name for s in victims}
        keep_v, keep_i = [], []
        for seg in victims:
            ids = np.asarray(seg.index.ids).astype(np.int64)
            live = ids >= 0
            if self._tombstones.size:
                live &= ~np.isin(ids, self._tombstones)
            keep_v.append(np.asarray(seg.index.vecs)[live])
            keep_i.append(ids[live])
        all_v = np.concatenate(keep_v) if keep_v else np.empty((0, self.dim))
        all_i = (
            np.concatenate(keep_i) if keep_i else np.empty((0,), np.int64)
        )
        order = np.argsort(all_i, kind="stable")
        # build + durably publish first; the handle's state is only
        # replaced once the new manifest exists, so a failed rebuild
        # leaves segments AND tombstones exactly as they were
        if all_i.size == 0:
            merged: list[Segment] = []
        else:
            built = build_index(
                jnp.asarray(all_v[order], jnp.float32),
                self.tree,
                self.mesh,
                ids=jnp.asarray(all_i[order].astype(np.int32)),
                wire_dtype=self.wire_dtype,
            )
            jax.block_until_ready(built.vecs)
            seg = Segment.from_built(self._next_name(), built)
            if self.directory:
                seg.save(self._segments_dir())
            merged = [seg]
        # survivors keep their order; the merged segment takes the first
        # victim's slot, so the cross-segment merge visits candidates in
        # the same segment-major order as before (stable on ties)
        new_committed: list[Segment] = []
        placed = False
        for s in old:
            if s.name in victim_names:
                if not placed:
                    new_committed.extend(merged)
                    placed = True
                continue
            new_committed.append(s)
        if not placed:
            new_committed.extend(merged)
        # tombstones pointing into the victims died with them; the rest
        # (ids living in surviving segments) stay masked
        new_tombstones = np.empty((0,), np.int64)
        if incremental and self._tombstones.size:
            survivors = [s for s in old if s.name not in victim_names]
            keep_ts = np.zeros(self._tombstones.shape, bool)
            for s in survivors:
                if not s.valid_rows or not s.overlaps(self._tombstones):
                    continue
                sorted_ids, _ = s.id_index()
                pos = np.searchsorted(sorted_ids, self._tombstones)
                keep_ts |= (pos < sorted_ids.size) & (
                    sorted_ids[np.minimum(pos, sorted_ids.size - 1)]
                    == self._tombstones
                )
            new_tombstones = self._tombstones[keep_ts]
        new_codes, new_codes_paths = self._codes, self._codes_paths
        if self.quantizer is not None:
            # the quantizer survives compaction unchanged (codebooks are
            # trained, not positional); only the merged segment's codes
            # are re-encoded — survivors keep their code files
            new_codes = {
                name: c for name, c in self._codes.items()
                if name not in victim_names
            }
            for s in merged:
                new_codes[s.name] = self.quantizer.encode(s.host_vecs())
            new_codes_paths = {
                name: p for name, p in self._codes_paths.items()
                if name not in victim_names
            }
            if self.directory:
                for s in new_committed:
                    if s.name not in new_codes_paths:
                        new_codes_paths[s.name] = manifest_lib.write_codes(
                            self.directory, s.name, new_codes[s.name]
                        )
        version = self._version + 1
        plan = self._plan_for(new_committed)
        if self.directory:
            rel = None
            if new_tombstones.size:
                rel = manifest_lib.write_tombstones(
                    self.directory, version, new_tombstones
                )
            manifest_lib.write(
                self.directory,
                self._manifest(rel, version=version,
                               segments=new_committed, shard_plan=plan,
                               codes_paths=new_codes_paths),
            )
        with self._lock:
            self._committed = new_committed
            self._staged = []
            self._shard_plan = plan
            self._shard_plan_dirty = False
            self._tombstones = new_tombstones
            self._tombstones_dirty = False
            self._meta_dirty = False
            self._codes = new_codes
            self._codes_paths = new_codes_paths
            self._codes_dirty = False
            self.calibration.mark_clean()
            self._version = version
            self._views = None
            self._stamp += 1
        if self.directory:
            self._gc_segments(old)
        if tr.enabled:
            tr.add_span(
                "index.compact", t_start, tr.now(),
                segments_in=len(victims), rows_out=int(all_i.size),
                version=version, incremental=bool(incremental),
            )
        reg = get_registry()
        reg.counter("index.compacts").inc()
        reg.gauge("index.tombstones_live").set(int(new_tombstones.size))
        return merged[0].name if merged else None

    def _gc_segments(self, old: Sequence[Segment]) -> None:
        live = {s.name for s in self._committed}
        for seg in old:
            if seg.name in live:
                continue
            shutil.rmtree(
                os.path.join(self._segments_dir(), seg.name),
                ignore_errors=True,
            )
            try:
                os.remove(os.path.join(
                    self.directory, manifest_lib.CODES_SUBDIR,
                    f"{seg.name}.npy",
                ))
            except OSError:
                pass

    def gc(self, *, dry_run: bool = False) -> dict:
        """Collect artifacts unreachable from the newest *on-disk* manifest:
        superseded manifest versions, orphan segment checkpoints from
        interrupted appends/compactions, unreferenced tombstone/code
        files, and stray ``*.tmp`` files from crashed publications.

        This handle's own staged (not-yet-committed) segments are never
        collected — only orphans no live handle can still publish.
        Removing an orphan segment directory un-reserves its name;
        that is safe because its code file (if any) is removed in the
        same pass.

        Args:
          dry_run: report what *would* be removed without touching disk.

        Returns:
          ``{"manifests": [...], "segments": [...], "tombstones": [...],
          "codes": [...], "tmp": [...]}`` — relative paths, collected (or
          merely listed, under ``dry_run``). All lists empty for an
          ephemeral index.
        """
        report: dict[str, list[str]] = {
            "manifests": [], "segments": [], "tombstones": [],
            "codes": [], "tmp": [],
        }
        d = self.directory
        if not d:
            return report
        m = manifest_lib.latest(d)
        if m is None:
            return report
        keep_segments = set(m.segments) | {s.name for s in self._staged}
        keep_files = {m.tombstones} if m.tombstones else set()
        if m.codes:
            keep_files |= set(m.codes.get("segments", {}).values())
        for v in manifest_lib.list_versions(d):
            if v != m.version:
                report["manifests"].append(
                    os.path.basename(manifest_lib.manifest_path(d, v))
                )
        seg_dir = os.path.join(d, manifest_lib.SEGMENTS_SUBDIR)
        if os.path.isdir(seg_dir):
            for name in sorted(os.listdir(seg_dir)):
                if name.startswith("seg_") and name not in keep_segments:
                    report["segments"].append(
                        os.path.join(manifest_lib.SEGMENTS_SUBDIR, name)
                    )
        for sub, key in (
            (manifest_lib.TOMBSTONES_SUBDIR, "tombstones"),
            (manifest_lib.CODES_SUBDIR, "codes"),
        ):
            p = os.path.join(d, sub)
            if not os.path.isdir(p):
                continue
            for name in sorted(os.listdir(p)):
                rel = os.path.join(sub, name)
                if name.endswith(".tmp"):
                    report["tmp"].append(rel)
                elif rel not in keep_files:
                    report[key].append(rel)
        for name in sorted(os.listdir(d)):
            if name.endswith(".tmp") and os.path.isfile(os.path.join(d, name)):
                report["tmp"].append(name)
        if not dry_run:
            for rel in report["segments"]:
                shutil.rmtree(os.path.join(d, rel), ignore_errors=True)
            for key in ("manifests", "tombstones", "codes", "tmp"):
                for rel in report[key]:
                    try:
                        os.remove(os.path.join(d, rel))
                    except OSError:
                        pass
        return report

    # -- read path ----------------------------------------------------------
    def read_rows(self, ids, *, segments=None, tombstones=None) -> np.ndarray:
        """Host gather of stored descriptor vectors by id — the corpus
        rows live inside the segments, so anything that consumes a
        ``read_rows``/``dim`` block store (e.g. the serving trace
        generator) can read straight from the index; a grown ``--index-
        dir`` needs no separate ``corpus/`` store. Probes each
        range-overlapping segment through its cached id index — no
        resident concatenated corpus copy is built.

        Tombstoned ids read as missing *immediately* (not only after the
        compaction that physically drops them), so the result never
        depends on compaction timing.

        Requested ids may repeat and arrive in any order: probes are
        deduplicated to one *sorted* unique set, each segment is gathered
        at most once, and results scatter back to the request order — the
        rerank fetch path hands whole candidate tables here without
        pre-sorting.

        ``segments`` / ``tombstones`` override the live state with a
        pinned :class:`IndexSnapshot`'s cut — serving sessions rerank
        against the exact state their candidates came from, so a
        concurrent delete or compaction can never make an in-flight
        request's candidate id unreadable."""
        segs = self.segments if segments is None else tuple(segments)
        ts = (
            self._tombstones if tombstones is None
            else np.asarray(tombstones, np.int64)
        )
        ids = np.atleast_1d(np.asarray(ids, np.int64))
        if ids.size and ids.min() < 0:
            # never let a requested -1 match a padding row's -1 id
            raise IndexError(f"descriptor ids must be >= 0; got {ids.min()}")
        if ids.size == 0:
            return np.empty((0, self.dim), np.float32)
        uniq, inverse = np.unique(ids, return_inverse=True)
        u_out = np.empty((uniq.size, self.dim), np.float32)
        u_found = np.zeros(uniq.size, bool)
        for seg in segs:
            if u_found.all() or not seg.overlaps(uniq):
                continue
            sorted_ids, order = seg.id_index()
            pos = np.searchsorted(sorted_ids, uniq)
            hit = (
                ~u_found
                & (pos < sorted_ids.size)
                & (sorted_ids[np.minimum(pos, sorted_ids.size - 1)] == uniq)
            )
            if hit.any():
                u_out[hit] = seg.host_vecs()[order[pos[hit]]]
                u_found |= hit
        if ts.size:
            u_found &= ~np.isin(uniq, ts)
        if not u_found.all():
            found = u_found[inverse]
            missing = ids[~found]
            raise IndexError(
                f"descriptor ids not in the index (absent or deleted): "
                f"{missing[:8].tolist()}"
                + ("..." if missing.size > 8 else "")
            )
        return u_out[inverse]

    def segment_views(self) -> tuple[DistributedIndex, ...]:
        """Per-segment indexes with tombstones masked (cached until the
        next append/delete/compact)."""
        if self._views is None:
            self._views = tuple(
                masked_view(s, self._tombstones) for s in self.segments
            )
        return self._views

    def search(
        self,
        queries,
        k: int = 10,
        *,
        plan: SearchPlan | None = None,
        layout: str = "auto",
        probes: int = 1,
        impl: str = "xla",
        block_rows: int | None = None,
        q_cap: int | None = None,
        q_tile: int | None = None,
        p_cap: int | None = None,
        rerank: int | None = None,
        cost_model="auto",
    ) -> SearchResult:
        """k-NN over every live row: one shared lookup build, one executor
        run per segment, one ascending-distance merge across segments.

        Args:
          queries: ``(q, dim)`` query rows (cast to float32).
          k: neighbours per query.
          plan: optional :class:`SearchPlan` template whose fields
            (layout, k, probes, impl, budgets) override the keyword
            arguments; budgets are still re-resolved per segment, since
            tile sizes must divide each segment's shard rows.
          layout/probes/impl/block_rows/q_cap/q_tile/p_cap: per-call plan
            knobs, as in :func:`repro.core.engine.plan`. ``layout`` also
            accepts ``"scan_codes"`` (ADC scan over PQ codes + exact
            rerank) once :meth:`enable_codes` has run; ``"auto"`` lets
            the cost model pick the codes tier on its own.
          rerank: ADC candidates per query to fetch + exactly rerank for
            the ``scan_codes`` layout (default from
            :func:`~repro.core.engine.plan.default_rerank`).
          cost_model: which model ranks an ``"auto"`` layout (``"auto"``
            / ``"heuristic"`` / ``"observed"`` / ``"fitted"``), consulting
            *this index's* manifest-persisted calibration store.

        Returns:
          A :class:`SearchResult`: ``(q, k)`` ids (``-1`` where fewer
          than ``k`` live rows matched) and squared-L2 dists (``inf``
          there), plus exact pairs/overflow counters. Dense layouts are
          bit-identical to a one-shot build+search over the concatenated
          live rows; ``scan_codes`` returns the exact-reranked top-k of
          the ADC candidate set (approximate recall, exact ordering).

        Raises:
          ValueError: invalid plan knobs (see
            :func:`repro.core.engine.plan`), or
            ``layout="scan_codes"`` without :meth:`enable_codes`.
        """
        if plan is not None:
            layout, k, probes, impl = plan.layout, plan.k, plan.probes, plan.impl
            block_rows = plan.block_rows if block_rows is None else block_rows
            q_cap = plan.q_cap if q_cap is None else q_cap
            q_tile = plan.q_tile if q_tile is None else q_tile
            p_cap = plan.p_cap if p_cap is None else p_cap
            rerank = plan.rerank if rerank is None else rerank
        queries = jnp.asarray(queries, jnp.float32)
        q = queries.shape[0]
        views = self.segment_views()
        if not views:
            return SearchResult(
                ids=jnp.full((q, k), -1, jnp.int32),
                dists=jnp.full((q, k), jnp.inf, jnp.float32),
                pairs=jnp.zeros((), jnp.float32),
                q_cap_overflow=jnp.zeros((), jnp.int32),
            )
        n_shards = data_axis_size(self.mesh)
        # ADC distances are approximations, incomparable with the dense
        # layouts' exact partial distances, so the codes-vs-exact decision
        # is resolved ONCE on the aggregate shape — per-segment plans then
        # all run the same tier and the cross-segment merge stays sound
        if layout == "scan_codes" and self.quantizer is None:
            raise ValueError(
                "layout='scan_codes' needs PQ codes; call "
                "enable_codes() first"
            )
        use_codes = False
        if self.quantizer is not None and layout in ("auto", "scan_codes"):
            agg = make_plan(
                rows=sum(v.rows for v in views),
                n_leaves=self.n_leaves, n_queries=q, n_shards=n_shards,
                k=k, probes=probes, layout=layout, impl=impl,
                model=cost_model, calibration=self.calibration,
                dim=self.dim, rerank=rerank,
                code_m=self.quantizer.m, code_bits=self.quantizer.bits,
            )
            use_codes = agg.layout == "scan_codes"
        lookup = jit_build_lookup(self.tree, queries, probes=probes)
        per = []
        pruned = 0
        segs_all = self.segments
        live_counts = np.array(
            [s.valid_rows for s in segs_all], np.int64
        ) - dead_counts(segs_all, self._tombstones)
        # dense-tier norm-bound pruning: a segment whose valid rows' L2
        # norms all sit outside [kth_dist - margin] of every query's
        # running top-k cannot contribute (||p - q||^2 >= (||p|| - ||q||)^2)
        # — result-safe by construction, and only exact dense distances
        # qualify (ADC distances are approximations, so the codes tier
        # never norm-prunes). Tracking the running top-k forces each
        # segment's result before the next dispatch, which is the price of
        # the bound; skipped entirely when no segment carries norm stats.
        q_norms = best_d = None
        if not use_codes and any(s.min_norm >= 0.0 for s in segs_all):
            q_norms = np.linalg.norm(np.asarray(queries, np.float64), axis=1)
            best_d = np.full((q, k), np.inf)
        for i, (seg, view) in enumerate(zip(segs_all, views)):
            if live_counts[i] == 0:
                # every row is padding or tombstoned: nothing to match
                pruned += 1
                continue
            if (
                best_d is not None
                and seg.min_norm >= 0.0
                and np.isfinite(best_d[:, -1]).all()
            ):
                gap = np.maximum(
                    seg.min_norm - q_norms, q_norms - seg.max_norm
                )
                lb = np.maximum(gap, 0.0) ** 2
                # margin absorbs fp32 accumulation error in the exact
                # distances (~1e-7 relative; 1e-4 is overwhelmingly safe)
                margin = 1e-4 * (seg.max_norm + q_norms) ** 2 + 1e-6
                if (lb > best_d[:, -1] + margin).all():
                    pruned += 1
                    continue
            if use_codes:
                p = make_plan(
                    rows=view.rows, n_leaves=self.n_leaves, n_queries=q,
                    n_shards=n_shards, k=k, probes=probes,
                    layout="scan_codes", impl=impl, block_rows=block_rows,
                    q_cap=q_cap, model=cost_model,
                    calibration=self.calibration,
                    dim=self.dim, rerank=rerank,
                    code_m=self.quantizer.m, code_bits=self.quantizer.bits,
                )
                per.append(search_with_lookup(
                    view, lookup, p, self.mesh, n_queries=q,
                    codes=self._codes[seg.name],
                    codebooks=self.quantizer.codebooks,
                ))
                continue
            p = make_plan(
                rows=view.rows,
                n_leaves=self.n_leaves,
                n_queries=q,
                n_shards=n_shards,
                k=k,
                probes=probes,
                layout=layout,
                impl=impl,
                block_rows=block_rows,
                q_cap=q_cap,
                q_tile=q_tile,
                p_cap=p_cap,
                model=cost_model,
                calibration=self.calibration,
            )
            per.append(
                search_with_lookup(view, lookup, p, self.mesh, n_queries=q)
            )
            if best_d is not None:
                best_d = np.sort(
                    np.concatenate(
                        [best_d, np.asarray(per[-1].dists, np.float64)],
                        axis=1,
                    ),
                    axis=1,
                )[:, :k]
        if pruned:
            get_registry().counter("index.segments_pruned").inc(pruned)
        if not per:
            # every segment was pruned — same sentinel as an empty index
            return SearchResult(
                ids=jnp.full((q, k), -1, jnp.int32),
                dists=jnp.full((q, k), jnp.inf, jnp.float32),
                pairs=jnp.zeros((), jnp.float32),
                q_cap_overflow=jnp.zeros((), jnp.int32),
            )
        if use_codes:
            r_max = max(r.ids.shape[1] for r in per)
            cand = per[0] if len(per) == 1 else _merge_results(per, r_max)
            cand_ids = np.asarray(cand.ids)
            with get_tracer().span("engine.rerank", k=k,
                                   candidates=int(cand_ids.shape[1])):
                ids_r, dists_r = rerank_exact(
                    self.read_rows, np.asarray(queries), cand_ids, k
                )
            return SearchResult(
                ids=jnp.asarray(ids_r),
                dists=jnp.asarray(dists_r),
                pairs=cand.pairs,
                q_cap_overflow=cand.q_cap_overflow,
            )
        if len(per) == 1:
            return per[0]
        return _merge_results(per, k)


def _merge_results(per: Sequence[SearchResult], k: int) -> SearchResult:
    """Fold per-segment k-NN tables into one — the same ascending-distance
    merge the executors apply across shards (stable on ties, so
    segment-major order mirrors the one-shot table's candidate order)."""
    all_i = np.concatenate([np.asarray(r.ids) for r in per], axis=1)
    all_d = np.concatenate([np.asarray(r.dists) for r in per], axis=1)
    sel = np.argsort(all_d, axis=1, kind="stable")[:, :k]
    return SearchResult(
        ids=jnp.asarray(np.take_along_axis(all_i, sel, axis=1)),
        dists=jnp.asarray(np.take_along_axis(all_d, sel, axis=1)),
        pairs=sum(r.pairs for r in per),
        q_cap_overflow=sum(r.q_cap_overflow for r in per),
    )
