"""Sharded scatter-gather search: partition an :class:`Index` across shards.

The paper's scalability story (§2.4, Fig 5) is distributed search: the 30B-
descriptor collection is split into partitions, map tasks scan partitions
independently, and a reduce step fuses per-partition candidate lists into
the final top-k. :class:`ShardPlan` + :class:`ShardedIndex` are that
workflow over the segment lifecycle: an explicit, manifest-persisted
mapping of the index's immutable segments onto N shards, and a
scatter-gather ``search`` that scans each shard's segments independently
and merges the per-shard candidates.

Exactness. The gather merge is **bit-identical** to the unsharded
``Index.search`` because every candidate carries its *global merge slot*
``segment_ordinal * k + position``: the unsharded merge is a stable
ascending-distance sort over the segment-ordered concatenation, i.e. a
total order by ``(distance, slot)``. Each shard keeps its local top-k
under that same total order (shard-local segment lists preserve global
append order, so a stable local sort *is* slot order), and the top-k of a
union of per-shard top-k lists under a total order equals the top-k of all
candidates. Ties — exact duplicate vectors included — therefore resolve
identically at any shard count.

Parallelism. Per-shard scans reuse the engine's jit-cached executors
(:func:`repro.core.search.search_with_lookup`); the lookup table is built
once and broadcast to every shard (the paper ships it to every map task
via HDFS). With enough devices, :func:`repro.distributed.meshutil.
shard_submeshes` gives each shard its own device group so shard scans run
on disjoint hardware; on one device every shard shares the mesh and runs
sequentially-but-isolated — same results, summed wall time.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.codes import rerank_exact
from repro.core.engine import (
    PlanShapes,
    SearchPlan,
    fitted_component,
    plan as make_plan,
    scale_slab_budget,
    shard_slab_scales,
)
from repro.core.engine.executors import SearchResult
from repro.core.search import jit_build_lookup, search_with_lookup
from repro.distributed.meshutil import data_axis_size, shard_submeshes
from repro.index.segment import dead_counts, place_on
from repro.obs import get_registry

STRATEGIES = ("round_robin", "balanced", "explicit")


@dataclasses.dataclass(frozen=True)
class ShardPlan:
    """Explicit mapping of segment names onto shards.

    ``assignment[s]`` lists the segment names owned by shard ``s``, each in
    global append order (the order the index's manifest lists them) — the
    invariant the bit-identical merge relies on. Plans are value objects:
    derive one with :meth:`round_robin` / :meth:`balanced` /
    :meth:`explicit` (or :meth:`for_index`), persist it via
    ``Index.set_shard_plan`` + ``commit`` and it comes back from
    ``Index.open``.
    """

    n_shards: int
    strategy: str  # "round_robin" | "balanced" | "explicit"
    assignment: tuple[tuple[str, ...], ...]  # per shard, global order

    def __post_init__(self):
        if self.n_shards < 1:
            raise ValueError(f"{self.n_shards=} must be >= 1")
        if self.strategy not in STRATEGIES:
            raise ValueError(
                f"unknown shard strategy {self.strategy!r}; want {STRATEGIES}"
            )
        if len(self.assignment) != self.n_shards:
            raise ValueError(
                f"assignment has {len(self.assignment)} shards; plan says "
                f"{self.n_shards}"
            )
        flat = [name for shard in self.assignment for name in shard]
        if len(set(flat)) != len(flat):
            raise ValueError("shard plan assigns a segment twice")

    # -- derivation ---------------------------------------------------------
    @classmethod
    def round_robin(cls, segment_names: Sequence[str],
                    n_shards: int) -> "ShardPlan":
        """Segment ``i`` goes to shard ``i % n_shards`` — the paper's
        partition-by-arrival default; even counts, arbitrary sizes."""
        names = list(segment_names)
        return cls(
            n_shards=n_shards,
            strategy="round_robin",
            assignment=tuple(
                tuple(names[s::n_shards]) for s in range(n_shards)
            ),
        )

    @classmethod
    def balanced(cls, segment_names: Sequence[str], sizes: Sequence[int],
                 n_shards: int) -> "ShardPlan":
        """Size-balanced greedy (LPT): biggest segment first onto the
        least-loaded shard, so shard scan times stay even when segment
        sizes are skewed (many small appends + one compacted giant)."""
        names = list(segment_names)
        if len(sizes) != len(names):
            raise ValueError(f"{len(sizes)} sizes for {len(names)} segments")
        order = sorted(range(len(names)), key=lambda i: (-int(sizes[i]), i))
        loads = [0] * n_shards
        owner: dict[int, int] = {}
        for i in order:
            s = min(range(n_shards), key=lambda j: (loads[j], j))
            owner[i] = s
            loads[s] += int(sizes[i])
        return cls(
            n_shards=n_shards,
            strategy="balanced",
            # global (append) order within each shard, not LPT pick order
            assignment=tuple(
                tuple(names[i] for i in range(len(names)) if owner[i] == s)
                for s in range(n_shards)
            ),
        )

    @classmethod
    def explicit(cls, assignment: Sequence[Sequence[str]]) -> "ShardPlan":
        """Pin segments to shards by hand (operator override)."""
        return cls(
            n_shards=len(assignment),
            strategy="explicit",
            assignment=tuple(tuple(s) for s in assignment),
        )

    @classmethod
    def for_index(cls, index, n_shards: int,
                  strategy: str = "round_robin") -> "ShardPlan":
        """Derive a plan over ``index``'s current segments (committed +
        staged, in append order).

        Raises ``ValueError`` for an unknown or non-derivable strategy
        (``explicit`` plans cannot be derived — build one with
        :meth:`explicit`).
        """
        segs = index.segments
        if strategy == "round_robin":
            return cls.round_robin([s.name for s in segs], n_shards)
        if strategy == "balanced":
            return cls.balanced(
                [s.name for s in segs], [s.valid_rows for s in segs], n_shards
            )
        raise ValueError(
            f"cannot derive a {strategy!r} plan; want one of "
            "('round_robin', 'balanced')"
        )

    # -- queries ------------------------------------------------------------
    def shard_of(self, segment_name: str) -> int:
        for s, names in enumerate(self.assignment):
            if segment_name in names:
                return s
        raise KeyError(f"segment {segment_name!r} not in shard plan")

    def covers(self, segment_names: Sequence[str]) -> bool:
        """True when the plan assigns exactly the given segment set (the
        staleness check: an append/compact since the plan was made means a
        re-derive is needed)."""
        flat = {n for shard in self.assignment for n in shard}
        return flat == set(segment_names)

    def rederived(self, index) -> "ShardPlan":
        """The same strategy re-applied to ``index``'s current segments —
        how a persisted plan follows appends and compactions. Explicit
        plans cannot be re-derived and raise ``ValueError``."""
        return self.for_index(index, self.n_shards, self.strategy)

    # -- persistence --------------------------------------------------------
    def to_json(self) -> dict:
        return {
            "n_shards": self.n_shards,
            "strategy": self.strategy,
            "assignment": [list(s) for s in self.assignment],
        }

    @classmethod
    def from_json(cls, d: dict) -> "ShardPlan":
        return cls(
            n_shards=int(d["n_shards"]),
            strategy=d["strategy"],
            assignment=tuple(tuple(s) for s in d["assignment"]),
        )

    def describe(self) -> str:
        sizes = "/".join(str(len(s)) for s in self.assignment)
        return f"{self.strategy} x{self.n_shards} (segments {sizes})"


# ---------------------------------------------------------------------------
# merge helpers — shared by ShardedIndex (host path) and the sharded
# serving session's gather. A *slot* is a candidate's position in the
# unsharded segment-ordered concatenation: segment_ordinal * k + column.
# ---------------------------------------------------------------------------


def shard_local_partial(
    per_segment: Sequence[SearchResult], ordinals: Sequence[int], k: int
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Fold one shard's per-segment k-NN tables into its local top-k.

    ``ordinals`` are the segments' global append positions (ascending, so
    the concatenated slot row is strictly increasing and a *stable* sort by
    distance is exactly the ``(distance, slot)`` total order). Returns
    ``(ids, dists, slots)`` of shape ``(q, k)`` each.
    """
    ids = np.concatenate([np.asarray(r.ids) for r in per_segment], axis=1)
    dists = np.concatenate([np.asarray(r.dists) for r in per_segment], axis=1)
    q = ids.shape[0]
    slots = np.concatenate(
        [np.arange(g * k, g * k + k, dtype=np.int64) for g in ordinals]
    )
    slots = np.broadcast_to(slots, (q, slots.size))
    sel = np.argsort(dists, axis=1, kind="stable")[:, :k]
    return (
        np.take_along_axis(ids, sel, axis=1),
        np.take_along_axis(dists, sel, axis=1),
        np.take_along_axis(slots, sel, axis=1),
    )


def fitted_shard_scales(
    index,
    shard_views,
    meshes,
    *,
    cost_model,
    n_queries: int,
    k: int,
    probes: int,
    layout: str,
    impl: str,
    max_scale: float = 2.0,
) -> list[float]:
    """Per-shard slab-headroom multipliers from fitted per-shard costs —
    shared by :meth:`ShardedIndex.search` and the sharded serving
    session's bucket ladders.

    Each non-empty shard's total rows are priced by the fitted model;
    the probe plan supplying the tile features is derived under the SAME
    ``cost_model`` the per-segment plans will use, so the priced layout
    matches the one that actually executes (a fitted flip prices the
    flipped layout). Shards above the mean earn proportionally more slab
    headroom (``engine.shard_slab_scales``, grow-only, so result-safe).
    All ones — the uniform-split fallback — until ``index.calibration``
    yields a usable fit, or when any shard cannot be planned/priced.
    """
    fitted = fitted_component(cost_model, index.calibration)
    if fitted is None:
        return [1.0] * len(shard_views)
    probe_plans, shapes = [], []
    for shard, mesh in zip(shard_views, meshes):
        if not shard:
            continue
        rows = sum(int(v.rows) for _, v in shard)
        n_shards = data_axis_size(mesh)
        try:
            probe_plans.append(make_plan(
                rows=rows, n_leaves=index.n_leaves, n_queries=n_queries,
                n_shards=n_shards, k=k, probes=probes, layout=layout,
                impl=impl, model=cost_model,
                calibration=index.calibration,
            ))
        except ValueError:  # e.g. unroutable leaves at this shard
            return [1.0] * len(shard_views)
        shapes.append(PlanShapes(
            rows=rows, n_queries=n_queries, n_shards=n_shards,
            n_leaves=index.n_leaves,
        ))
    scales = iter(shard_slab_scales(fitted, probe_plans, shapes,
                                    max_scale=max_scale))
    return [next(scales) if shard else 1.0 for shard in shard_views]


def _pad_cols(res: SearchResult, width: int) -> SearchResult:
    """Right-pad a candidate table to ``width`` columns with the engine's
    absent-row sentinels (``-1``/``inf`` sort behind every candidate)."""
    w = int(res.ids.shape[1])
    if w == width:
        return res
    q = int(res.ids.shape[0])
    ids = np.full((q, width), -1, np.int32)
    dists = np.full((q, width), np.inf, np.float32)
    ids[:, :w] = np.asarray(res.ids)
    dists[:, :w] = np.asarray(res.dists)
    return SearchResult(
        ids=jnp.asarray(ids), dists=jnp.asarray(dists),
        pairs=res.pairs, q_cap_overflow=res.q_cap_overflow,
    )


def gather_merge(
    partials: Sequence[tuple[np.ndarray, np.ndarray, np.ndarray]], k: int
) -> tuple[np.ndarray, np.ndarray]:
    """Fuse per-shard ``(ids, dists, slots)`` partials into the global
    top-k, ordered by ``(distance, slot)`` — bit-identical to the unsharded
    stable merge over the segment-ordered concatenation."""
    ids = np.concatenate([p[0] for p in partials], axis=1)
    dists = np.concatenate([p[1] for p in partials], axis=1)
    slots = np.concatenate([p[2] for p in partials], axis=1)
    # primary key dists, ties by global slot (np.lexsort: last key wins)
    sel = np.lexsort((slots, dists), axis=1)[:, :k]
    return (
        np.take_along_axis(ids, sel, axis=1),
        np.take_along_axis(dists, sel, axis=1),
    )


class ShardedIndex:
    """Scatter-gather search view over an :class:`Index` and a
    :class:`ShardPlan`.

    Wraps — never copies — the underlying index: segments stay where the
    lifecycle put them, tombstones are applied by the same masked views,
    and the plan only decides which shard scans which segment. Construct
    with an explicit ``plan``, or give ``n_shards`` (+ ``strategy``) to
    derive one; a persisted plan on the index is picked up when neither is
    given.

    ``segments`` / ``views`` / ``codes`` / ``tombstones`` pin the scatter
    to one :class:`~repro.index.lifecycle.IndexSnapshot`'s cut instead of
    the index's live state — the read-during-write path: a serving
    session's sharded runtimes and its rerank fetches keep resolving
    against the pinned state while the index mutates underneath.
    """

    def __init__(
        self,
        index,
        plan: ShardPlan | None = None,
        *,
        n_shards: int | None = None,
        strategy: str = "round_robin",
        segments=None,
        views=None,
        codes=None,
        tombstones=None,
    ):
        self.index = index
        self._pin_segments = (
            tuple(segments) if segments is not None else None
        )
        self._pin_views = tuple(views) if views is not None else None
        self._pin_codes = dict(codes) if codes is not None else None
        self._pin_tombstones = (
            np.asarray(tombstones, np.int64)
            if tombstones is not None else None
        )
        if plan is None:
            if n_shards is not None:
                plan = ShardPlan.for_index(index, n_shards, strategy)
            elif getattr(index, "shard_plan", None) is not None:
                plan = index.shard_plan
            else:
                raise ValueError(
                    "need a ShardPlan, n_shards, or an index with a "
                    "persisted shard plan"
                )
        if not plan.covers([s.name for s in self.segments]):
            raise ValueError(
                "shard plan does not cover the index's current segments "
                f"({plan.describe()} vs {len(self.segments)} segments); "
                "re-derive with plan.rederived(index)"
            )
        self.plan = plan
        self._meshes = shard_submeshes(index.mesh, plan.n_shards)
        self._placed_views: dict = {}

    @property
    def n_shards(self) -> int:
        return self.plan.n_shards

    @property
    def segments(self) -> tuple:
        """The segment cut this view scatters over: the pinned snapshot's
        when given, else the index's live committed + staged set."""
        if self._pin_segments is not None:
            return self._pin_segments
        return tuple(self.index.segments)

    def segment_views(self) -> tuple:
        if self._pin_views is not None:
            return self._pin_views
        return tuple(self.index.segment_views())

    @property
    def tombstones(self) -> np.ndarray:
        if self._pin_tombstones is not None:
            return self._pin_tombstones
        return self.index.tombstones

    def _codes_for(self, name: str) -> np.ndarray:
        codes = (
            self._pin_codes if self._pin_codes is not None
            else self.index._codes
        )
        return codes[name]

    def persist_plan(self) -> None:
        """Stage the plan into the index manifest (durable at the next
        ``commit``)."""
        self.index.set_shard_plan(self.plan)

    def shard_views(self) -> list[list[tuple[int, object]]]:
        """Per shard: ``(global_ordinal, masked DistributedIndex view)``
        pairs in global append order. Views are the index's cached
        tombstone-masked views — refreshed automatically after
        append/delete/compact on the underlying index — placed on the
        shard's own devices where it has a submesh."""
        by_name = {
            s.name: (g, v)
            for g, (s, v) in enumerate(
                zip(self.segments, self.segment_views())
            )
        }
        return [
            [(g, self._placed(si, g, v))
             for g, v in (by_name[name] for name in shard)]
            for si, shard in enumerate(self.plan.assignment)
        ]

    def _placed(self, si: int, g: int, view):
        """``view`` re-cut onto shard ``si``'s submesh (cached per view:
        placing moves the segment's rows between devices)."""
        mesh = self._meshes[si]
        if mesh == self.index.mesh:
            return view
        hit = self._placed_views.get((si, g))
        if hit is None or hit[0] is not view:
            hit = (view, place_on(view, mesh))
            self._placed_views[(si, g)] = hit
        return hit[1]

    def replicated(self, si: int, x):
        """``x`` (a pytree the scatter legs share, e.g. the tree or a
        lookup table) replicated over shard ``si``'s devices."""
        mesh = self._meshes[si]
        if mesh == self.index.mesh:
            return x
        return jax.device_put(x, NamedSharding(mesh, PartitionSpec()))

    def stats(self) -> dict:
        segs = {s.name: s for s in self.segments}
        per = [
            {
                "shard": s,
                "segments": list(names),
                "rows": sum(segs[n].valid_rows for n in names),
            }
            for s, names in enumerate(self.plan.assignment)
        ]
        return {"plan": self.plan.to_json(), "shards": per}

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
        """Scatter-gather k-NN: one shared lookup build, each shard scans
        its segments with the engine's jit-cached executors, per-shard
        candidates merge by ``(distance, slot)``.

        Args mirror :meth:`Index.search` exactly — including the
        ``plan`` template, whose fields override the keyword arguments,
        and ``cost_model``, which consults the index's calibration store.
        When a fitted model is available, per-shard predicted costs set
        per-shard slab budgets (``shard_slab_scales``): a shard the fit
        prices above the mean gets proportionally more slab headroom in
        place of the uniform split. Scales only ever *grow* budgets, so
        in the zero-overflow regime (``q_cap_overflow == 0``, the one
        every identity test pins down) results are bit-identical to
        :meth:`Index.search` (ids and distances, both layouts, any
        ``probes``, tombstones respected) at every shard count and under
        every ``cost_model``; when a derived slab *would* overflow, a
        grown slab can only recover candidates the uniform split
        truncated — strictly closer to the true k-NN, overflow still
        counted — see the module docstring for the slot argument.

        Returns a :class:`SearchResult`; ``pairs`` / ``q_cap_overflow``
        are summed across shards. Raises ``ValueError`` via ``plan()``
        for invalid layout/probes combinations.
        """
        if plan is not None:
            layout, k, probes, impl = (
                plan.layout, plan.k, plan.probes, plan.impl,
            )
            block_rows = plan.block_rows if block_rows is None else block_rows
            q_cap = plan.q_cap if q_cap is None else q_cap
            q_tile = plan.q_tile if q_tile is None else q_tile
            p_cap = plan.p_cap if p_cap is None else p_cap
            rerank = plan.rerank if rerank is None else rerank
        queries = jnp.asarray(queries, jnp.float32)
        q = queries.shape[0]
        views = self.shard_views()
        if not any(views):
            return SearchResult(
                ids=jnp.full((q, k), -1, jnp.int32),
                dists=jnp.full((q, k), jnp.inf, jnp.float32),
                pairs=jnp.zeros((), jnp.float32),
                q_cap_overflow=jnp.zeros((), jnp.int32),
            )
        # codes-vs-exact resolves ONCE on the aggregate shape (ADC and
        # exact distances are incomparable), exactly like Index.search
        pq = getattr(self.index, "quantizer", None)
        if layout == "scan_codes" and pq is None:
            raise ValueError(
                "layout='scan_codes' needs PQ codes; call "
                "enable_codes() first"
            )
        use_codes = False
        if pq is not None and layout in ("auto", "scan_codes"):
            agg = make_plan(
                rows=sum(int(v.rows) for shard in views for _, v in shard),
                n_leaves=self.index.n_leaves, n_queries=q,
                n_shards=data_axis_size(self.index.mesh), k=k,
                probes=probes, layout=layout, impl=impl, model=cost_model,
                calibration=self.index.calibration,
                dim=self.index.dim, rerank=rerank,
                code_m=pq.m, code_bits=pq.bits,
            )
            use_codes = agg.layout == "scan_codes"
        lookup = jit_build_lookup(self.index.tree, queries, probes=probes)
        scales = fitted_shard_scales(
            self.index, views, self._meshes, cost_model=cost_model,
            n_queries=q, k=k, probes=probes,
            layout="auto" if use_codes else layout, impl=impl,
        )
        if use_codes:
            return self._search_codes(
                queries, k, views, lookup, scales, probes=probes,
                impl=impl, block_rows=block_rows, q_cap=q_cap,
                rerank=rerank, cost_model=cost_model,
            )
        partials = []
        pairs = overflow = 0
        pruned = 0
        live = self._live_counts()
        for si, (shard, mesh, scale) in enumerate(
            zip(views, self._meshes, scales)
        ):
            if not shard:
                continue  # more shards than segments: an empty scatter leg
            n_shards = data_axis_size(mesh)
            shard_lookup = self.replicated(si, lookup)
            per_seg, ordinals = [], []
            for g, view in shard:
                if live[g] == 0:
                    # every row is padding or tombstoned — the segment can
                    # only emit (-1, inf) sentinels, so skipping it is
                    # result-identical (same prune as Index.search)
                    pruned += 1
                    continue
                p = make_plan(
                    rows=view.rows,
                    n_leaves=self.index.n_leaves,
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
                    calibration=self.index.calibration,
                )
                # never scale a budget the caller pinned: a pinned
                # slab must reproduce exactly (Args mirror Index.search)
                pinned = (q_cap is not None
                          if p.layout == "point_major"
                          else p_cap is not None)
                if not pinned:
                    p = scale_slab_budget(
                        p, scale, n_queries=q,
                        shard_rows=view.rows // n_shards,
                    )
                per_seg.append(search_with_lookup(
                    view, shard_lookup, p, mesh, n_queries=q
                ))
                ordinals.append(g)
            if not per_seg:
                continue  # every segment of this shard was pruned
            partials.append(shard_local_partial(per_seg, ordinals, k))
            pairs = pairs + sum(r.pairs for r in per_seg)
            overflow = overflow + sum(r.q_cap_overflow for r in per_seg)
        if pruned:
            get_registry().counter("index.segments_pruned").inc(pruned)
        if not partials:
            return SearchResult(
                ids=jnp.full((q, k), -1, jnp.int32),
                dists=jnp.full((q, k), jnp.inf, jnp.float32),
                pairs=jnp.zeros((), jnp.float32),
                q_cap_overflow=jnp.zeros((), jnp.int32),
            )
        ids, dists = gather_merge(partials, k)
        return SearchResult(
            ids=jnp.asarray(ids),
            dists=jnp.asarray(dists),
            pairs=pairs,
            q_cap_overflow=overflow,
        )

    def _live_counts(self) -> np.ndarray:
        """Per-segment (global ordinal order) live-row counts under the
        active tombstone cut — the zero-live prune's input."""
        segs = self.segments
        valid = np.array([s.valid_rows for s in segs], np.int64)
        return valid - dead_counts(segs, self.tombstones)

    def _search_codes(
        self, queries, k, views, lookup, scales, *, probes, impl,
        block_rows, q_cap, rerank, cost_model,
    ) -> SearchResult:
        """Sharded ``scan_codes`` tier: every shard ADC-scans its segments,
        the gather merges *candidate* tables (slot-tagged, so the merged
        candidate set is deterministic at any shard count), and one global
        exact rerank over ``Index.read_rows`` produces the final top-k —
        the rerank is shard-count-invariant because it re-sorts candidates
        by id before fetching."""
        pq = self.index.quantizer
        q = queries.shape[0]
        shard_entries = []  # per shard: [(ordinal, SearchResult), ...]
        pairs = overflow = 0
        pruned = 0
        live = self._live_counts()
        segs = self.segments
        for si, (shard, mesh, scale) in enumerate(
            zip(views, self._meshes, scales)
        ):
            if not shard:
                continue
            n_shards = data_axis_size(mesh)
            shard_lookup = self.replicated(si, lookup)
            entries = []
            for g, view in shard:
                if live[g] == 0:
                    pruned += 1
                    continue
                p = make_plan(
                    rows=view.rows, n_leaves=self.index.n_leaves,
                    n_queries=q, n_shards=n_shards, k=k, probes=probes,
                    layout="scan_codes", impl=impl, block_rows=block_rows,
                    q_cap=q_cap, model=cost_model,
                    calibration=self.index.calibration,
                    dim=self.index.dim, rerank=rerank,
                    code_m=pq.m, code_bits=pq.bits,
                )
                # scan_codes slabs budget by q_cap (point-major family);
                # never scale a budget the caller pinned
                if q_cap is None:
                    p = scale_slab_budget(
                        p, scale, n_queries=q,
                        shard_rows=view.rows // n_shards,
                    )
                res = search_with_lookup(
                    view, shard_lookup, p, mesh, n_queries=q,
                    codes=self._codes_for(segs[g].name),
                    codebooks=pq.codebooks,
                )
                entries.append((g, res))
                pairs = pairs + res.pairs
                overflow = overflow + res.q_cap_overflow
            if entries:
                shard_entries.append(entries)
        if pruned:
            get_registry().counter("index.segments_pruned").inc(pruned)
        if not shard_entries:
            return SearchResult(
                ids=jnp.full((q, k), -1, jnp.int32),
                dists=jnp.full((q, k), jnp.inf, jnp.float32),
                pairs=jnp.zeros((), jnp.float32),
                q_cap_overflow=jnp.zeros((), jnp.int32),
            )
        # per-segment candidate widths can differ (rerank clamps to each
        # segment's block_rows); pad to one width so slots stay uniform
        r_max = max(
            int(res.ids.shape[1]) for e in shard_entries for _, res in e
        )
        partials = []
        for entries in shard_entries:
            per_seg = [_pad_cols(res, r_max) for _, res in entries]
            partials.append(shard_local_partial(
                per_seg, [g for g, _ in entries], r_max
            ))
        cand_ids, _ = gather_merge(partials, r_max)
        # rerank fetches resolve against the same (possibly pinned) cut
        # the candidates came from — a concurrent delete cannot turn a
        # candidate id into an IndexError mid-request
        ids_r, dists_r = rerank_exact(
            lambda ids: self.index.read_rows(
                ids, segments=segs, tombstones=self.tombstones
            ),
            np.asarray(queries), cand_ids, k,
        )
        return SearchResult(
            ids=jnp.asarray(ids_r),
            dists=jnp.asarray(dists_r),
            pairs=pairs,
            q_cap_overflow=overflow,
        )
