"""SearchSession: the long-lived serving core.

The paper's search phase is a batch job: build (or load) the index, ship
the lookup table, scan. A *service* runs the same engine continuously, and
on an XLA backend the extra failure mode is recompilation — every new query
batch shape lowers a new program, which at serving latencies is the
difference between 5 ms and 5 s. The session closes that hole:

  * **Index-backed** — a session is constructed from a segment-based
    :class:`repro.index.Index` (the legacy ``(DistributedIndex, tree)``
    pair still works and is wrapped in an ephemeral single-segment
    facade). Each bucket rung compiles ONE fused program that builds the
    lookup once and runs every segment's executor over it, merging the
    per-segment k-NN tables on device — so serving a grown, multi-segment
    index keeps the zero-recompile and bit-identity invariants;
  * **load-or-build** — ``Index.open`` when a committed manifest exists,
    else build + commit (index-once/serve-many across restarts);
  * **bucketed executors** — a small ladder of padded batch-size buckets
    (``engine.bucket_ladder``), one fused jitted pipeline per rung
    (probe routing -> fixed-shape lookup -> executor). Requests snap up to
    a rung (``snap_to_bucket``) with the valid-row count passed as a
    *traced* scalar, so steady state never sees a new shape and never
    recompiles (``recompiles()`` exposes the jit cache stats; tests and
    the smoke gate assert it stays at the warmed count);
  * **hot-leaf cache** — ``serving.cache.HotLeafCache`` answers repeated
    hot queries locally (see its docstring);
  * **metrics** — ``serving.metrics.ServingMetrics`` plus per-plan
    measured ms/image fed to ``SearchPlan.observe`` (the ROADMAP cost-model
    calibration hook).
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.codes import rerank_exact
from repro.core.engine import (
    PlanShapes,
    SearchPlan,
    bucket_ladder,
    make_executor,
    plan as make_plan,
    resolve_model,
    scale_slab_budget,
    snap_to_bucket,
)
from repro.core import phases
from repro.core.engine.executors import (
    SearchResult,
    pad_lookup,
    wave_shape,
)
from repro.core.index_build import DistributedIndex
from repro.core.lookup import build_lookup_bucketed
from repro.core.search import lookup_q_total
from repro.core.engine.costmodel import plan_signature, signature_key
from repro.core.tree import VocabTree
from repro.distributed.meshutil import data_axis_size, local_mesh
from repro.obs import get_registry, get_tracer
from repro.serving.cache import HotLeafCache
from repro.serving.metrics import ServingMetrics


def _jit_cache_size(fn) -> int:
    # private jax API; if it moves we must NOT silently return 0 — the
    # zero-recompile serving gate would become vacuous
    return int(fn._cache_size())


@dataclasses.dataclass
class _BucketRuntime:
    """One warmed rung: per-segment plans + one fused jitted pipeline."""

    bucket: int  # query-row capacity of this rung
    plan: SearchPlan  # primary plan (largest segment) — observe()/reporting
    plans: tuple  # one resolved plan per segment
    q_total: int  # largest per-segment padded lookup row count
    fn: object  # jitted (segments, tree, queries, n_valid) -> (result, leaves)
    plan_rows: tuple = ()  # (plan, padded rows, n_shards) per segment
    # scan_codes rungs only: the uniform ADC candidate width the pipeline
    # emits (the caller reranks exactly), and the fused fn's signature
    # grows to (segments, codes, codebooks, tree, queries, n_valid)
    rerank: int | None = None
    # tiles one dispatch would scan with none skipped, over every segment
    # and shard, and per segment the distance pairs one scanned tile
    # evaluates, useful or not (executors.wave_shape)
    waves_total: int = 0
    pairs_per_wave: tuple = ()


def make_bucket_runtime(
    mesh,
    n_leaves: int,
    segments,
    bucket: int,
    *,
    k: int,
    probes: int,
    layout: str,
    impl: str,
    ordinals=None,
    emit_slots: bool = False,
    cost_model="auto",
    calibration=None,
    slab_scale: float = 1.0,
    rerank: int | None = None,
    codes=None,
    codebooks=None,
) -> _BucketRuntime:
    """Build one warmed bucket rung over ``segments`` (masked views).

    ``cost_model``/``calibration`` select which cost model ranks an
    ``"auto"`` layout (see :mod:`repro.core.engine.costmodel`);
    ``slab_scale`` grows each segment plan's slab budget (the sharded
    session's per-shard fitted-cost headroom — never shrinks, so it is
    result-safe).

    The fused jitted pipeline runs ONE lookup build (probe routing + leaf
    sort) shared by every segment, then each segment's executor over it,
    then the cross-segment ascending-distance merge on device.

    ``ordinals`` are the segments' global append positions (default
    ``0..len-1`` — the whole-index case). With ``emit_slots=True`` the
    pipeline returns ``(result, leaves, slots)`` where ``slots[q, j] =
    segment_ordinal * k + column`` is each candidate's position in the
    global segment-ordered concatenation — the key the sharded
    scatter-gather merge (:mod:`repro.index.sharding`) fuses shard
    partials by — and the merge uses a *stable* sort so ties keep global
    slot order at any shard count.
    """
    n_shards = data_axis_size(mesh)
    if ordinals is None:
        ordinals = tuple(range(len(segments)))
    q_rows = bucket * probes
    use_codes = layout == "scan_codes"
    code_kw = {}
    if use_codes:
        if codes is None or codebooks is None:
            raise ValueError("scan_codes rungs need codes + codebooks")
        m, n_centers, dsub = codebooks.shape
        code_kw = dict(
            dim=m * dsub, rerank=rerank, code_m=int(m),
            code_bits=int(n_centers).bit_length() - 1,
        )

    def base_plan(view, rerank_override=None):
        kw = dict(code_kw)
        if rerank_override is not None:
            kw["rerank"] = rerank_override
        return make_plan(
            rows=view.rows,
            n_leaves=n_leaves,
            n_queries=bucket,
            n_shards=n_shards,
            k=k,
            probes=probes,
            layout=layout,
            impl=impl,
            model=cost_model,
            calibration=calibration,
            **kw,
        )

    base_plans = [base_plan(view) for view in segments]
    r = k
    if use_codes:
        # one uniform ADC candidate width across segments (each plan may
        # clamp rerank to its own block_rows): the min is valid everywhere
        # and keeps the merge's slot arithmetic a single stride
        r = min(p.rerank for p in base_plans)
        base_plans = [
            p if p.rerank == r else base_plan(view, rerank_override=r)
            for p, view in zip(base_plans, segments)
        ]
    plans, q_totals, execs = [], [], []
    for base_p, view in zip(base_plans, segments):
        p = scale_slab_budget(
            base_p, slab_scale, n_queries=bucket,
            shard_rows=view.rows // n_shards,
        )
        q_total = lookup_q_total(p, bucket, n_shards)
        execs.append(make_executor(
            mesh, p, n_leaves=n_leaves,
            shard_rows=view.rows // n_shards, q_total=q_total,
        ))
        plans.append(p)
        q_totals.append(q_total)
    primary = max(range(len(plans)), key=lambda i: segments[i].rows)
    # each candidate's column in the global segment-ordered concatenation
    # (scan_codes rungs stride by the candidate width r instead of k)
    width = r if use_codes else k
    slot_cols = jnp.concatenate([
        jnp.arange(g * width, g * width + width, dtype=jnp.int32)
        for g in ordinals
    ])

    @jax.named_scope(phases.MERGE)
    def merge(outs, leaves):
        # each segment's scanned tiles, for the host's pair accounting
        waves = jnp.stack([r_.waves for r_ in outs])
        if len(outs) == 1 and not emit_slots:
            return dataclasses.replace(outs[0], waves=waves), leaves
        all_d = jnp.concatenate([r_.dists[:bucket] for r_ in outs], axis=1)
        all_i = jnp.concatenate([r_.ids[:bucket] for r_ in outs], axis=1)
        pairs = sum(r_.pairs for r_ in outs)
        overflow = sum(r_.q_cap_overflow for r_ in outs)
        if emit_slots:
            # stable sort: ties keep concat order == ascending global slot
            sel = jnp.argsort(all_d, axis=1, stable=True)[:, :width]
            merged = SearchResult(
                ids=jnp.take_along_axis(all_i, sel, axis=1),
                dists=jnp.take_along_axis(all_d, sel, axis=1),
                pairs=pairs,
                q_cap_overflow=overflow,
                waves=waves,
            )
            return merged, leaves, slot_cols[sel]
        # cross-segment merge: same ascending-distance fold the
        # executors use across shards (ties keep segment-major order)
        neg, sel = jax.lax.top_k(-all_d, width)
        merged = SearchResult(
            ids=jnp.take_along_axis(all_i, sel, axis=1),
            dists=-neg,
            pairs=pairs,
            q_cap_overflow=overflow,
            waves=waves,
        )
        return merged, leaves

    if use_codes:
        def fused(segs, seg_codes, cbs, tree, queries, n_valid):
            lookup, leaves = build_lookup_bucketed(
                tree, queries, n_valid, probes=probes, q_total=q_rows
            )
            outs = [
                fn(seg, pad_lookup(lookup, qt), c, cbs)
                for seg, fn, qt, c in zip(segs, execs, q_totals, seg_codes)
            ]
            return merge(outs, leaves)
    else:
        def fused(segs, tree, queries, n_valid):
            # ONE lookup build (probe routing + leaf sort) shared by every
            # segment; per-segment executors only see tail padding on top
            lookup, leaves = build_lookup_bucketed(
                tree, queries, n_valid, probes=probes, q_total=q_rows
            )
            outs = [
                fn(seg, pad_lookup(lookup, qt))
                for seg, fn, qt in zip(segs, execs, q_totals)
            ]
            return merge(outs, leaves)

    shapes = [
        wave_shape(p, shard_rows=v.rows // n_shards, q_total=qt,
                   n_shards=n_shards)
        for p, v, qt in zip(plans, segments, q_totals)
    ]
    return _BucketRuntime(
        bucket=bucket, plan=plans[primary], plans=tuple(plans),
        q_total=max(q_totals), fn=jax.jit(fused),
        # calibration keys on the UNSCALED plans (what a later consult
        # will derive, before any slab scaling) at each plan's own
        # n_shards (sharded rungs plan on per-shard submeshes)
        plan_rows=tuple(
            (bp, int(v.rows), n_shards)
            for bp, v in zip(base_plans, segments)
        ),
        rerank=r if use_codes else None,
        waves_total=sum(w for w, _ in shapes),
        pairs_per_wave=tuple(pw for _, pw in shapes),
    )


def _retrace(f):
    """A new function object calling ``f``, which ``jax.jit`` traces
    anew (its caches key on the function object)."""
    @functools.wraps(f)
    def g(*args):
        return f(*args)

    return g


def attach_cache(cache: HotLeafCache, views, n_leaves: int) -> None:
    """Point a hot-leaf cache at the live rows of ``views`` (masked
    segment views) — padding and tombstoned rows are skipped, so a cached
    slab can never resurrect a deleted row."""
    if cache.capacity <= 0:
        return
    vv, ii, ll = [], [], []
    for view in views:
        ids = np.asarray(view.ids)
        live = ids >= 0  # skip padding and tombstoned rows
        vv.append(np.asarray(view.vecs)[live])
        ii.append(ids[live])
        ll.append(np.asarray(view.leaves)[live])
    cache.attach_index(
        np.concatenate(vv), np.concatenate(ii), np.concatenate(ll), n_leaves
    )


def load_or_build_index(
    index_dir: str | None,
    *,
    build_fn,
    mesh=None,
    rebuild: bool = False,
):
    """Index-once / serve-many: ``Index.open`` when ``index_dir`` holds a
    committed non-empty manifest, else ``build_fn() -> (built, tree,
    extra)`` committed there (when a directory is given).

    Returns ``(index, meta)``; ``meta["restored"]`` says which path ran.
    Shared by :meth:`SearchSession.load_or_build` and the sharded
    session's loader. ``build_fn`` may return either the historical
    ``(built, tree, extra)`` triple (committed here as one segment) or an
    already-committed :class:`~repro.index.Index` (e.g. a multi-segment
    build shaped for sharding).
    """
    import warnings

    from repro.index import Index, has_index, has_legacy_index

    mesh = mesh if mesh is not None else local_mesh()
    if index_dir and not rebuild and has_index(index_dir):
        opened = Index.open(index_dir, mesh=mesh)
        if opened.n_segments:
            return opened, dict(opened.meta, restored=True)
        # else: a crash between create and the first commit left a
        # committed-empty index — rebuild instead of serving nothing
    if index_dir and not has_index(index_dir) and has_legacy_index(index_dir):
        warnings.warn(
            f"{index_dir} holds a pre-segment-format index (index_ckpt/), "
            "which this version no longer reads; rebuilding it in the "
            "segment format",
            stacklevel=2,
        )
    out = build_fn()
    if isinstance(out, Index):
        return out, dict(out.meta, restored=False)
    built, tree, extra = out
    idx = Index.create(
        tree, index_dir or None, mesh=mesh, extra=extra, overwrite=True,
    )
    idx.append_built(built)
    idx.commit()
    return idx, dict(extra or {}, restored=False)


class SearchSession:
    """Long-lived search service over one :class:`repro.index.Index`.

    Args:
      index: a ``repro.index.Index``, or (legacy) a raw
        ``DistributedIndex`` with its ``tree`` as the second argument.
      tree/mesh: only needed for the legacy pair; an ``Index`` carries
        both.
      k/layout/probes/impl: the serving plan knobs (see
        :func:`repro.core.engine.plan`). ``layout`` also accepts
        ``"scan_codes"`` on an index with PQ codes (``enable_codes``);
        with ``"auto"`` the cost model may pick the codes tier itself.
        The decision is made once per session so every warmed rung
        serves the same tier.
      rerank: ADC candidates per query to exactly rerank on the codes
        tier (default from
        :func:`~repro.core.engine.plan.default_rerank`).
      cost_model: which cost model ranks an ``"auto"`` layout —
        ``"auto"`` (fitted > observed > heuristic, the default),
        ``"heuristic"``, ``"observed"``, or ``"fitted"`` — consulting the
        index's manifest-persisted calibration store. Post-warmup
        dispatches record measured ms/image back into that store
        (durable at the index's next ``commit``).
      max_batch_rows/n_buckets/buckets: the warmed bucket ladder —
        explicit ``buckets`` override the derived geometric ladder.
      cache_leaves/cache_admit_after: hot-leaf cache capacity (0 = off)
        and admission threshold.
      cache_eviction: ``"cost"`` (predicted ms-saved-per-resident-byte
        via the fitted cost model, the default) or ``"lru"`` — see
        :class:`~repro.serving.cache.HotLeafCache`.

    Raises:
      TypeError: a non-``Index`` first argument without its ``tree``.
      ValueError: an index with no segments (nothing to serve).
    """

    def __init__(
        self,
        index,
        tree: VocabTree | None = None,
        mesh=None,
        *,
        k: int = 10,
        layout: str = "auto",
        probes: int = 1,
        impl: str = "xla",
        rerank: int | None = None,
        max_batch_rows: int = 4096,
        n_buckets: int = 3,
        buckets: Sequence[int] | None = None,
        cache_leaves: int = 0,
        cache_admit_after: int = 2,
        cache_eviction: str = "cost",
        cost_model: str = "auto",
    ):
        from repro.index import Index

        if isinstance(index, Index):
            self.index = index
            self.mesh = mesh if mesh is not None else index.mesh
            self.tree = index.tree
        else:
            # legacy constructor: a raw DistributedIndex + its tree becomes
            # an ephemeral single-segment facade
            if not isinstance(index, DistributedIndex) or tree is None:
                raise TypeError(
                    "SearchSession takes a repro.index.Index, or the legacy "
                    "(DistributedIndex, tree) pair"
                )
            self.mesh = mesh if mesh is not None else local_mesh()
            self.index = Index.from_built(index, tree, mesh=self.mesh)
            self.tree = tree
        # pin one consistent cut of the index: every runtime, cache slab,
        # and rerank fetch resolves against this snapshot until refresh()/
        # maybe_refresh() adopts a newer one — mutations on the underlying
        # Index never perturb in-flight or queued requests
        self._pin = self.index.snapshot()
        self._segments = self._pin.views
        if not self._segments:
            raise ValueError("cannot serve an index with no segments")
        self.k = int(k)
        self.layout = layout
        self.probes = int(probes)
        self.impl = impl
        self.rerank = rerank
        self.cost_model = cost_model
        self.buckets = (
            tuple(sorted(int(b) for b in buckets))
            if buckets
            else bucket_ladder(max_batch_rows, n_buckets=n_buckets)
        )
        # codes-vs-exact resolves ONCE per session on the aggregate shape
        # (ADC and exact distances are incomparable across a merge), so
        # every rung of every ladder serves the same tier
        pq = self._pin.quantizer
        if layout == "scan_codes" and pq is None:
            raise ValueError(
                "layout='scan_codes' needs PQ codes; call "
                "index.enable_codes() first"
            )
        self._use_codes = False
        if pq is not None and layout in ("auto", "scan_codes"):
            agg = make_plan(
                rows=sum(int(v.rows) for v in self._segments),
                n_leaves=self.index.n_leaves,
                n_queries=self.buckets[-1],
                n_shards=data_axis_size(self.mesh),
                k=self.k, probes=self.probes, layout=layout, impl=impl,
                model=cost_model, calibration=self.index.calibration,
                dim=self.index.dim, rerank=rerank,
                code_m=pq.m, code_bits=pq.bits,
            )
            self._use_codes = agg.layout == "scan_codes"
        self._codes_dev = None
        self._codebooks_dev = None
        if self._use_codes:
            self._refresh_codes()
        self.metrics = ServingMetrics()
        self.cache = HotLeafCache(cache_leaves, admit_after=cache_admit_after,
                                  eviction=cache_eviction)
        self._attach_cache()
        self._build_runtimes()
        self._warmed_compiles: int | None = None
        # seed the cache's eviction score with the fitted model's view of
        # what one engine-served image costs (measured EMA refines it)
        self.cache.note_engine_cost(self.predicted_ms_per_image())

    def _attach_cache(self) -> None:
        attach_cache(self.cache, self._segments, self.index.n_leaves)

    def _refresh_codes(self) -> None:
        """Device copies of each pinned segment's PQ codes + the codebook
        table, aligned with ``self._segments`` order."""
        self._codes_dev = tuple(
            jnp.asarray(self._pin.codes[s.name])
            for s in self._pin.segments
        )
        self._codebooks_dev = jnp.asarray(self._pin.quantizer.codebooks)

    def _read_pinned_rows(self, ids) -> np.ndarray:
        """Rerank row fetches against the pinned cut — a concurrent
        delete or compaction cannot make an in-flight request's candidate
        id unreadable."""
        return self.index.read_rows(
            ids, segments=self._pin.segments, tombstones=self._pin.tombstones
        )

    @property
    def serving_layout(self) -> str:
        """The layout the warmed ladders actually execute (``layout``
        with the session's one-time codes decision applied)."""
        return "scan_codes" if self._use_codes else self.layout

    def _build_runtimes(self) -> None:
        """(Re)compile-point: one runtime per warmed bucket rung. The
        sharded session overrides this to build one rung per (shard,
        bucket) pair instead."""
        self._runtimes = {b: self._make_runtime(b) for b in self.buckets}

    # -- construction -------------------------------------------------------
    @classmethod
    def load_or_build(
        cls,
        index_dir: str | None,
        *,
        build_fn,
        mesh=None,
        rebuild: bool = False,
        **session_kw,
    ) -> tuple["SearchSession", dict]:
        """Index-once / serve-many: ``Index.open`` when ``index_dir`` holds
        a committed manifest, else call ``build_fn() -> (index, tree,
        extra)`` and commit the result there (when ``index_dir`` is given).

        Returns ``(session, meta)`` where ``meta`` is the index metadata
        (corpus geometry etc.) on restore, or ``build_fn``'s extra.
        """
        mesh = mesh if mesh is not None else local_mesh()
        idx, meta = load_or_build_index(
            index_dir, build_fn=build_fn, mesh=mesh, rebuild=rebuild,
        )
        return cls(idx, mesh=mesh, **session_kw), meta

    @property
    def pinned_version(self) -> int:
        """The index manifest version this session is currently serving
        (the snapshot pinned at construction or the last refresh)."""
        return self._pin.version

    def refresh(self) -> None:
        """Re-pin the index's current segments/tombstones (after append/
        delete/compact on the underlying Index) and rebuild the bucket
        pipelines. New shapes compile at the next :meth:`warmup` — prefer
        :meth:`maybe_refresh` on a serving loop, which warms before
        swapping."""
        self._adopt(self.index.snapshot())

    def maybe_refresh(self) -> bool:
        """Adopt the index's latest state iff it changed since the pin —
        the serve-loop's read-during-write hook (``--refresh-every``).

        O(1) when nothing changed (one stamp compare — safe to call
        between every micro-batch). On change, the new snapshot's bucket
        ladders are rebuilt AND warmed *before* this method returns, so
        the caller's next dispatch replays a compiled program: requests
        queued behind the refresh never see a half-adopted index and
        steady-state recompiles stay at zero. An index mutated down to
        zero segments keeps the old pin (there is nothing to serve).

        Returns ``True`` when a new snapshot was adopted.
        """
        if self.index.stamp == self._pin.stamp:
            return False
        snap = self.index.snapshot()
        if not snap.segments:
            return False
        self._adopt(snap)
        self.warmup()
        return True

    def _adopt(self, snap) -> None:
        """Swap the pinned snapshot: re-point views, cache slabs, device
        codes, and rebuild the bucket runtimes. Callers own warmup."""
        self._pin = snap
        self._segments = snap.views
        self._attach_cache()
        if self._use_codes:
            self._refresh_codes()
        self._build_runtimes()
        self._warmed_compiles = None

    def _make_runtime(self, bucket: int) -> _BucketRuntime:
        return make_bucket_runtime(
            self.mesh, self.index.n_leaves, self._segments, bucket,
            k=self.k, probes=self.probes, layout=self.serving_layout,
            impl=self.impl,
            cost_model=self.cost_model, calibration=self.index.calibration,
            rerank=self.rerank, codes=self._codes_dev,
            codebooks=self._codebooks_dev,
        )

    def active_cost_model(self) -> str:
        """Which model currently decides (e.g. ``"auto(fitted)"``) —
        resolved against the index's live calibration store."""
        return resolve_model(
            self.cost_model, self.index.calibration
        ).describe()

    def predicted_ms_per_image(self, bucket: int | None = None
                               ) -> float | None:
        """Modelled engine ms per image for one dispatch at ``bucket``
        (default: the largest warmed rung) — what the SLO policy derives
        its shed threshold from and the hot-leaf cache scores evictions
        with. Prefers the fitted cost model (summed over every executed
        per-segment plan, mirroring how serving attributes measurements),
        falls back to the calibration store's exact-signature means, then
        to this session's own measured ms/image; ``None`` when nothing
        can price it (callers must treat the cost as unknown)."""
        from repro.core.engine import fitted_component

        b = self.buckets[-1] if bucket is None else snap_to_bucket(
            min(int(bucket), self.max_batch_rows), self.buckets
        )
        rt = self._runtimes[b]
        fitted = fitted_component(self.cost_model, self.index.calibration)
        for model in (fitted, self.index.calibration):
            if model is None:
                continue
            preds = [
                (
                    model.predict_ms(
                        p, PlanShapes(rows=rows, n_queries=rt.bucket,
                                      n_shards=ns,
                                      n_leaves=self.index.n_leaves,
                                      dim=self._shapes_dim(p)),
                    )
                    if fitted is model
                    else model.mean_ms(p)
                )
                for p, rows, ns in rt.plan_rows
            ]
            if all(v is not None for v in preds):
                total = float(sum(preds))
                if total > 0:
                    return total
        if self.metrics.engine_images:
            return self.metrics.ms_per_image
        return None

    # -- compile accounting -------------------------------------------------
    def recompiles(self) -> int:
        """Total jitted-executor compilations so far (jit cache entries)."""
        return sum(_jit_cache_size(rt.fn) for rt in self._runtimes.values())

    def steady_state_recompiles(self) -> int:
        """Compilations after warmup — the serving invariant is 0."""
        if self._warmed_compiles is None:
            return 0
        n = self.recompiles() - self._warmed_compiles
        self.metrics.recompiles_after_warmup = n
        return n

    def warmup(self) -> float:
        """Compile every bucket rung once (dummy batch) — steady-state
        requests then only ever replay warmed programs. Returns the wall
        milliseconds spent compiling (also folded into the metrics)."""
        d = self.index.dim
        with get_tracer().span("session.warmup", buckets=len(self.buckets)):
            t0 = time.perf_counter()
            for rt in self._runtimes.values():
                dummy = jnp.zeros((rt.bucket, d), jnp.float32)
                res, leaves = self._dispatch(rt, dummy, np.int32(0))
                jax.block_until_ready((res.ids, leaves))
            dt_ms = (time.perf_counter() - t0) * 1e3
        self.metrics.warmup_ms += dt_ms
        self._warmed_compiles = self.recompiles()
        return dt_ms

    # -- serve path ---------------------------------------------------------
    @property
    def max_batch_rows(self) -> int:
        return self.buckets[-1]

    def _args(self, rt: _BucketRuntime, buf, n_valid) -> tuple:
        """One rung's call arguments (codes rungs take the device codes +
        codebook table as extra leading arguments)."""
        if rt.rerank is not None:
            return (self._segments, self._codes_dev, self._codebooks_dev,
                    self.tree, buf, n_valid)
        return (self._segments, self.tree, buf, n_valid)

    def _dispatch(self, rt: _BucketRuntime, buf, n_valid):
        """Invoke one rung's fused pipeline."""
        return rt.fn(*self._args(rt, buf, n_valid))

    def _programs(self):
        """``(bucket, jitted fn, arguments)`` of every warmed program, on
        an empty batch of the rung's shape."""
        for b, rt in self._runtimes.items():
            dummy = jnp.zeros((b, self.index.dim), jnp.float32)
            yield b, rt.fn, self._args(rt, dummy, np.int32(0))

    def compiled_hlo(self, buckets: Sequence[int] | None = None) -> list[str]:
        """The optimized HLO text of each warmed program (of ``buckets``
        only, when given), compiled for the backend that serves it.

        Every instruction's ``op_name`` metadata carries the device phase
        it belongs to (:mod:`repro.core.phases`), so a profiler trace's
        ops can be read as time per phase (docs/observability.md). Each
        program is traced and compiled afresh, with the arguments serving
        uses and past JAX's in-memory and persistent compilation caches:
        their keys leave metadata out, so a cached executable may carry
        the metadata of an earlier source of the same program. The
        compiler is deterministic, so the instruction names are those of
        the executable that serves; that one is left untouched, and
        nothing runs on the device. Switches the persistent cache off
        process-wide while it compiles.
        """
        from jax.experimental.compilation_cache import compilation_cache

        progs = [(fn, args) for b, fn, args in self._programs()
                 if buckets is None or b in buckets]
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            return [
                jax.jit(_retrace(fn.__wrapped__)).lower(*args).compile()
                .as_text()
                for fn, args in progs
            ]
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()

    def _execute(
        self, queries: np.ndarray, *, n_images: int | None = None
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray, float]:
        """Run one micro-batch through its snapped bucket rung.

        Returns ``(ids (n,k), dists (n,k), probe_leaves (n,probes),
        seconds)``; feeds metrics, the hot-leaf cache, and the plan's
        ms/image observations. Traced, the call is one ``engine.execute``
        span whose children place the host's part of it: ``session.pad``,
        ``session.dispatch`` (H2D and enqueue), ``session.wait`` (the
        device), ``session.fetch`` (D2H), then ``session.record``.
        """
        n, d = queries.shape
        if n > self.max_batch_rows:
            raise ValueError(
                f"batch of {n} rows exceeds largest bucket "
                f"{self.max_batch_rows}; split it across dispatches"
            )
        rt = self._runtimes[snap_to_bucket(n, self.buckets)]
        tr = get_tracer()
        with tr.span("engine.execute") as span:
            if tr.enabled:
                span.set(
                    rows=n, bucket=rt.bucket, layout=rt.plan.layout,
                    segments=len(rt.plans),
                    plan=signature_key(plan_signature(rt.plan)),
                    cost_model=self.active_cost_model(),
                )
            with tr.span("session.pad"):
                buf = np.zeros((rt.bucket, d), np.float32)
                buf[:n] = queries
            t0 = time.perf_counter()
            with tr.span("session.dispatch"):
                res, leaves = self._dispatch(rt, jnp.asarray(buf),
                                             np.int32(n))
            with tr.span("session.wait"):
                jax.block_until_ready((res.ids, res.dists, leaves))
            dt = time.perf_counter() - t0
            with tr.span("session.fetch"):
                ids = np.asarray(res.ids[:n])
                dists = np.asarray(res.dists[:n])
                leaves_np = np.asarray(leaves[:n])
                overflow, pairs, waves = jax.device_get(
                    (res.q_cap_overflow, res.pairs, res.waves)
                )
            if self._use_codes:
                # the rung emitted rt.rerank ADC candidates per query;
                # fetch the survivors' raw rows and rerank exactly (the
                # rerank wall time is part of serving the request, so it
                # stays in dt)
                t_r = time.perf_counter()
                with tr.span("engine.rerank", k=self.k,
                             candidates=int(ids.shape[1])):
                    ids, dists = rerank_exact(
                        self.index.read_rows, queries, ids, self.k
                    )
                dt += time.perf_counter() - t_r
            with tr.span("session.record"):
                self._record(rt, queries, leaves_np, n, n_images, dt,
                             overflow=int(overflow), pairs=float(pairs),
                             waves=waves)
        return ids, dists, leaves_np, dt

    def _record(self, rt, queries, leaves_np, n, n_images, dt, *,
                overflow: int, pairs: float, waves) -> None:
        """One dispatch's accounting: metrics, calibration, hot-leaf
        cache. ``waves`` holds the tiles each segment's scan visited, in
        ``rt.pairs_per_wave`` order."""
        self.metrics.engine_batches += 1
        self.metrics.engine_ms += dt * 1e3
        self.metrics.query_rows += n
        self.metrics.q_cap_overflow += overflow
        useful = int(round(pairs))
        waves = [int(w) for w in np.ravel(waves)]
        scanned = sum(waves)
        computed = sum(w * pw for w, pw in zip(waves, rt.pairs_per_wave))
        self.metrics.pairs_useful += useful
        self.metrics.pairs_computed += computed
        self.metrics.waves_scanned += scanned
        self.metrics.waves_total += rt.waves_total
        # process-wide totals: they outlive the session (the benchmark
        # reads them after set-up objects are freed)
        reg = get_registry()
        reg.counter("engine.pairs_useful").inc(useful)
        reg.counter("engine.pairs_computed").inc(computed)
        reg.counter("engine.waves_scanned").inc(scanned)
        reg.counter("engine.waves_total").inc(rt.waves_total)
        if n_images:
            self.metrics.engine_images += n_images
            self._record_calibration(rt, dt * 1e3 / n_images)
            # measured engine cost refines the cache's eviction score
            self.cache.note_engine_cost(dt * 1e3 / n_images)
        if not self._use_codes:
            # a starved dispatch must not seed the cache: a cached
            # full-slab scan would disagree with the truncated engine
            # answer. Codes sessions never seed it at all — a cache hit
            # would answer with an exact scan, diverging from the
            # ADC+rerank tier the engine serves.
            self.cache.record(queries, leaves_np, exact=overflow == 0)

    def search(
        self, queries: np.ndarray, *, n_images: int | None = None
    ) -> tuple[np.ndarray, np.ndarray]:
        """One-shot search of ``(n, d)`` query rows.

        Args:
          queries: ``(n, d)`` float rows; batches larger than the top
            bucket are split across dispatches.
          n_images: images this batch represents — feeds the ms/image
            metric and the plan's cost-model observations when given.

        Returns:
          ``(ids, dists)`` of shape ``(n, k)`` each — bit-identical to
          ``core.search.batch_search`` under the same plan budgets.
        """
        queries = np.asarray(queries, np.float32)
        if len(queries) <= self.max_batch_rows:
            ids, dists, _, _ = self._execute(queries, n_images=n_images)
            return ids, dists
        # split batches: per-chunk plan observations would mis-attribute the
        # whole request's images to one chunk's wall time, so only the
        # aggregate image/ms counters are fed (ms_per_image stays honest)
        out_i, out_d = [], []
        for s in range(0, len(queries), self.max_batch_rows):
            chunk = queries[s: s + self.max_batch_rows]
            ids, dists, _, _ = self._execute(chunk)
            out_i.append(ids)
            out_d.append(dists)
        if n_images:
            self.metrics.engine_images += n_images
        return np.concatenate(out_i), np.concatenate(out_d)

    def serve_many(self, request_batches) -> list[tuple[np.ndarray, np.ndarray]]:
        """Serve a coalesced micro-batch in one engine dispatch.

        Args:
          request_batches: per-request ``(rows, d)`` arrays whose total
            row count fits the largest warmed bucket.

        Returns:
          One ``(ids, dists)`` pair per request, in order.

        Raises:
          ValueError: the concatenated batch exceeds the largest bucket
            (the micro-batcher's coalescing contract was violated).
        """
        sizes = [len(q) for q in request_batches]
        ids, dists, _, _ = self._execute(
            np.concatenate(request_batches), n_images=len(request_batches)
        )
        out, off = [], 0
        for s in sizes:
            out.append((ids[off: off + s], dists[off: off + s]))
            off += s
        return out

    def _record_calibration(self, rt: _BucketRuntime, ms_per_image: float
                            ) -> None:
        """Measured ms/image -> the index's calibration store. A dispatch
        scans every segment (and shard) in one fused program, so the
        measured ms is attributed to each executed plan proportionally to
        its rows share — each record's shapes then match what the next
        session's per-segment ``plan()`` consult will ask about, and the
        fit gets one shape-consistent point per plan. Only after warmup:
        a compile-tainted first dispatch must not poison the fit."""
        if self._warmed_compiles is None:
            return
        total = sum(r for _, r, _ in rt.plan_rows) or 1
        for p, rows, n_shards in rt.plan_rows:
            self.index.calibration.record(
                p, ms_per_image * rows / total,
                shapes=PlanShapes(
                    rows=rows,
                    n_queries=rt.bucket,
                    n_shards=n_shards,
                    n_leaves=self.index.n_leaves,
                    dim=self._shapes_dim(p),
                ),
            )

    def _shapes_dim(self, p: SearchPlan) -> int:
        """``PlanShapes.dim`` for a recorded/consulted plan: the codes
        tier prices by dim, the dense layouts never did — keeping dense
        shapes at ``dim=0`` preserves exact-shape matches against every
        pre-codes record and the dense consults elsewhere."""
        return self.index.dim if p.layout == "scan_codes" else 0

    def plan_summary(self) -> list[dict]:
        return [
            {
                "bucket": rt.bucket,
                "cost_model": self.cost_model,
                "layout": rt.plan.layout,
                "impl": rt.plan.impl,
                "q_total": rt.q_total,
                "block_rows": rt.plan.block_rows,
                "q_cap": rt.plan.q_cap,
                "q_tile": rt.plan.q_tile,
                "p_cap": rt.plan.p_cap,
                "rerank": rt.plan.rerank,
                "segments": len(rt.plans),
            }
            for rt in self._runtimes.values()
        ]
