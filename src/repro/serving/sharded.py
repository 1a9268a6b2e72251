"""Sharded scatter-gather serving: N shard ladders behind one session.

The paper's search phase runs as a fleet of map tasks, each scanning its
partition of the index, with one merge step fusing per-partition candidate
lists (§2.4). :class:`ShardedSearchSession` is that topology as a serving
layer over a :class:`~repro.index.ShardedIndex`:

  * **scatter** — every dispatch snaps to a warmed bucket and fans the
    padded query batch out to one fused jitted pipeline *per shard*
    (each shard owns a full bucket ladder over its segments — compile
    cost is ``shards x buckets`` programs, all paid at :meth:`warmup`);
  * **gather** — per-shard partials carry global merge *slots*
    (``segment_ordinal * k + column``), so the host-side fuse
    (:func:`repro.index.sharding.gather_merge`) reproduces the unsharded
    stable ascending-distance merge bit for bit — results are identical
    to a plain :class:`~repro.serving.SearchSession` over the same index
    at any shard count, both layouts, any probe width, tombstones
    respected;
  * **above the scatter** — the hot-leaf cache keys on the *pre-scatter*
    query bytes (one cache for the whole index, consulted before any
    shard is touched) and records routing *post-gather*; the
    micro-batcher coalesces above the session exactly as in the
    unsharded case — neither knows shards exist.

On one device the shards share the mesh and run sequentially-but-isolated
(same numerics, summed wall time — this is the regime the bit-identity
tests pin down); with enough devices each shard's programs are placed on
its own device group via ``meshutil.shard_submeshes`` and the sequential
dispatch loop overlaps across shards (dispatch is async; the gather blocks
once at the end).
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import (
    PlanShapes,
    SearchPlan,
    fitted_component,
    plan as make_plan,
    snap_to_bucket,
)
from repro.codes import rerank_exact
from repro.distributed.meshutil import data_axis_size
from repro.core.engine.costmodel import plan_signature, signature_key
from repro.index.sharding import (
    ShardedIndex,
    ShardPlan,
    fitted_shard_scales,
    gather_merge,
)
from repro.obs import get_tracer
from repro.serving.session import (
    SearchSession,
    _jit_cache_size,
    make_bucket_runtime,
)
from repro.serving.slo import slab_scale_cap


@dataclasses.dataclass
class _ShardedRuntime:
    """One warmed bucket rung, fanned out: one fused pipeline per shard."""

    bucket: int  # query-row capacity of this rung
    parts: tuple  # (shard_index, views, _BucketRuntime) per non-empty shard
    plan: SearchPlan  # primary plan (largest shard) — observe()/reporting
    plans: tuple  # every resolved per-segment plan across shards
    q_total: int  # largest per-segment padded lookup row count
    plan_rows: tuple = ()  # (plan, padded rows, n_shards) across shards
    # over every shard's every segment, in parts order (_BucketRuntime)
    waves_total: int = 0
    pairs_per_wave: tuple = ()


class ShardedSearchSession(SearchSession):
    """Scatter-gather :class:`SearchSession`: same public surface (the
    micro-batcher, trace replay, and CLI drive either interchangeably),
    shard-parallel execution underneath.

    Construct from a ``repro.index.Index`` plus either ``shards=N`` (+
    ``shard_strategy``), an explicit ``shard_plan``, or an index whose
    manifest carries a persisted plan; a ``ShardedIndex`` is also
    accepted directly. ``target_p95_ms`` caps the fitted per-shard
    slab-headroom multipliers so a grown dispatch still fits the latency
    target (see :func:`repro.serving.slo.slab_scale_cap`); ``None``
    keeps the stock cap. All other keywords are :class:`SearchSession`'s.

    Raises ``ValueError`` when no shard plan can be resolved, or when an
    explicit plan no longer covers the index's segments after a
    :meth:`refresh` (derivable strategies re-derive automatically).
    """

    def __init__(
        self,
        index,
        tree=None,
        mesh=None,
        *,
        shards: int | None = None,
        shard_plan: ShardPlan | None = None,
        shard_strategy: str = "round_robin",
        target_p95_ms: float | None = None,
        **session_kw,
    ):
        if isinstance(index, ShardedIndex):
            shard_plan = shard_plan or index.plan
            index = index.index
        self._n_shards_arg = shards
        self._shard_plan_arg = shard_plan
        self._strategy_arg = shard_strategy
        self._target_p95_ms = target_p95_ms
        super().__init__(index, tree, mesh, **session_kw)

    # -- runtime construction -----------------------------------------------
    def _derive_plan(self, n_shards: int, strategy: str) -> ShardPlan:
        """Derive a plan over the *pinned* segment cut (not the index's
        live segments — a concurrent append must not leak into the plan
        this session serves). Raises for non-derivable strategies."""
        segs = self._pin.segments
        if strategy == "round_robin":
            return ShardPlan.round_robin([s.name for s in segs], n_shards)
        if strategy == "balanced":
            return ShardPlan.balanced(
                [s.name for s in segs], [s.valid_rows for s in segs], n_shards
            )
        raise ValueError(
            f"cannot derive a {strategy!r} plan; want one of "
            "('round_robin', 'balanced')"
        )

    def _resolve_plan(self) -> ShardPlan:
        plan = self._shard_plan_arg
        if plan is None and self._n_shards_arg is not None:
            return self._derive_plan(self._n_shards_arg, self._strategy_arg)
        if plan is None:
            plan = self._pin.shard_plan
        if plan is None:
            raise ValueError(
                "ShardedSearchSession needs shards=N, a shard_plan, or an "
                "index with a persisted shard plan"
            )
        if not plan.covers([s.name for s in self._pin.segments]):
            # raises for explicit plans (cannot follow a changed cut)
            plan = self._derive_plan(plan.n_shards, plan.strategy)
        return plan

    def _build_runtimes(self) -> None:
        self.sharded = ShardedIndex(
            self.index, plan=self._resolve_plan(),
            segments=self._pin.segments, views=self._pin.views,
            codes=self._pin.codes or None, tombstones=self._pin.tombstones,
        )
        shard_views = self.sharded.shard_views()
        self._shard_trees = [
            self.sharded.replicated(si, self.tree)
            for si in range(len(shard_views))
        ]
        self._shard_codes = {}
        if self._use_codes:
            # device codes aligned with global segment ordinals; each
            # shard's rung sees only its own segments' code arrays
            for si, shard in enumerate(shard_views):
                if shard:
                    self._shard_codes[si] = tuple(
                        self._codes_dev[g] for g, _ in shard
                    )
        self._runtimes = {}
        for b in self.buckets:
            scales = self._shard_scales(shard_views, b)
            rerank = self._global_rerank(shard_views, b)
            parts = []
            for si, (shard, mesh, scale) in enumerate(
                zip(shard_views, self.sharded._meshes, scales)
            ):
                if not shard:
                    continue  # more shards than segments: empty scatter leg
                rt = make_bucket_runtime(
                    mesh, self.index.n_leaves,
                    tuple(v for _, v in shard), b,
                    k=self.k, probes=self.probes,
                    layout=self.serving_layout,
                    impl=self.impl,
                    ordinals=tuple(g for g, _ in shard),
                    emit_slots=True,
                    cost_model=self.cost_model,
                    calibration=self.index.calibration,
                    slab_scale=scale,
                    rerank=rerank,
                    codes=self._shard_codes.get(si),
                    codebooks=self._codebooks_dev,
                )
                parts.append((si, tuple(v for _, v in shard), rt))
            primary = max(
                range(len(parts)),
                key=lambda i: sum(int(v.rows) for v in parts[i][1]),
            )
            self._runtimes[b] = _ShardedRuntime(
                bucket=b,
                parts=tuple(parts),
                plan=parts[primary][2].plan,
                plans=tuple(p for _, _, rt in parts for p in rt.plans),
                q_total=max(rt.q_total for _, _, rt in parts),
                # every shard scans the dispatch: the base session's
                # rows-share attribution then covers all executed plans
                plan_rows=tuple(
                    pr for _, _, rt in parts for pr in rt.plan_rows
                ),
                waves_total=sum(rt.waves_total for _, _, rt in parts),
                pairs_per_wave=tuple(
                    pw for _, _, rt in parts for pw in rt.pairs_per_wave
                ),
            )

    def _global_rerank(self, shard_views, bucket: int) -> int | None:
        """One uniform ADC candidate width for EVERY shard's rung at this
        bucket: each segment's plan clamps ``rerank`` to its own
        ``block_rows``, and the gather's slot arithmetic (``ordinal *
        width + column``) only stays a global total order when every
        shard emits the same width — the min across all segments is
        valid everywhere. ``None`` on dense tiers."""
        if not self._use_codes:
            return None
        pq = self._pin.quantizer
        widths = []
        for shard, mesh in zip(shard_views, self.sharded._meshes):
            ns = data_axis_size(mesh)
            for _, view in shard:
                p = make_plan(
                    rows=view.rows, n_leaves=self.index.n_leaves,
                    n_queries=bucket, n_shards=ns, k=self.k,
                    probes=self.probes, layout="scan_codes",
                    impl=self.impl, model=self.cost_model,
                    calibration=self.index.calibration,
                    dim=self.index.dim, rerank=self.rerank,
                    code_m=pq.m, code_bits=pq.bits,
                )
                widths.append(p.rerank)
        return min(widths)

    def _shard_scales(self, shard_views, bucket: int) -> list[float]:
        """Per-shard slab-headroom multipliers for one bucket rung —
        the shared :func:`repro.index.sharding.fitted_shard_scales`
        (all ones until the index's calibration yields a usable fit, i.e.
        the uniform budget split). With ``target_p95_ms`` set, the
        multiplier ceiling shrinks so the fitted model predicts a grown
        dispatch still fits the target's dispatch budget."""
        max_scale = 2.0
        if self._target_p95_ms:
            max_scale = slab_scale_cap(
                self._target_p95_ms,
                self._predicted_dispatch_ms(shard_views, bucket),
            )
        return fitted_shard_scales(
            self.index, shard_views, self.sharded._meshes,
            cost_model=self.cost_model, n_queries=bucket, k=self.k,
            probes=self.probes,
            # codes rungs budget like the dense point-major family; the
            # probe plans only supply tile features, and grow-only scales
            # keep any mispricing result-safe
            layout="auto" if self._use_codes else self.layout,
            impl=self.impl,
            max_scale=max_scale,
        )

    def _predicted_dispatch_ms(self, shard_views, bucket: int) -> float | None:
        """Fitted prediction for one full-bucket dispatch at scale 1 —
        the sum of per-shard scan costs (on one device the shard scans
        run back to back). ``None`` when any shard cannot be planned or
        priced, which falls back to the stock headroom cap."""
        fitted = fitted_component(self.cost_model, self.index.calibration)
        if fitted is None:
            return None
        total = 0.0
        for shard, mesh in zip(shard_views, self.sharded._meshes):
            if not shard:
                continue
            rows = sum(int(v.rows) for _, v in shard)
            ns = data_axis_size(mesh)
            try:
                p = make_plan(
                    rows=rows, n_leaves=self.index.n_leaves,
                    n_queries=bucket, n_shards=ns, k=self.k,
                    probes=self.probes, layout=self.layout, impl=self.impl,
                    model=self.cost_model,
                    calibration=self.index.calibration,
                )
            except ValueError:
                return None
            pred = fitted.predict_ms(p, PlanShapes(
                rows=rows, n_queries=bucket, n_shards=ns,
                n_leaves=self.index.n_leaves,
            ))
            if pred is None:
                return None
            total += pred
        return total or None

    # -- compile accounting --------------------------------------------------
    def recompiles(self) -> int:
        """Total jitted compilations across every (shard, bucket) program."""
        return sum(
            _jit_cache_size(rt.fn)
            for rtb in self._runtimes.values()
            for _, _, rt in rtb.parts
        )

    def warmup(self) -> float:
        """Compile every shard's every bucket rung once (dummy batch);
        steady state then replays warmed programs only. Returns wall ms."""
        d = self.index.dim
        with get_tracer().span("session.warmup", buckets=len(self.buckets),
                               shards=self.n_shards):
            t0 = time.perf_counter()
            for rtb in self._runtimes.values():
                dummy = jnp.zeros((rtb.bucket, d), jnp.float32)
                outs = [
                    self._dispatch_shard(si, rt, views, dummy, np.int32(0))
                    for si, views, rt in rtb.parts
                ]
                for res, leaves, _slots in outs:
                    jax.block_until_ready((res.ids, leaves))
            dt_ms = (time.perf_counter() - t0) * 1e3
        self.metrics.warmup_ms += dt_ms
        self._warmed_compiles = self.recompiles()
        return dt_ms

    # -- serve path ----------------------------------------------------------
    def _shard_args(self, si, rt, views, buf, n_valid) -> tuple:
        """One shard's call arguments (codes rungs take that shard's
        device codes + the codebook table as extra args)."""
        tree = self._shard_trees[si]
        if rt.rerank is not None:
            return (views, self._shard_codes[si], self._codebooks_dev,
                    tree, buf, n_valid)
        return (views, tree, buf, n_valid)

    def _dispatch_shard(self, si, rt, views, buf, n_valid):
        """Invoke one shard's fused pipeline."""
        return rt.fn(*self._shard_args(si, rt, views, buf, n_valid))

    def _programs(self):
        """Every (shard, bucket) program, on an empty batch."""
        for b, rtb in self._runtimes.items():
            dummy = jnp.zeros((b, self.index.dim), jnp.float32)
            for si, views, rt in rtb.parts:
                yield b, rt.fn, self._shard_args(si, rt, views, dummy,
                                                 np.int32(0))

    def _execute(
        self, queries: np.ndarray, *, n_images: int | None = None
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray, float]:
        """Scatter one micro-batch to every shard, gather-merge the
        partials. Same contract as the unsharded ``_execute``: returns
        ``(ids, dists, probe_leaves, seconds)``, feeds metrics, the
        (pre-scatter) hot-leaf cache, and the plan observations."""
        n, d = queries.shape
        if n > self.max_batch_rows:
            raise ValueError(
                f"batch of {n} rows exceeds largest bucket "
                f"{self.max_batch_rows}; split it across dispatches"
            )
        rtb = self._runtimes[snap_to_bucket(n, self.buckets)]
        buf = np.zeros((rtb.bucket, d), np.float32)
        buf[:n] = queries
        jbuf = jnp.asarray(buf)
        nv = np.int32(n)
        tr = get_tracer()
        t0 = time.perf_counter()
        if tr.enabled:
            # per-shard spans need per-shard completion times, so block
            # each scatter leg in turn. The programs, inputs, and merge
            # are untouched — numerics (ids/dists) stay bit-identical to
            # the async path; only wall attribution differs.
            outs = []
            for si, views, rt in rtb.parts:
                with tr.span(
                    "shard.scan", shard=si, bucket=rtb.bucket,
                    rows=sum(int(v.rows) for v in views),
                    segments=len(views),
                ):
                    out = self._dispatch_shard(si, rt, views, jbuf, nv)
                    jax.block_until_ready(
                        (out[0].ids, out[0].dists, out[2], out[1])
                    )
                outs.append(out)
        else:
            # dispatch every shard first (async), block once for the
            # gather — on disjoint device groups the scans overlap; on one
            # device XLA runs them back to back with identical numerics
            outs = [
                self._dispatch_shard(si, rt, views, jbuf, nv)
                for si, views, rt in rtb.parts
            ]
            for res, leaves, slots in outs:
                jax.block_until_ready((res.ids, res.dists, slots, leaves))
        dt = time.perf_counter() - t0
        if tr.enabled:
            t1 = tr.now()
            tr.add_span(
                "engine.execute", t1 - dt, t1, rows=n, bucket=rtb.bucket,
                layout=rtb.plan.layout, shards=len(rtb.parts),
                plan=signature_key(plan_signature(rtb.plan)),
                cost_model=self.active_cost_model(),
            )
        # codes rungs gather CANDIDATE tables (uniform width, slot-tagged,
        # so the merged candidate set is shard-count-invariant), then one
        # global exact rerank produces the final top-k
        width = rtb.parts[0][2].rerank or self.k
        with tr.span("gather.merge", shards=len(rtb.parts), rows=n):
            ids, dists = gather_merge(
                [
                    (
                        np.asarray(res.ids[:n]),
                        np.asarray(res.dists[:n]),
                        np.asarray(slots[:n]),
                    )
                    for res, _leaves, slots in outs
                ],
                width,
            )
        if self._use_codes:
            t_r = time.perf_counter()
            with tr.span("engine.rerank", k=self.k, candidates=width):
                ids, dists = rerank_exact(
                    self._read_pinned_rows, queries, ids, self.k
                )
            dt += time.perf_counter() - t_r
        # every shard routes the same queries through the same tree; shard
        # 0's probe-leaf matrix is THE routing (the broadcast analog)
        leaves_np = np.asarray(outs[0][1][:n])
        counts = jax.device_get(
            [(res.q_cap_overflow, res.pairs, res.waves) for res, _, _ in outs]
        )
        self._record(rtb, queries, leaves_np, n, n_images, dt,
                     overflow=sum(int(o) for o, _, _ in counts),
                     pairs=sum(float(p) for _, p, _ in counts),
                     waves=np.concatenate(
                         [np.ravel(w) for _, _, w in counts]))
        return ids, dists, leaves_np, dt

    # -- reporting ------------------------------------------------------------
    @property
    def n_shards(self) -> int:
        return self.sharded.n_shards

    @property
    def shard_plan(self) -> ShardPlan:
        return self.sharded.plan

    def per_shard_stats(self) -> dict:
        """The bound plan plus rows/segments per shard (CLI + benchmark
        reporting)."""
        return self.sharded.stats()

    def plan_summary(self) -> list[dict]:
        return [
            {
                "bucket": rtb.bucket,
                "cost_model": self.cost_model,
                "layout": rtb.plan.layout,
                "q_total": rtb.q_total,
                "block_rows": rtb.plan.block_rows,
                "q_cap": rtb.plan.q_cap,
                "q_tile": rtb.plan.q_tile,
                "p_cap": rtb.plan.p_cap,
                "rerank": rtb.plan.rerank,
                "segments": len(rtb.plans),
                "shards": len(rtb.parts),
            }
            for rtb in self._runtimes.values()
        ]
