"""Serving benchmark: the online analog of paper Exp #5.

Exp #5 reports batch throughput (ms/image) at two batch sizes; a service
additionally owns the *latency distribution* that micro-batching buys that
throughput with. This module replays uniform and Zipf traces through a
warmed :class:`~repro.serving.SearchSession` + ``MicroBatcher`` and emits

  * CSV rows (the harness contract): per-trace p50/p95 latency, engine
    ms/image, cache hit rate, steady-state recompiles;
  * a JSON file (``benchmarks/out/serving.json`` or ``$REPRO_BENCH_OUT``)
    with the full metrics, per-bucket plans, and the per-plan *measured*
    ms/image observations (the session index's calibration store) — the
    data the ``plan()`` cost model is calibrated against;
  * ``--calibrate``: sweep batch-size x layout shapes, record measured
    ms/image into an index's calibration store, commit the fit, and emit
    the fitted coefficients (``serving_calibration.json``) — see
    docs/cost_model.md.
"""

from __future__ import annotations

import json
import math
import os

from benchmarks.common import (
    Corpus,
    bench_header,
    fit_payload,
    layout_bytes,
    row,
    write_artifact,
)


def _session(c, *, buckets, cache_leaves=0, cache_admit=2, probes=1,
             cost_model="auto"):
    from repro.serving import SearchSession

    s = SearchSession(
        c.index, c.tree, c.mesh, k=10, layout="auto", probes=probes,
        buckets=buckets, cache_leaves=cache_leaves,
        cache_admit_after=cache_admit, cost_model=cost_model,
    )
    s.warmup()
    return s


def _replay(session, c, *, skew, n_requests, desc_per_image, rate, seed=3):
    from repro.serving import MicroBatcher, TraceLoadGenerator

    n_images = len(c.vecs_np) // desc_per_image
    gen = TraceLoadGenerator(c.vecs_np, desc_per_image, seed=seed)
    reqs = gen.from_trace(n_requests, n_images, skew=skew, rate=rate)
    MicroBatcher(session, max_wait_ms=5.0, max_queue=4096).run(reqs)
    return session.metrics


def _traced_shard_replay(c, out_dir, *, trace_out=None, trace_sample=1.0,
                         shards=2, n_requests=200, desc_per_image=24):
    """The traced scatter-gather leg of :func:`run`: one Zipf replay over
    a ``shards``-segment index with a real tracer installed, exporting
    the trace artifacts next to the benchmark JSONs — the Chrome timeline
    (``serving_trace.json``, per-request queue-wait vs compute bars plus
    one process lane per shard), the structured event log
    (``serving_events.jsonl``), and the unified registry snapshot
    (``serving_metrics.json``). ``scripts/tracereport.py`` digests either
    trace file into a top-N-slowest breakdown."""
    import numpy as np

    from repro.index import Index
    from repro.obs import (
        Tracer,
        export_trace,
        get_registry,
        tracing,
        write_jsonl,
    )
    from repro.serving import (
        MicroBatcher,
        ShardedSearchSession,
        TraceLoadGenerator,
    )

    idx = Index.create(c.tree, None, mesh=c.mesh)
    for chunk in np.array_split(c.vecs_np, shards):
        idx.append(chunk)
    idx.commit()
    session = ShardedSearchSession(
        idx, mesh=c.mesh, shards=shards, k=10, buckets=(1024, 4096),
        cache_leaves=256, cache_admit_after=1,
    )
    session.warmup()
    n_images = len(c.vecs_np) // desc_per_image
    gen = TraceLoadGenerator(c.vecs_np, desc_per_image, seed=3)
    reqs = gen.from_trace(n_requests, n_images, skew="zipf", rate=100.0)
    tracer = Tracer(sample=trace_sample, seed=3)
    with tracing(tracer):
        MicroBatcher(session, max_wait_ms=5.0, max_queue=4096).run(reqs)
    paths = {
        "trace": export_trace(
            tracer, trace_out or os.path.join(out_dir, "serving_trace.json")
        ),
        "events": write_jsonl(
            tracer, os.path.join(out_dir, "serving_events.jsonl")
        ),
        "metrics": get_registry().dump(
            os.path.join(out_dir, "serving_metrics.json")
        ),
    }
    return tracer, session, paths


def run(*, trace_out=None, trace_sample=1.0):
    from repro.core.engine import CalibrationStore

    out_rows = []
    payload = {}
    c = Corpus()
    dpi = 24
    session = None
    # each session wraps the shared corpus index in its own ephemeral
    # facade; fold their calibration stores for the artifact
    calibration = CalibrationStore()
    for skew, cache_leaves in (("uniform", 0), ("zipf", 1024)):
        session = _session(
            c, buckets=(1024, 4096), cache_leaves=cache_leaves,
            cache_admit=1,
        )
        m = _replay(session, c, skew=skew, n_requests=200,
                    desc_per_image=dpi, rate=100.0)
        lat = m.latency.summary()
        name = f"serving_{skew}_200req"
        out_rows.append(row(
            name, lat["p50_ms"] / 1e3,
            f"p95_ms={lat['p95_ms']:.1f} ms_per_image={m.ms_per_image:.2f} "
            f"cache_hit={session.cache.hit_rate:.2f} "
            f"recompiles={session.steady_state_recompiles()}",
        ))
        calibration.merge(session.index.calibration)
        payload[skew] = {
            "metrics": m.to_dict(),
            "cache": session.cache.stats(),
            "plans": session.plan_summary(),
        }
    out_dir = os.environ.get("REPRO_BENCH_OUT", "benchmarks/out")
    os.makedirs(out_dir, exist_ok=True)
    # the traced scatter-gather leg: same engine, tracing on — its trace/
    # events/registry artifacts land next to serving.json
    tracer, traced_session, trace_paths = _traced_shard_replay(
        c, out_dir, trace_out=trace_out, trace_sample=trace_sample,
    )
    tm = traced_session.metrics
    calibration.merge(traced_session.index.calibration)
    payload["sharded_traced"] = {
        "metrics": tm.to_dict(),
        "obs": tracer.describe(),
        "shards": traced_session.n_shards,
        "artifacts": trace_paths,
    }
    out_rows.append(row(
        "serving_traced_2shard", tm.latency.percentile(50) / 1e3,
        f"p95_ms={tm.latency.percentile(95):.1f} "
        f"spans={tracer.describe()['spans']} "
        f"trace={trace_paths['trace']}",
    ))
    payload["header"] = bench_header(
        cost_model=session.active_cost_model(),
        layout_bytes=layout_bytes(session.index),
    )
    payload["plan_observations"] = calibration.snapshot()
    path = write_artifact(os.path.join(out_dir, "serving.json"), payload)
    out_rows.append(row("serving_json", 0.0, f"wrote={path}"))
    return out_rows


def _calibrated_index(c, *, batch_sizes=(256, 1024), rounds=2,
                      desc_per_image=24):
    """An ephemeral lifecycle Index over the benchmark corpus with a
    usable fitted calibration: measurements are recorded by sessions
    pinned to ``cost_model="heuristic"`` (they must not be steered by the
    model they feed), two batch shapes per layout — enough for the
    per-layout fit. The SLO replay's admission control and ladder/slab
    tuning all key off this fit."""
    import numpy as np

    from repro.index import Index
    from repro.serving import SearchSession

    idx = Index.create(c.tree, None, mesh=c.mesh)
    idx.append(c.vecs_np)
    idx.commit()
    q, _ = c.queries(max(batch_sizes))
    q = np.asarray(q)
    for layout in ("point_major", "query_routed"):
        for b in batch_sizes:
            s = SearchSession(idx, k=10, layout=layout, buckets=(int(b),),
                              cost_model="heuristic")
            s.warmup()
            for _ in range(rounds):
                s.search(q[:int(b)],
                         n_images=max(1, int(b) // desc_per_image))
    idx.commit()
    return idx


def _identical_results(by_rid_a: dict, by_rid_b: dict) -> tuple[int, int]:
    """(compared, mismatches) over the rids completed in both replays —
    the scheduling-never-changes-results gate."""
    import numpy as np

    shared = set(by_rid_a) & set(by_rid_b)
    mismatches = 0
    for rid in shared:
        a, b = by_rid_a[rid], by_rid_b[rid]
        if not (np.array_equal(a.ids, b.ids)
                and np.array_equal(a.dists, b.dists)):
            mismatches += 1
    return len(shared), mismatches


def slo_run(
    *,
    n_requests: int = 400,
    rate: float = 2000.0,
    desc_per_image: int = 24,
    corpus: Corpus | None = None,
    json_path: str | None = None,
) -> list[str]:
    """Deadline-aware vs FIFO scheduling under one multi-tenant trace.

    The same bursty multi-tenant trace (:func:`default_tenant_mix` —
    steady interactive/standard classes plus heavily bursty batch
    traffic) is replayed through a FIFO and an EDF micro-batcher over the
    same calibrated index at the same offered load. The JSON artifact
    (``serving_slo.json``) carries, per scheduler, the per-class latency
    distributions and SLO attainment, the queue-wait vs compute
    breakdown, queue-depth percentiles, and the shed/downgrade counters —
    plus the cross-scheduler comparison (interactive p95 speedup) and the
    result-divergence gate (must be zero: scheduling changes *when* a
    request runs, never *what* it returns).
    """
    from repro.serving import (
        MicroBatcher,
        SearchSession,
        TraceLoadGenerator,
        default_tenant_mix,
    )

    c = corpus or Corpus()
    idx = _calibrated_index(c, desc_per_image=desc_per_image)
    n_images = len(c.vecs_np) // desc_per_image
    gen = TraceLoadGenerator(c.vecs_np, desc_per_image, seed=3)
    # the queue-owned regime: offered load outruns the engine, so the
    # pending set is deep and dispatch *order* decides each class's tail;
    # a minority interactive class is the one EDF protects
    classes = default_tenant_mix(n_requests, rate=rate,
                                 interactive_frac=0.2, standard_frac=0.3)
    reqs = gen.multi_tenant(classes, n_images, seed=7)
    out_rows, sched_payload, by_rid, p95s = [], {}, {}, {}
    session = None
    for sched in ("fifo", "edf"):
        # buckets sized so the trace spans many dispatches — one giant
        # bucket would put every class in the same dispatch and leave the
        # scheduler nothing to order
        session = SearchSession(idx, mesh=c.mesh, k=10, layout="auto",
                                buckets=(128, 512), cost_model="auto")
        session.warmup()
        batcher = MicroBatcher(session, max_wait_ms=5.0, max_queue=4096,
                               scheduler=sched)
        comps = batcher.run(reqs)
        by_rid[sched] = {cc.rid: cc for cc in comps if cc.ids is not None}
        m = session.metrics
        pc = {
            name: cm.latency.percentile(95)
            for name, cm in m.per_class.items()
        }
        p95s[sched] = pc
        offered = len(reqs)
        sched_payload[sched] = {
            "metrics": m.to_dict(),
            "queue": m.queue_summary(),
            "shed_rate": m.shed / offered,
            "policy": {
                "shed_depth": batcher.policy.shed_depth,
                "on_overload": batcher.policy.on_overload,
                "deadlines_ms": dict(batcher.policy.deadlines_ms),
                "max_wait_ms": dict(batcher.policy.max_wait_ms),
            },
        }
        attain = {
            name: cm.slo_attainment for name, cm in m.per_class.items()
        }
        out_rows.append(row(
            f"serving_slo_{sched}",
            pc.get("interactive", float("nan")) / 1e3,
            f"int_p95={pc.get('interactive', float('nan')):.1f} "
            f"std_p95={pc.get('standard', float('nan')):.1f} "
            f"batch_p95={pc.get('batch', float('nan')):.1f} "
            f"attain_int={attain.get('interactive', 1.0):.2f} "
            f"shed={m.shed} wait_p95={m.wait.percentile(95):.1f} "
            f"compute_p95={m.compute.percentile(95):.1f}",
        ))
    compared, mismatches = _identical_results(by_rid["fifo"], by_rid["edf"])
    assert mismatches == 0, (
        f"{mismatches}/{compared} requests diverged between fifo and edf"
    )
    speedup = p95s["fifo"]["interactive"] / max(1e-9,
                                                p95s["edf"]["interactive"])
    out_rows.append(row(
        "serving_slo_speedup", 0.0,
        f"interactive_p95_fifo={p95s['fifo']['interactive']:.1f} "
        f"interactive_p95_edf={p95s['edf']['interactive']:.1f} "
        f"speedup={speedup:.2f}x divergence=0/{compared}",
    ))
    payload = {
        "header": bench_header(cost_model=session.active_cost_model()),
        "trace": {
            "n_requests": len(reqs),
            "rate": rate,
            "desc_per_image": desc_per_image,
            "classes": [
                {"priority": tc.priority, "n_requests": tc.n_requests,
                 "rate": tc.rate, "skew": tc.skew,
                 "burst_factor": tc.burst_factor}
                for tc in classes
            ],
        },
        "schedulers": sched_payload,
        "comparison": {
            "interactive_p95_fifo_ms": p95s["fifo"]["interactive"],
            "interactive_p95_edf_ms": p95s["edf"]["interactive"],
            "interactive_p95_speedup": speedup,
            "divergence": {"compared": compared, "mismatches": mismatches},
        },
    }
    out_dir = os.environ.get("REPRO_BENCH_OUT", "benchmarks/out")
    path = write_artifact(
        json_path or os.path.join(out_dir, "serving_slo.json"), payload
    )
    out_rows.append(row("serving_slo_json", 0.0, f"wrote={path}"))
    return out_rows


def slo_smoke() -> int:
    """SLO scheduling gate: one small multi-tenant trace replayed under
    FIFO and EDF over the same corpus. Asserts (a) zero result divergence
    (bit-identical ids + distances per request — scheduling never changes
    *what* a request returns) and (b) under EDF the interactive class's
    p95 beats the batch class's p95 (the deadline-aware ordering is
    actually doing something)."""
    from repro.serving import MicroBatcher, TraceLoadGenerator, \
        default_tenant_mix

    c = Corpus(rows=20_000, dim=32, fanouts=(16, 16))
    dpi = 20
    n_images = len(c.vecs_np) // dpi
    gen = TraceLoadGenerator(c.vecs_np, dpi, seed=3)
    # the offered load must outrun the engine (the queue, not the kernel,
    # owns the tail — the regime this PR schedules): at 2000 req/s the
    # whole trace arrives inside a couple of dispatches' wall time, so
    # the pending set is deep and ordering it is what matters
    reqs = gen.multi_tenant(
        default_tenant_mix(150, rate=2000.0), n_images, seed=7
    )
    by_rid, metrics = {}, {}
    for sched in ("fifo", "edf"):
        session = _session(c, buckets=(256, 1024))
        comps = MicroBatcher(session, max_wait_ms=5.0, max_queue=4096,
                             scheduler=sched).run(reqs)
        assert session.metrics.requests == len(reqs), (
            f"{sched}: served {session.metrics.requests}/{len(reqs)}"
        )
        by_rid[sched] = {cc.rid: cc for cc in comps if cc.ids is not None}
        metrics[sched] = session.metrics
    compared, mismatches = _identical_results(by_rid["fifo"], by_rid["edf"])
    assert compared == len(reqs) and mismatches == 0, (
        f"fifo vs edf divergence: {mismatches}/{compared} "
        f"(of {len(reqs)} requests)"
    )
    m = metrics["edf"]
    int_p95 = m.per_class["interactive"].latency.percentile(95)
    bat_p95 = m.per_class["batch"].latency.percentile(95)
    assert int_p95 < bat_p95, (
        f"EDF interactive p95 {int_p95:.1f} ms not under batch p95 "
        f"{bat_p95:.1f} ms"
    )
    print(
        f"# slo smoke: fifo == edf on {compared} requests (0 diverged); "
        f"EDF interactive p95 {int_p95:.1f} ms < batch p95 {bat_p95:.1f} ms; "
        f"wait p95 {m.wait.percentile(95):.1f} ms, "
        f"compute p95 {m.compute.percentile(95):.1f} ms"
    )
    return 0


def shard_sweep(
    shard_counts=(1, 2, 4),
    *,
    segments: int = 4,
    strategy: str = "balanced",
    n_queries: int = 2048,
    batch_rows: int = 1024,
    desc_per_image: int = 24,
    corpus: Corpus | None = None,
    json_path: str | None = None,
    check_identity: bool = True,
) -> list[str]:
    """Scatter-gather scaling: engine ms/image vs. shard count.

    The same corpus is appended as ``segments`` segments of one Index,
    then served through a :class:`~repro.serving.ShardedSearchSession` at
    each shard count — one JSON entry (and one CSV row) per count, all
    stamped with the shard plan and git rev so trajectories are
    comparable across PRs. Every dispatch feeds the per-plan ms/image
    observations (the ``plan()`` cost-model calibration data), and the
    sweep asserts each count's results are bit-identical to the first
    (the scatter-gather exactness gate, on by default).
    """
    import numpy as np

    from repro.index import Index
    from repro.serving import ShardedSearchSession

    c = corpus or Corpus()
    idx = Index.create(c.tree, None, mesh=c.mesh)
    # segment sizes on a round boundary: build_index pads each segment to
    # ~2x its rows, and a prime-ish padded count leaves plan() no usable
    # block_rows divisor (loud ValueError) — same corpus either way
    n = len(c.vecs_np)
    step = max(1000, n // segments // 1000 * 1000)
    bounds = [min(i * step, n) for i in range(1, segments)] + [n]
    for lo, hi in zip([0] + bounds[:-1], bounds):
        if hi > lo:
            idx.append(c.vecs_np[lo:hi])
    idx.commit()
    q, _ = c.queries(n_queries)
    q = np.asarray(q)
    out_rows, entries, ref = [], [], None
    session = None
    for n in shard_counts:
        session = ShardedSearchSession(
            idx, shards=n, shard_strategy=strategy, k=10, layout="auto",
            buckets=(batch_rows,),
        )
        session.warmup()
        got_i, got_d = [], []
        for s in range(0, len(q), batch_rows):
            chunk = q[s: s + batch_rows]
            ids, dists = session.search(
                chunk, n_images=max(1, len(chunk) // desc_per_image)
            )
            got_i.append(ids)
            got_d.append(dists)
        if check_identity:
            if ref is None:
                ref = (np.concatenate(got_i), np.concatenate(got_d))
            else:
                np.testing.assert_array_equal(np.concatenate(got_i), ref[0])
                np.testing.assert_array_equal(np.concatenate(got_d), ref[1])
        m = session.metrics
        recomp = session.steady_state_recompiles()
        assert recomp == 0, f"shards={n}: {recomp} steady-state recompiles"
        entries.append({
            "shards": n,
            "plan": session.shard_plan.to_json(),
            "ms_per_image": m.ms_per_image,
            "engine_ms": m.engine_ms,
            "engine_batches": m.engine_batches,
            "query_rows": m.query_rows,
        })
        out_rows.append(row(
            f"serving_shards_{n}", m.engine_ms / 1e3 / m.engine_batches,
            f"ms_per_image={m.ms_per_image:.2f} "
            f"plan={session.shard_plan.describe().replace(' ', '_')} "
            f"identical={'checked' if check_identity else 'unchecked'}",
        ))
    out_dir = os.environ.get("REPRO_BENCH_OUT", "benchmarks/out")
    path = json_path or os.path.join(out_dir, "serving_shards.json")
    payload = {
        "header": bench_header(
            shard_plan={"strategy": strategy, "counts": list(shard_counts),
                        "segments": segments},
            cost_model=session.active_cost_model(),
        ),
        "sweep": entries,
        "plan_observations": idx.calibration.snapshot(),
    }
    write_artifact(path, payload)
    out_rows.append(row("serving_shards_json", 0.0, f"wrote={path}"))
    return out_rows


def _index_queries(idx, n: int, *, noise: float = 4.0, seed: int = 0):
    """``n`` perturbed live descriptor rows from ``idx`` — dimension-true
    query vectors for calibrating an arbitrary durable index."""
    import numpy as np

    if not idx.segments:
        raise ValueError(f"index at {idx.directory} has no live rows")
    ids = np.concatenate([s.host_ids() for s in idx.segments])
    ids = ids[ids >= 0]
    ids = np.setdiff1d(ids, idx.tombstones)
    if ids.size == 0:
        raise ValueError(f"index at {idx.directory} has no live rows")
    rng = np.random.default_rng(seed)
    take = rng.choice(ids, size=n, replace=ids.size < n)
    q = idx.read_rows(take)
    return q + rng.standard_normal(q.shape).astype(np.float32) * noise


def calibrate(
    *,
    index_dir: str | None = None,
    batch_sizes=(256, 1024),
    layouts=("point_major", "query_routed"),
    rounds: int = 3,
    desc_per_image: int = 24,
    corpus: Corpus | None = None,
    json_path: str | None = None,
    rows: int | None = None,
):
    """Sweep (batch size x layout) shapes, record measured ms/image into
    an index's calibration store, commit, and fit the cost model.

    Each sweep cell runs a warmed single-bucket session pinned to one
    layout with ``cost_model="heuristic"`` (measurements must not be
    steered by the model they will feed). The recorded observations land
    in the index's manifest via ``commit`` (for a durable ``index_dir``),
    and the fitted per-layout coefficients (``ms ≈ a·(rows_scanned/tile)
    + b·probes·leaves + c·batch + d``) are written to
    ``serving_calibration.json`` — after which ``plan(model="auto")``
    over this index prefers the fit (docs/cost_model.md).
    """
    import numpy as np

    from repro.index import Index
    from repro.serving import SearchSession

    if index_dir:
        # calibrate the durable index in place: queries must come from
        # *its* corpus (its dim), not the synthetic benchmark Corpus
        idx = Index.open(index_dir)
        q_base = _index_queries(idx, max(batch_sizes))
    else:
        c = corpus or (Corpus(rows=rows) if rows else Corpus())
        idx = Index.create(c.tree, None, mesh=c.mesh)
        idx.append(c.vecs_np)
        idx.commit()
        q_base, _ = c.queries(max(batch_sizes))
        q_base = np.asarray(q_base)
    out_rows = []
    for layout in layouts:
        for b in batch_sizes:
            session = SearchSession(
                idx, k=10, layout=layout, buckets=(int(b),),
                cost_model="heuristic",
            )
            session.warmup()
            q = q_base[:int(b)]
            for _ in range(rounds):
                session.search(
                    q, n_images=max(1, int(b) // desc_per_image)
                )
            m = session.metrics
            out_rows.append(row(
                f"calibrate_{layout}_b{b}",
                m.engine_ms / 1e3 / max(1, m.engine_batches),
                f"ms_per_image={m.ms_per_image:.3f}",
            ))
    # ephemeral indexes commit too: committed_version must name a
    # manifest state that actually contains these observations
    version = idx.commit()
    payload = dict(
        fit_payload(idx.calibration, version),
        observations=idx.calibration.snapshot(),
    )
    out_dir = os.environ.get("REPRO_BENCH_OUT", "benchmarks/out")
    path = write_artifact(
        json_path or os.path.join(out_dir, "serving_calibration.json"),
        payload,
    )
    out_rows.append(row(
        "serving_calibration_json", 0.0,
        f"wrote={path} layouts_fitted={len(payload['coefficients'])}",
    ))
    return out_rows


def calibration_smoke() -> int:
    """Calibration round-trip gate: record during serving → ``commit``
    persists it to the manifest → ``Index.open`` reloads it →
    ``plan(model="auto")`` over the reopened store is decided by the
    calibrated models (fitted/observed), not the heuristic."""
    import tempfile

    import numpy as np

    from repro.core.engine import PlanShapes, plan as make_plan, resolve_model
    from repro.index import Index
    from repro.serving import SearchSession

    c = Corpus(rows=20_000, dim=32, fanouts=(16, 16))
    with tempfile.TemporaryDirectory() as d:
        idx = Index.create(c.tree, d, mesh=c.mesh)
        idx.append(c.vecs_np)
        idx.commit()
        q, _ = c.queries(512)
        q = np.asarray(q)
        # two batch shapes per layout: enough distinct measurements for
        # the per-layout fit to become usable
        for layout in ("point_major", "query_routed"):
            for b in (256, 512):
                s = SearchSession(idx, k=10, layout=layout, buckets=(b,),
                                  cost_model="heuristic")
                s.warmup()
                for _ in range(2):
                    s.search(q[:b], n_images=max(1, b // 24))
        assert idx.calibration.dirty, "serving dispatches did not record"
        n_recorded = len(idx.calibration)
        assert n_recorded >= 4, idx.calibration.snapshot()
        idx.commit()
        assert not idx.calibration.dirty
        reopened = Index.open(d, mesh=c.mesh)
    assert len(reopened.calibration) == n_recorded, (
        f"reopened {len(reopened.calibration)} != recorded {n_recorded}"
    )
    # decide at a batch size the sweep never measured: only the fit can
    # price it — plan(model="auto") must be decided by the fitted model
    rows_ = reopened.segments[0].rows
    shapes = dict(rows=rows_, n_leaves=c.tree.n_leaves, n_queries=384,
                  n_shards=1, k=10)
    candidates = tuple(
        make_plan(layout=lay, **shapes)
        for lay in ("point_major", "query_routed")
    )
    pick, kind = resolve_model("auto", reopened.calibration).decide(
        candidates,
        PlanShapes(rows=rows_, n_queries=384, n_shards=1,
                   n_leaves=c.tree.n_leaves),
    )
    assert kind == "fitted", (
        f"plan(model='auto') fell back to {kind!r} despite "
        f"{len(reopened.calibration)} reloaded calibration records"
    )
    auto = make_plan(model="auto", calibration=reopened.calibration, **shapes)
    assert auto.layout == pick.layout
    print(
        f"# calibration smoke: record → commit → reopen round-trips "
        f"{len(reopened.calibration)} plan signatures; plan(model='auto') "
        f"decided by the {kind} model → {auto.layout}"
    )
    return 0


def smoke() -> int:
    """Tiny serving gate: small corpus, 2 buckets, ~100 requests; asserts
    p95 is finite and the compile count stays at the warmed-bucket count."""
    c = Corpus(rows=20_000, dim=32, fanouts=(16, 16))
    session = _session(c, buckets=(256, 1024), cache_leaves=256,
                       cache_admit=1, probes=2)
    warmed = session.recompiles()
    assert warmed == 2, f"expected 2 warmed bucket programs, got {warmed}"
    m = _replay(session, c, skew="zipf", n_requests=100, desc_per_image=20,
                rate=200.0)
    p95 = m.latency.percentile(95)
    assert math.isfinite(p95), f"p95 latency not finite: {p95}"
    assert session.recompiles() == warmed, (
        f"steady-state recompile: {session.recompiles()} != {warmed}"
    )
    assert m.requests == 100, f"served {m.requests}/100"
    print(
        f"# serving smoke: p50 {m.latency.percentile(50):.1f} ms, "
        f"p95 {p95:.1f} ms, ms/image {m.ms_per_image:.2f}, "
        f"cache hit {session.cache.hit_rate:.2f}, recompiles 0",
    )
    return 0


def sharded_smoke() -> int:
    """Scatter-gather gate. Asserts (a) a `ShardedSearchSession` returns
    ids+dists bit-identical to the unsharded `SearchSession` over the
    same index, (b) a small shard sweep (counts 1/2/3 over a 3-segment
    index) is per-count bit-identical and recompile-free (assertions
    inside :func:`shard_sweep`), and (c) the sweep's JSON artifact
    carries one row per shard count plus the git-rev/shard-plan header."""
    import tempfile

    import numpy as np

    from repro.index import Index
    from repro.serving import SearchSession, ShardedSearchSession

    c = Corpus(rows=20_000, dim=32, fanouts=(16, 16))
    idx = Index.create(c.tree, None, mesh=c.mesh)
    idx.append(c.vecs_np[:12_000])
    idx.append(c.vecs_np[12_000:])
    idx.commit()
    q, _ = c.queries(256)
    q = np.asarray(q)
    ref = SearchSession(idx, k=10, probes=2, buckets=(256,))
    ref.warmup()
    for shards in (2, 3):
        s = ShardedSearchSession(idx, shards=shards, k=10, probes=2,
                                 buckets=(256,))
        s.warmup()
        for n in (1, 100, 256):
            ids, dists = s.search(q[:n])
            ref_ids, ref_dists = ref.search(q[:n])
            np.testing.assert_array_equal(ids, ref_ids)
            np.testing.assert_array_equal(dists, ref_dists)
        assert s.steady_state_recompiles() == 0

    counts = (1, 2, 3)
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "serving_shards.json")
        shard_sweep(
            counts, segments=3, n_queries=512, batch_rows=256,
            corpus=c, json_path=path,
        )
        with open(path) as f:
            payload = json.load(f)
    assert [e["shards"] for e in payload["sweep"]] == list(counts), payload
    assert payload["header"]["git_rev"], payload["header"]
    assert payload["header"]["shard_plan"]["strategy"] == "balanced"
    ms = ", ".join(
        f"x{e['shards']}={e['ms_per_image']:.2f}" for e in payload["sweep"]
    )
    print("# sharded smoke: session == sharded session (shards 2/3, "
          f"256 queries, k=10); sweep bit-identical at 1/2/3; ms/image {ms}")
    return 0


def codes_smoke() -> int:
    """Compressed-codes gate: train → encode → commit → ``Index.open``
    round-trips the codebook → ``plan(model="auto")`` picks the
    ``scan_codes`` tier at the serving shape → the ADC scan + exact
    rerank session meets the recall floor against a scan-exact reference
    at the same probe width — all at a ≥8x resident-bytes reduction
    (docs/compressed_codes.md)."""
    import tempfile

    import numpy as np

    from repro.index import Index
    from repro.serving import SearchSession

    c = Corpus(rows=20_000, dim=32, fanouts=(16, 16))
    k, probes = 10, 8
    with tempfile.TemporaryDirectory() as d:
        idx = Index.create(c.tree, d, mesh=c.mesh)
        idx.append(c.vecs_np[:12_000])
        idx.append(c.vecs_np[12_000:])
        idx.enable_codes(m=8, bits=8)
        idx.commit()
        reopened = Index.open(d, mesh=c.mesh)
        cs = reopened.codes_stats()
        assert cs is not None, "codes artifact did not survive the commit"
        assert cs["compression_ratio"] >= 8.0, cs
        q, _ = c.queries(256)
        q = np.asarray(q)
        # scan-exact reference over the same index at the same probes —
        # the recall floor is codes-vs-exact, not codes-vs-ground-truth
        ref_ids = np.asarray(
            reopened.search(q, k=k, probes=probes,
                            layout="point_major").ids
        )
        session = SearchSession(reopened, mesh=c.mesh, k=k, probes=probes,
                                buckets=(256,))
        assert session.serving_layout == "scan_codes", (
            f"plan(auto) served {session.serving_layout} at a shape the "
            "codes tier should win"
        )
        session.warmup()
        ids, dists = session.search(q)
        assert session.steady_state_recompiles() == 0
        # the warmed session and the index facade run the same tier —
        # one ADC scan + exact rerank — and must agree bit for bit
        res = reopened.search(q, k=k, probes=probes, layout="scan_codes")
        np.testing.assert_array_equal(ids, np.asarray(res.ids))
        np.testing.assert_array_equal(dists, np.asarray(res.dists))
        recall = float(np.mean([
            len(set(ids[i][ids[i] >= 0]) & set(ref_ids[i][ref_ids[i] >= 0]))
            / k
            for i in range(len(q))
        ]))
        assert recall >= 0.9, (
            f"recall@{k}(scan_codes vs scan-exact) {recall:.3f} < 0.9"
        )
        rr = session.plan_summary()[0]["rerank"]
    print(
        f"# codes smoke: {cs['compression_ratio']:.0f}x resident bytes "
        f"({cs['bytes_per_row']}B/row vs {cs['raw_bytes_per_row']}B), "
        f"plan(auto) -> scan_codes, rerank={rr}, "
        f"recall@{k} {recall:.3f} vs scan-exact, session == facade, "
        f"recompiles 0"
    )
    return 0


def kernel_smoke() -> int:
    """Fused fast-path gate (docs/kernels.md): the same served trace
    through an ``impl="xla"`` and an ``impl="fused"`` session over one
    index. Asserts (a) every request's ids + distances are bit-identical
    between the two impls (the fused executor contract), (b) zero
    steady-state recompiles after warmup on both, and (c) fused ms/image
    within 1.5x of xla — off-TPU the fused path is the pipelined wave
    sweep, so it must not regress throughput while buying the kernel its
    on-TPU dispatch. Writes ``serving_kernel.json`` with each leg's
    ms/image stamped under its active impl in the header."""
    import numpy as np  # noqa: F401 (via _identical_results)

    from repro.index import Index
    from repro.serving import MicroBatcher, SearchSession, TraceLoadGenerator

    c = Corpus(rows=20_000, dim=32, fanouts=(16, 16))
    idx = Index.create(c.tree, None, mesh=c.mesh)
    idx.append(c.vecs_np[:12_000])
    idx.append(c.vecs_np[12_000:])
    idx.commit()
    dpi = 20
    n_images = len(c.vecs_np) // dpi
    gen = TraceLoadGenerator(c.vecs_np, dpi, seed=3)
    reqs = gen.from_trace(100, n_images, skew="zipf", rate=200.0)
    by_impl, legs = {}, {}
    for impl in ("xla", "fused"):
        # cache OFF: a cache-served answer is a CPU recompute under a
        # rounding contract, not the executor's bits — and this gate is
        # exactly about the executor's bits
        s = SearchSession(idx, mesh=c.mesh, k=10, layout="point_major",
                          probes=2, impl=impl, buckets=(256, 1024),
                          cache_leaves=0, cost_model="heuristic")
        s.warmup()
        comps = MicroBatcher(s, max_wait_ms=5.0, max_queue=4096).run(reqs)
        m = s.metrics
        assert m.requests == len(reqs), (
            f"{impl}: served {m.requests}/{len(reqs)}"
        )
        recomp = s.steady_state_recompiles()
        assert recomp == 0, f"{impl}: {recomp} steady-state recompiles"
        assert all(p["impl"] == impl for p in s.plan_summary())
        by_impl[impl] = {cc.rid: cc for cc in comps if cc.ids is not None}
        legs[impl] = {
            "header": bench_header(impl=impl),
            "ms_per_image": m.ms_per_image,
            "plans": s.plan_summary(),
        }
    compared, mismatches = _identical_results(by_impl["xla"],
                                              by_impl["fused"])
    assert compared == len(reqs) and mismatches == 0, (
        f"fused vs xla divergence: {mismatches}/{compared} "
        f"(of {len(reqs)} requests)"
    )
    ratio = legs["fused"]["ms_per_image"] / max(
        1e-9, legs["xla"]["ms_per_image"]
    )
    assert ratio <= 1.5, (
        f"fused ms/image {legs['fused']['ms_per_image']:.2f} is {ratio:.2f}x "
        f"xla's {legs['xla']['ms_per_image']:.2f} (bound 1.5x)"
    )
    out_dir = os.environ.get("REPRO_BENCH_OUT", "benchmarks/out")
    write_artifact(os.path.join(out_dir, "serving_kernel.json"), {
        "header": bench_header(impl="fused"),
        "legs": legs,
        "divergence": {"compared": compared, "mismatches": mismatches},
        "ms_per_image_ratio": ratio,
    })
    print(
        f"# kernel smoke: fused == xla on {compared} requests (0 diverged); "
        f"ms/image fused {legs['fused']['ms_per_image']:.2f} vs "
        f"xla {legs['xla']['ms_per_image']:.2f} ({ratio:.2f}x, bound 1.5x); "
        f"recompiles 0"
    )
    return 0


def dynamicity_smoke() -> int:
    """Read-during-write gate (docs/dynamicity.md): replay a multi-tenant
    trace against a pinned-version session while a background thread
    appends + incrementally compacts the same durable index. Asserts no
    request is dropped, zero steady-state recompiles across every adopted
    version, p95 within 2x of a frozen-index baseline, and the final
    refreshed results bit-identical to a fresh ``Index.open``."""
    import tempfile
    import threading

    import numpy as np

    from repro.index import Index
    from repro.serving import MicroBatcher, SearchSession, TraceLoadGenerator
    from repro.serving.trace import default_tenant_mix

    c = Corpus(rows=20_000, dim=32, fanouts=(16, 16))
    base, chunk, desc, n_req = 16_000, 500, 20, 150
    kw = dict(mesh=c.mesh, k=10, layout="point_major", probes=2,
              buckets=(256, 1024), cost_model="heuristic")
    with tempfile.TemporaryDirectory() as d:
        idx = Index.create(c.tree, d, mesh=c.mesh)
        idx.append(c.vecs_np[: base // 2])
        idx.append(c.vecs_np[base // 2: base])
        idx.commit()

        gen = TraceLoadGenerator(c.vecs_np[:base], desc, seed=3)
        reqs = gen.multi_tenant(
            default_tenant_mix(n_req, rate=250.0), base // desc)

        # frozen baseline: the same trace against the index as committed
        # above, with no writer running
        frozen = SearchSession(idx, **kw)
        frozen.warmup()
        MicroBatcher(frozen, max_wait_ms=5.0, max_queue=4096,
                     scheduler="fifo").run(reqs)
        base_p95 = frozen.metrics.latency.percentile(95)

        session = SearchSession(idx, **kw)
        session.warmup()
        v0 = session.pinned_version
        # one commit lands before the replay starts, so at least one
        # adoption happens regardless of writer-thread scheduling
        idx.append(c.vecs_np[base: base + chunk])
        idx.commit()

        stop = threading.Event()

        def writer() -> None:
            nxt = base + chunk
            while not stop.is_set() and nxt + chunk <= len(c.vecs_np):
                idx.append(c.vecs_np[nxt: nxt + chunk])
                idx.commit()
                idx.compact(incremental=True)
                nxt += chunk

        t = threading.Thread(target=writer)
        t.start()
        try:
            done = MicroBatcher(session, max_wait_ms=5.0, max_queue=4096,
                                scheduler="fifo", refresh_every=5).run(reqs)
        finally:
            stop.set()
            t.join()

        dropped = [x for x in done if x.source in ("rejected", "shed")]
        assert not dropped, f"{len(dropped)} requests dropped mid-refresh"
        assert len(done) == n_req
        assert session.steady_state_recompiles() == 0, (
            "adopting a new index version recompiled on the request path"
        )
        adopted = session.pinned_version - v0
        assert adopted > 0, "no newer version was ever adopted"
        # 2x the frozen baseline, plus absolute headroom for scheduler
        # noise: compute is wall-clock on a shared CPU, and the writer
        # thread competes for it by design
        p95 = session.metrics.latency.percentile(95)
        assert p95 <= 2.0 * base_p95 + 150.0, (
            f"p95 {p95:.1f}ms vs frozen baseline {base_p95:.1f}ms"
        )
        # final identity: adopt the last committed version and compare
        # against a cold open of the same directory
        session.maybe_refresh()
        q, _ = c.queries(256)
        q = np.asarray(q)
        ids, dists = session.search(q)
        res = Index.open(d, mesh=c.mesh).search(
            q, k=10, probes=2, layout="point_major", cost_model="heuristic")
        np.testing.assert_array_equal(ids, np.asarray(res.ids))
        np.testing.assert_array_equal(dists, np.asarray(res.dists))
    print(
        f"# dynamicity smoke: {n_req} requests served across "
        f"{adopted} adopted versions (v{v0} -> v{session.pinned_version}), "
        f"0 dropped, recompiles 0, p95 {p95:.1f}ms "
        f"(frozen {base_p95:.1f}ms), refreshed session == fresh open"
    )
    return 0


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="run the serving-session smoke gate")
    ap.add_argument("--sharded-smoke", action="store_true",
                    help="run the scatter-gather bit-identity gate")
    ap.add_argument("--calibration-smoke", action="store_true",
                    help="run the calibration round-trip gate "
                         "(record -> commit -> reopen -> fitted plan)")
    ap.add_argument("--slo-smoke", action="store_true",
                    help="run the SLO scheduling gate (fifo == edf "
                         "results, EDF interactive p95 < batch p95)")
    ap.add_argument("--codes-smoke", action="store_true",
                    help="run the compressed-codes gate (train -> commit "
                         "-> reopen -> auto plans scan_codes -> ADC + "
                         "rerank recall floor at >=8x fewer bytes)")
    ap.add_argument("--kernel-smoke", action="store_true",
                    help="run the fused fast-path gate (fused == xla on a "
                         "served trace, 0 recompiles, ms/image within "
                         "1.5x) -> benchmarks/out/serving_kernel.json")
    ap.add_argument("--dynamicity-smoke", action="store_true",
                    help="run the read-during-write gate (serve a trace "
                         "while a writer thread appends + incrementally "
                         "compacts: 0 drops, 0 recompiles, bounded p95, "
                         "final results == fresh open)")
    ap.add_argument("--slo", action="store_true",
                    help="replay the multi-tenant trace under fifo and "
                         "edf, report per-class SLO attainment and the "
                         "queue-wait vs compute breakdown -> "
                         "benchmarks/out/serving_slo.json")
    ap.add_argument("--requests", type=int, default=400,
                    help="trace length for --slo")
    ap.add_argument("--rate", type=float, default=250.0,
                    help="offered load (req/s) for --slo")
    ap.add_argument("--shard-sweep", action="store_true",
                    help="ms/image vs shard count -> "
                         "benchmarks/out/serving_shards.json")
    ap.add_argument("--calibrate", action="store_true",
                    help="sweep batch x layout shapes, commit the measured "
                         "ms/image into the index manifest, and fit the "
                         "cost model -> serving_calibration.json")
    ap.add_argument("--index-dir", default=None,
                    help="calibrate an existing durable index instead of "
                         "an ephemeral benchmark corpus (--calibrate)")
    ap.add_argument("--batch-sizes", type=int, nargs="+",
                    default=(256, 1024),
                    help="bucket sizes the calibration sweep measures")
    ap.add_argument("--shards", type=int, nargs="+", default=(1, 2, 4),
                    help="shard counts to sweep")
    ap.add_argument("--segments", type=int, default=4,
                    help="segments the sweep corpus is appended as")
    ap.add_argument("--strategy", choices=("round_robin", "balanced"),
                    default="balanced")
    ap.add_argument("--json", default=None, help="JSON output path")
    ap.add_argument("--trace-out", default=None,
                    help="write the traced leg's Chrome trace here "
                         "(default: benchmarks/out/serving_trace.json; "
                         ".jsonl = structured event log)")
    ap.add_argument("--trace-sample", type=float, default=1.0,
                    help="fraction of requests traced in the traced leg "
                         "(deterministic per-request hash)")
    args = ap.parse_args(argv)
    if args.smoke:
        return smoke()
    if args.sharded_smoke:
        return sharded_smoke()
    if args.calibration_smoke:
        return calibration_smoke()
    if args.slo_smoke:
        return slo_smoke()
    if args.codes_smoke:
        return codes_smoke()
    if args.kernel_smoke:
        return kernel_smoke()
    if args.dynamicity_smoke:
        return dynamicity_smoke()
    print("name,us_per_call,derived")
    if args.slo:
        rows = slo_run(n_requests=args.requests, rate=args.rate,
                       json_path=args.json)
    elif args.shard_sweep:
        rows = shard_sweep(tuple(args.shards), segments=args.segments,
                           strategy=args.strategy, json_path=args.json)
    elif args.calibrate:
        rows = calibrate(index_dir=args.index_dir,
                         batch_sizes=tuple(args.batch_sizes),
                         json_path=args.json)
    else:
        rows = run(trace_out=args.trace_out, trace_sample=args.trace_sample)
    for r in rows:
        print(r)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
