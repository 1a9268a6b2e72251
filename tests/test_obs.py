"""Observability: span-tree fan-in integrity under coalesced batches,
deterministic sampling, the hard bit-identity invariant (traced ==
untraced ids AND distances at shards 1-3), Chrome/JSONL export
round-trips, the unified metrics registry, and bounded-memory
LatencyStats (exact by default, seeded reservoir when bounded)."""

import gc
import importlib.util
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.tree import build_tree
from repro.data import synth
from repro.distributed.meshutil import local_mesh
from repro.index import Index
from repro.obs import (
    NULL_SPAN,
    NULL_TRACER,
    MetricsRegistry,
    Span,
    Tracer,
    chrome_trace_events,
    summary,
    tracing,
    write_chrome_trace,
    write_jsonl,
)
from repro.obs.export import PID_ENGINE, PID_REQUESTS, PID_SHARD_BASE
from repro.serving import (
    MicroBatcher,
    SearchSession,
    ShardedSearchSession,
    TraceLoadGenerator,
)
from repro.serving.metrics import HIST_BOUNDS_MS, LatencyStats, ServingMetrics

DIM = 16
N = 2000


@pytest.fixture(scope="module")
def corpus():
    vecs_np, _ = synth.sample_descriptors(N, DIM, seed=0, n_centers=40)
    tree = build_tree(jnp.asarray(vecs_np), (8, 4), key=jax.random.PRNGKey(1))
    return vecs_np, tree, local_mesh()


@pytest.fixture(scope="module")
def grown(corpus):
    """Three-segment in-memory index, so shards 1-3 are all non-empty."""
    vecs_np, tree, mesh = corpus
    idx = Index.create(tree, None, mesh=mesh)
    for lo, hi in ((0, 500), (500, 1500), (1500, N)):
        idx.append(vecs_np[lo:hi])
    idx.commit()
    return idx


def _replay(corpus, idx, *, shards, tracer, n_requests=40, rate=2000.0,
            cache_leaves=0):
    """One seeded zipf replay; returns (completions, session). The trace
    is deterministic given the seed, so two replays see identical
    requests — only the tracer differs. Bit-identity comparisons keep the
    hot-leaf cache OFF: the virtual clock advances by measured wall
    compute, so cache admission timing can differ between replays, and a
    cache-served answer is a CPU recompute under a rounding contract
    (tests/test_serving.py), not the engine's bits. Engine results are
    batch-composition invariant, so engine-only replays are deterministic
    by construction."""
    vecs_np, tree, mesh = corpus
    if shards is None:
        s = SearchSession(idx, k=5, layout="point_major", probes=2,
                          buckets=(32, 96), cache_leaves=cache_leaves,
                          cache_admit_after=1)
    else:
        s = ShardedSearchSession(idx, shards=shards, k=5,
                                 layout="point_major", probes=2,
                                 buckets=(32, 96), cache_leaves=cache_leaves,
                                 cache_admit_after=1)
    s.warmup()
    gen = TraceLoadGenerator(vecs_np, 20, seed=3)
    reqs = gen.from_trace(n_requests, N // 20, skew="zipf", rate=rate)
    with tracing(tracer):
        done = MicroBatcher(s, max_wait_ms=4.0, max_queue=1024).run(reqs)
    return done, s


@pytest.fixture(scope="module")
def traced2(corpus, grown):
    """One traced 2-shard replay shared by the export/fan-in tests (cache
    enabled here — no cross-run comparison, just span coverage)."""
    tracer = Tracer(sample=1.0, seed=0)
    done, _ = _replay(corpus, grown, shards=2, tracer=tracer,
                      cache_leaves=32)
    return tracer, done


# ---------------------------------------------------------------------------
# Tracer unit behavior
# ---------------------------------------------------------------------------


def test_tracer_records_span_tree():
    tr = Tracer()
    with tr.span("outer", kind_of="root") as outer:
        with tr.span("inner") as inner:  # auto-parents under outer
            inner.set(rows=3)
        ex = tr.add_span("explicit", 1.0, 2.0, trace_id=7, parent=outer,
                         shard=1)
        ev = tr.event("tick", t=1.5, trace_id=7)
    assert inner.parent_id == outer.span_id
    assert ex.parent_id == outer.span_id and ex.trace_id == 7
    assert ex.dur_ms == pytest.approx(1000.0)
    assert ev.kind == "event" and ev.dur_ms == 0.0
    assert outer.t1 is not None and outer.t1 >= outer.t0
    assert len(tr) == 4 and tr.n_events() == 1
    d = tr.describe()
    assert d == {"enabled": True, "sample": 1.0, "spans": 3, "events": 1,
                 "dropped": 0}


def test_tracer_max_spans_cap_counts_drops():
    tr = Tracer(max_spans=2)
    a = tr.add_span("a", 0.0, 1.0)
    b = tr.add_span("b", 0.0, 1.0)
    c = tr.add_span("c", 0.0, 1.0)  # over the cap: dropped, not recorded
    assert isinstance(a, Span) and isinstance(b, Span)
    assert c is NULL_SPAN
    assert len(tr) == 2 and tr.dropped == 1
    with tr.span("d") as d:  # context-manager path drops too
        assert d is NULL_SPAN
    assert tr.dropped == 2


def test_tracer_validates_sample_rate():
    with pytest.raises(ValueError, match="must be in"):
        Tracer(sample=1.5)


def test_timebase_rebases_wall_spans():
    tr = Tracer()
    with tr.timebase(5.0):
        with tr.span("work") as s:
            pass
    assert 5.0 <= s.t0 < 5.5  # lands at virtual time, not wall time
    assert s.t1 >= s.t0
    assert tr.now() < 5.0  # restored after the block


def test_null_tracer_is_inert():
    assert not NULL_TRACER.enabled
    assert len(NULL_TRACER) == 0
    assert NULL_TRACER.sampled(1) is False
    assert NULL_TRACER.add_span("x", 0, 1) is NULL_SPAN
    assert NULL_TRACER.event("x") is NULL_SPAN
    with NULL_TRACER.span("x") as s:
        assert s.set(rows=1) is s
    assert NULL_TRACER.describe() == {"enabled": False, "sample": 0.0,
                                      "spans": 0, "events": 0, "dropped": 0}


def test_tracer_span_opens_a_trace_annotation_only_when_enabled(
        monkeypatch):
    from repro.obs import tracer as tracer_mod

    opened = []

    class Recorder:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            opened.append(("open", self.name))

        def __exit__(self, *exc):
            opened.append(("close", self.name))
            return False

    monkeypatch.setattr(tracer_mod, "TraceAnnotation", Recorder)
    with NULL_TRACER.span("off") as s:
        assert s is NULL_SPAN
    assert opened == []  # the disabled tracer builds none
    tr = Tracer()
    with tr.span("outer"):
        with tr.span("inner"):
            assert opened == [("open", "outer"), ("open", "inner")]
    assert opened[2:] == [("close", "inner"), ("close", "outer")]
    tr.add_span("virtual", 0.0, 1.0)  # explicit intervals are not profiled
    tr.event("tick")
    assert len(opened) == 4


def test_tracer_spans_land_on_the_profilers_host_plane(tmp_path):
    from jax.profiler import ProfileData

    tr = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    with tr.span("obs.probe"):
        with tr.span("obs.probe.child"):
            jnp.ones(8).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
               for f in fs if f.endswith(".xplane.pb")]
    events = {ev.name: (ev.start_ns, ev.duration_ns)
              for plane in ProfileData.from_file(path).planes
              if plane.name == "/host:CPU"
              for line in plane.lines for ev in line.events}
    assert {"obs.probe", "obs.probe.child"} <= set(events)
    (p0, pd), (c0, cd) = events["obs.probe"], events["obs.probe.child"]
    assert p0 <= c0 and c0 + cd <= p0 + pd  # nested on the profiler clock


SESSION_CHILDREN = ["session.pad", "session.dispatch", "session.wait",
                    "session.fetch", "session.record"]


def test_execute_emits_one_span_tree_per_call(corpus, grown):
    vecs_np, _, _ = corpus
    s = SearchSession(grown, k=5, layout="point_major", probes=2,
                      buckets=(32, 96))
    s.warmup()
    tracer = Tracer()
    with tracing(tracer):
        s.search(vecs_np[:20], n_images=2)
        s.search(vecs_np[20:90], n_images=7)
    execs = [sp for sp in tracer.spans if sp.name == "engine.execute"]
    assert [sp.attrs["bucket"] for sp in execs] == [32, 96]
    assert [sp.attrs["rows"] for sp in execs] == [20, 70]
    for ex in execs:
        assert ex.parent_id is None
        kids = [sp for sp in tracer.spans if sp.parent_id == ex.span_id]
        assert [k.name for k in kids] == SESSION_CHILDREN
        for a, b in zip(kids, kids[1:]):
            assert a.t1 <= b.t0  # in order, one after the other
        assert ex.t0 <= kids[0].t0 and kids[-1].t1 <= ex.t1
    assert len(tracer.spans) == 2 * (1 + len(SESSION_CHILDREN))


def _brute_force_pairs(session, leaves):
    """Same-leaf (index row, lookup row) pairs of one dispatch: every real
    row of each probed leaf, counted by numpy over the index arrays."""
    n_leaves = session.index.n_leaves
    per_leaf = sum(
        np.bincount(lf[(lf >= 0) & (lf < n_leaves)], minlength=n_leaves)
        for lf in (np.asarray(v.leaves) for v in session._segments))
    return int(per_leaf[np.asarray(leaves).reshape(-1)].sum())


@pytest.mark.parametrize("layout,probes", [
    ("point_major", 1), ("point_major", 2), ("query_routed", 1)])
def test_pair_counters_match_brute_force(corpus, grown, layout, probes):
    vecs_np, _, _ = corpus
    s = SearchSession(grown, k=5, layout=layout, probes=probes,
                      buckets=(96,))
    s.warmup()
    # each dispatch's tiles scanned per segment, as the session records them
    seen = []
    record = s._record

    def spy(*args, **kw):
        seen.append(np.ravel(kw["waves"]).astype(int))
        return record(*args, **kw)

    s._record = spy
    rng = np.random.default_rng(5)
    useful = 0
    for n in (13, 96):
        q = vecs_np[rng.choice(N, n, replace=False)] + 0.25
        _, _, leaves, _ = s._execute(q.astype(np.float32))
        useful += _brute_force_pairs(s, leaves)
    m = s.metrics
    assert m.q_cap_overflow == 0
    assert m.pairs_useful == useful > 0
    rt = s._runtimes[96]
    assert m.waves_total == 2 * rt.waves_total
    assert m.waves_scanned == sum(int(w.sum()) for w in seen)
    assert 0 < m.waves_scanned <= m.waves_total
    if layout == "point_major":
        # each segment's sweep tiles its rows by block_rows against a q_cap
        # slab; only the tiles a lookup row meets are scanned
        assert rt.waves_total == sum(
            int(v.rows) // p.block_rows for p, v in zip(rt.plans, s._segments))
        for w in seen:
            assert all(0 <= wi <= int(v.rows) // p.block_rows
                       for wi, p, v in zip(w, rt.plans, s._segments))
        assert m.pairs_computed == sum(
            int(wi) * p.block_rows * p.q_cap
            for w in seen for wi, p in zip(w, rt.plans)) > useful
    else:
        # query-routed scans every routed query tile
        assert m.waves_scanned == m.waves_total
        assert m.pairs_computed == 2 * sum(
            w * pw for w, pw in zip(seen[0], rt.pairs_per_wave)) > useful
    # the process-wide registry carries the same totals
    counters = obs.get_registry().snapshot()["metrics"]
    assert counters["engine.pairs_useful"] == m.pairs_useful
    assert counters["engine.pairs_computed"] == m.pairs_computed
    assert counters["engine.waves_scanned"] == m.waves_scanned
    assert counters["engine.waves_total"] == m.waves_total
    d = m.to_dict()
    assert list(d)[-4:] == ["pairs_useful", "pairs_computed",
                            "waves_scanned", "waves_total"]
    assert d["pairs_useful"] == useful
    assert d["waves_scanned"] == m.waves_scanned
    assert d["waves_total"] == m.waves_total


def test_sampling_is_deterministic_given_seed():
    rids = range(400)
    a = Tracer(sample=0.35, seed=7)
    b = Tracer(sample=0.35, seed=7)
    da = [a.sampled(r) for r in rids]
    db = [b.sampled(r) for r in rids]
    assert da == db  # same seed -> same traced subset, always
    assert a.dropped == b.dropped == da.count(False)
    assert 0.15 < sum(da) / len(da) < 0.55  # roughly the asked-for rate
    c = Tracer(sample=0.35, seed=8)
    assert [c.sampled(r) for r in rids] != da  # seed changes the subset
    full = Tracer(sample=1.0)
    assert all(full.sampled(r) for r in rids) and full.dropped == 0


# ---------------------------------------------------------------------------
# span-tree fan-in under coalesced batches
# ---------------------------------------------------------------------------


def test_fan_in_integrity_under_coalesced_batches(traced2):
    tracer, done = traced2
    spans = tracer.spans
    dispatches = [s for s in spans if s.name == "engine.dispatch"]
    requests = [s for s in spans if s.name == "request"]
    engine_reqs = [s for s in requests if s.attrs.get("source") == "engine"]
    assert dispatches and engine_reqs
    # rate=2000 forces coalescing: at least one dispatch serves >1 request
    assert max(len(d.attrs["rids"]) for d in dispatches) > 1
    by_dispatch = {}
    for r in engine_reqs:
        by_dispatch.setdefault(r.attrs["dispatch_id"], []).append(r)
    # every engine-served request fans into exactly one dispatch span,
    # and each dispatch's fan-in is exactly its recorded rid set
    assert sum(len(v) for v in by_dispatch.values()) == len(engine_reqs)
    for d in dispatches:
        fan_in = by_dispatch.get(d.span_id, [])
        assert {r.trace_id for r in fan_in} == set(d.attrs["rids"])
    # each request span owns exactly one queue.wait and one compute child
    for r in requests:
        kids = [s for s in spans if s.parent_id == r.span_id]
        names = sorted(k.name for k in kids if k.name != "cache.lookup")
        assert names == ["compute", "queue.wait"]
        for k in kids:
            assert k.trace_id == r.trace_id
            assert r.t0 <= k.t0 and k.t1 <= r.t1 + 1e-9
    # every completion produced a request span (sample=1.0: none dropped)
    assert {s.trace_id for s in requests} == {c.rid for c in done}
    # scatter legs cover both shards; the merge closes each dispatch
    shard_lanes = {s.attrs["shard"] for s in spans if s.name == "shard.scan"}
    assert shard_lanes == {0, 1}
    assert any(s.name == "gather.merge" for s in spans)


# ---------------------------------------------------------------------------
# the hard invariant: tracing never perturbs results (shards 1-3)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shards", [1, 2, 3])
def test_bit_identity_traced_vs_untraced(corpus, grown, shards):
    base, _ = _replay(corpus, grown, shards=shards, tracer=None)
    tracer = Tracer(sample=1.0, seed=0)
    traced, _ = _replay(corpus, grown, shards=shards, tracer=tracer)
    assert len(tracer) > 0  # the traced leg really recorded
    ref = {c.rid: c for c in base}
    assert set(ref) == {c.rid for c in traced}
    for c in traced:
        r = ref[c.rid]
        np.testing.assert_array_equal(np.asarray(c.ids), np.asarray(r.ids))
        np.testing.assert_array_equal(np.asarray(c.dists),
                                      np.asarray(r.dists))


# ---------------------------------------------------------------------------
# exporters: Chrome trace_event + JSONL round-trips
# ---------------------------------------------------------------------------


def test_chrome_export_roundtrip(traced2, tmp_path):
    tracer, _ = traced2
    path = write_chrome_trace(tracer, str(tmp_path / "trace.json"))
    with open(path) as f:
        doc = json.load(f)  # valid JSON or this raises
    assert doc["displayTimeUnit"] == "ms"
    assert doc["otherData"]["enabled"] is True
    events = doc["traceEvents"]
    meta = [e for e in events if e["ph"] == "M"]
    body = [e for e in events if e["ph"] != "M"]
    assert len(body) == len(tracer.spans)
    # monotone timestamps (the sort contract Perfetto relies on)
    ts = [e["ts"] for e in body]
    assert ts == sorted(ts)
    # pid/tid placement: one process lane per shard, requests keyed by rid
    names = {e["pid"]: e["args"]["name"] for e in meta}
    assert names[PID_SHARD_BASE] == "shard 0"
    assert names[PID_SHARD_BASE + 1] == "shard 1"
    assert names[PID_REQUESTS] == "requests" and names[PID_ENGINE] == "engine"
    for e in body:
        assert e["ph"] in ("X", "i")
        if e["name"] == "shard.scan":
            assert e["pid"] == PID_SHARD_BASE + e["args"]["shard"]
        elif e["name"] in ("engine.dispatch", "engine.execute",
                           "gather.merge"):
            assert e["pid"] == PID_ENGINE
        elif e["name"] == "request":
            assert e["pid"] == PID_REQUESTS
            assert e["tid"] == e["args"]["trace_id"]
        if e["ph"] == "X":
            assert e["dur"] >= 0.0


def test_jsonl_export_roundtrip(traced2, tmp_path):
    tracer, _ = traced2
    path = write_jsonl(tracer, str(tmp_path / "trace.jsonl"))
    with open(path) as f:
        lines = [json.loads(ln) for ln in f]
    assert lines[0] == {"header": tracer.describe()}
    assert len(lines) - 1 == len(tracer.spans)
    for rec, span in zip(lines[1:], tracer.spans):
        assert rec["name"] == span.name
        assert rec["dur_ms"] == pytest.approx(span.dur_ms)


def test_summary_and_tracereport_read_both_formats(traced2, tmp_path):
    tracer, _ = traced2
    text = summary(tracer, top=3)
    assert "slowest requests" in text and "shard.scan" in text
    # scripts/tracereport.py is stdlib-only; load it straight off disk
    script = os.path.join(os.path.dirname(__file__), os.pardir, "scripts",
                          "tracereport.py")
    spec = importlib.util.spec_from_file_location("tracereport", script)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    chrome = write_chrome_trace(tracer, str(tmp_path / "t.json"))
    jsonl = write_jsonl(tracer, str(tmp_path / "t.jsonl"))
    for path in (chrome, jsonl):
        report = mod.report(mod._load_spans(path), top=3)
        assert "slowest requests" in report
        assert "shard 0" in report and "shard 1" in report


# ---------------------------------------------------------------------------
# MetricsRegistry
# ---------------------------------------------------------------------------


def test_registry_instruments_get_or_create_and_labels():
    reg = MetricsRegistry()
    c = reg.counter("serving.requests")
    c.inc()
    assert reg.counter("serving.requests") is c  # get-or-create identity
    reg.counter("serving.class.completed", cls="interactive").inc(2)
    reg.counter("serving.class.completed", cls="batch").inc()
    reg.gauge("index.version").set(3)
    h = reg.histogram("latency.ms")
    for v in (0.5, 3.0, 3.0, 1e6):
        h.observe(v)
    snap = reg.snapshot()["metrics"]
    assert snap["serving.requests"] == 1
    assert snap["serving.class.completed{cls=interactive}"] == 2
    assert snap["serving.class.completed{cls=batch}"] == 1
    assert snap["index.version"] == 3
    assert snap["latency.ms"]["count"] == 4
    assert snap["latency.ms"]["counts"][0] == 1  # <= 1ms bucket
    assert snap["latency.ms"]["counts"][-1] == 1  # overflow bucket
    assert snap["latency.ms"]["max"] == 1e6
    assert len(reg) == 5
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("serving.requests")
    # float counters export as float, integral ones as int
    reg.counter("engine.ms").inc(1.5)
    snap = reg.snapshot()["metrics"]
    assert snap["engine.ms"] == 1.5 and isinstance(snap["engine.ms"], float)
    assert isinstance(snap["serving.requests"], int)


def test_registry_sources_are_weak(tmp_path):
    class Box:
        def series(self):
            return {"box.value": 42}

    reg = MetricsRegistry()
    box = Box()
    reg.register_source("box", box, Box.series)
    assert reg.snapshot()["sources"] == {"box": {"box.value": 42}}
    path = reg.dump(str(tmp_path / "metrics.json"))
    with open(path) as f:
        assert json.load(f)["sources"]["box"]["box.value"] == 42
    del box
    gc.collect()
    assert reg.snapshot()["sources"] == {}  # dead owner pruned, not stale
    reg.register_source("box2", self_ := Box(), Box.series)
    reg.unregister_source("box2")
    assert reg.snapshot()["sources"] == {}
    assert self_ is not None


def test_serving_and_cache_register_in_process_registry():
    from repro.serving.cache import HotLeafCache

    reg = obs.get_registry()  # fresh per test (conftest isolation)
    m = ServingMetrics()
    m.requests = 5
    cache = HotLeafCache(8, admit_after=1)
    sources = reg.snapshot()["sources"]
    mine = [s for n, s in sources.items() if n.startswith("serving_metrics@")]
    assert any(s["serving.requests"] == 5 for s in mine)
    cs = [s for n, s in sources.items() if n.startswith("hot_leaf_cache@")]
    assert any(s["cache.hits"] == 0 for s in cs)
    del m, cache
    gc.collect()
    sources = reg.snapshot()["sources"]
    assert not any(n.startswith("serving_metrics@") for n in sources)
    assert not any(n.startswith("hot_leaf_cache@") for n in sources)


# ---------------------------------------------------------------------------
# LatencyStats: exact default, bounded reservoir mode
# ---------------------------------------------------------------------------


def test_latency_stats_exact_default_unchanged():
    ls = LatencyStats()
    for v in range(1, 101):
        ls.add(float(v))
    assert len(ls) == 100
    assert ls.percentile(50) == pytest.approx(50.5)
    s = ls.summary()
    assert s["count"] == 100
    assert s["mean_ms"] == pytest.approx(50.5)
    assert s["max_ms"] == 100.0
    assert LatencyStats().summary() == {"count": 0}
    h = ls.histogram()
    assert h["bounds_ms"] == list(HIST_BOUNDS_MS)
    assert sum(h["counts"]) == 100
    assert h["counts"][0] == 1  # only 1.0 <= 1ms


def test_latency_stats_reservoir_bounds_memory_exactly():
    with pytest.raises(ValueError, match="must be >= 1"):
        LatencyStats(0)
    exact = LatencyStats()
    bounded = LatencyStats(32, seed=0)
    vals = np.random.default_rng(5).uniform(0.1, 400.0, size=1000)
    for v in vals:
        exact.add(float(v))
        bounded.add(float(v))
    # count / mean / max / histogram stay exact; retention is bounded
    assert len(bounded) == 1000 and len(bounded._ms) == 32
    assert bounded.summary()["count"] == 1000
    assert bounded.summary()["mean_ms"] == pytest.approx(
        exact.summary()["mean_ms"]
    )
    assert bounded.summary()["max_ms"] == exact.summary()["max_ms"]
    assert bounded.histogram() == exact.histogram()
    # percentiles are estimates from retained samples, inside the range
    assert vals.min() <= bounded.percentile(50) <= vals.max()
    # deterministic: same seed + same sequence -> same reservoir
    again = LatencyStats(32, seed=0)
    for v in vals:
        again.add(float(v))
    assert again._ms == bounded._ms


def test_serving_metrics_bounded_mode_and_to_dict_shape():
    m = ServingMetrics(max_samples=16)
    for i in range(200):
        m.observe_latency("interactive" if i % 3 else "batch",
                          wait_ms=float(i % 7), compute_ms=1.0,
                          deadline_ms=50.0)
        m.observe_queue_depth(i % 11)
    m.requests = 200
    d = m.to_dict()
    assert d["latency"]["count"] == 200  # exact despite the bound
    assert len(m.queue_depth) == 16
    assert m.queue_summary()["count"] == 200
    # the historical to_dict surface is unchanged (byte-compat contract)
    assert list(d) == list(ServingMetrics().to_dict())
    assert list(d["per_class"]["batch"]) == [
        "completed", "shed", "rejected", "attained", "slo_attainment",
        "deadline_ms", "latency", "wait", "compute",
    ]
    # the additive registry view carries the same numbers, labeled
    series = m.registry_series()
    assert series["serving.requests"] == 200
    assert series["serving.class.completed{class=batch}"] == \
        d["per_class"]["batch"]["completed"]
    assert sum(series["serving.latency.hist"]["counts"]) == 200
    m.observe_drop("batch", "shed")
    assert m.registry_series()["serving.class.shed{class=batch}"] == 1
    with pytest.raises(ValueError, match="unknown drop kind"):
        m.observe_drop("batch", "nope")
