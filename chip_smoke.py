"""Prove that the index-build and serve path runs on a TPU.

    python chip_smoke.py             # one chip: build, serve, kernels
    python chip_smoke.py --chips 4   # four chips: sharded scatter-gather

One chip, at a size a deployment holds on one v5e: SIFT-shaped
descriptors (d=128, 14,000 images x 300 rows = 4.2M rows, 2 GiB f32)
generated from ``--seed``, the paper's 256 x 256 tree (65,536 leaves),
k=20. Phases, all through the library's normal API:

  1. device   exit non-zero unless JAX's first device is a TPU;
  2. build    tree on a sample, then ``Index.create`` -> ``append`` ->
              ``commit`` -> ``Index.open``; prints build rows/s;
  3. serve    a Zipf trace through ``SearchSession`` + ``MicroBatcher``
              (impl "xla", default layout), zero steady-state recompiles,
              a sample of requests checked against a float64 numpy
              brute-force k-NN within each query's leaf;
  4. kernels  the sample again through impl "pallas" (l2topk), impl
              "fused" (fusedscan) and the codes tier (adcscan, fusedadc);
              each compiled program must hold a Pallas TPU kernel, and
              match its reference leg: exact ids, distances within 2e-4
              relative (docs/kernels.md);
  5. verdict  exit non-zero if any check failed.

``--chips 4`` runs only the sharded path: a data=4 index build, a 4-shard
``ShardedSearchSession`` whose shards sit one per chip, and its results
against a one-chip unsharded session over the same index.

The last line on stdout is the JSON verdict
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, ".chip_smoke")  # git-ignored; index files

N_IMAGES = 14_000
DESC_PER_IMAGE = 300
DIM = 128
FANOUTS = (256, 256)
K = 20
TREE_SAMPLE = 1 << 18  # tree rows: ~1,000 per top-level node
N_REQUESTS = 300
CHECK_REQUESTS = 12  # 3,600 query rows: one 4096-row dispatch per leg
BUCKET = 4096
KERNEL_RTOL = 2e-4  # docs/kernels.md dense contract


def oracle_tolerance(q_norm, p_norm):
    """Largest f32 error of ``||p||^2 - 2 p.q + ||q||^2`` at d=DIM.

    Each of the DIM products and partial sums rounds once (2^-24), so the
    expansion is off by at most (DIM + 8) * 2^-24 * (||p|| + ||q||)^2 (the
    8 covers the norm additions). About 14 at SIFT norms: a bf16-pass
    product (2^-9 per operand, the TPU's default matmul precision) misses
    by ~1000 and fails it.
    """
    return (DIM + 8) * 2.0**-24 * (q_norm + p_norm) ** 2


class Checks:
    """Failed checks are printed as they happen and fail the run at the
    end, so one chip run reports every phase."""

    def __init__(self):
        self.failed = []

    def __call__(self, ok: bool, what: str) -> None:
        print(f"{'ok  ' if ok else 'FAIL'} {what}", flush=True)
        if not ok:
            self.failed.append(what)


def _peak_hbm(devices) -> str:
    """Peak device memory per device (``n/a`` where not reported)."""
    stats = [(d.id, d.memory_stats() or {}) for d in devices]
    return ", ".join(
        f"{i}: {s['peak_bytes_in_use'] / 2**30:.3f} GiB"
        if "peak_bytes_in_use" in s else f"{i}: n/a"
        for i, s in stats
    )


def _compiled_text(session) -> str:
    """Optimized HLO of the session's largest warmed rung, lowered with
    the arguments its dispatch passes."""
    import jax.numpy as jnp

    rt = session._runtimes[session.buckets[-1]]
    buf = jnp.zeros((rt.bucket, session.index.dim), jnp.float32)
    if rt.rerank is not None:
        args = (session._segments, session._codes_dev,
                session._codebooks_dev, session.tree, buf, np.int32(0))
    else:
        args = (session._segments, session.tree, buf, np.int32(0))
    return rt.fn.lower(*args).compile().as_text()


def _corpus(seed: int):
    from repro.data import synth

    t0 = time.perf_counter()
    vecs, _ = synth.sample_images(N_IMAGES, DESC_PER_IMAGE, DIM, seed=seed)
    print(f"data: {vecs.shape[0]} x {DIM} f32 rows "
          f"({vecs.nbytes / 2**30:.2f} GiB) made in "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    return vecs


def _tree(vecs, seed: int):
    import jax
    import jax.numpy as jnp

    from repro.core.tree import build_tree

    rng = np.random.default_rng(seed)
    sample = vecs[np.sort(rng.choice(len(vecs), TREE_SAMPLE, replace=False))]
    t0 = time.perf_counter()
    tree = build_tree(jnp.asarray(sample), FANOUTS,
                      key=jax.random.PRNGKey(seed))
    jax.block_until_ready(tree.levels)
    print(f"tree: {tree.n_leaves} leaves from {TREE_SAMPLE} sample rows in "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    return tree


def _build(tree, vecs, mesh, directory: str, n_segments: int):
    """create -> append (``n_segments`` chunks) -> commit -> open."""
    from repro.index import Index

    t0 = time.perf_counter()
    idx = Index.create(tree, directory, mesh=mesh, overwrite=True)
    for chunk in np.array_split(vecs, n_segments):
        idx.append(chunk)
    idx.commit()
    dt = time.perf_counter() - t0
    print(f"build: {len(vecs)} rows in {n_segments} segment(s) in "
          f"{dt:.1f}s = {len(vecs) / dt:.0f} rows/s "
          "(append + durable commit)", flush=True)
    t0 = time.perf_counter()
    idx = Index.open(directory, mesh=mesh)
    print(f"open: v{idx.version}, {idx.rows} rows, {idx.n_segments} "
          f"segment(s) in {time.perf_counter() - t0:.1f}s", flush=True)
    return idx


def _leaf_rows(tree, vecs):
    """Every corpus row's leaf by the library's tree descent, grouped:
    ``(row_leaf, rows sorted by leaf, leaf start offsets)``."""
    import jax
    import jax.numpy as jnp

    from repro.core.tree import tree_assign

    assign = jax.jit(tree_assign)
    step = 1 << 13  # tree_assign holds (step, 256, d) f32: 1 GiB
    row_leaf = np.concatenate([
        np.asarray(assign(tree, jnp.asarray(vecs[s:s + step])))
        for s in range(0, len(vecs), step)
    ])
    order = np.argsort(row_leaf, kind="stable")
    starts = np.searchsorted(row_leaf[order], np.arange(tree.n_leaves + 1))
    return row_leaf, order, starts


def _oracle_check(check, vecs, queries, q_leaves, ids, dists, leaf_rows):
    """Engine k-NN vs a float64 brute force within each query's leaf.

    Ids must equal the oracle's, except that a rank may hold another row
    whose exact distance ties the oracle's within
    :func:`oracle_tolerance`; every reported distance must be within it
    of the exact distance of the row it names.
    """
    row_leaf, order, starts = leaf_rows
    swaps = bad_d = bad_id = 0
    worst = 0.0
    for i, q in enumerate(queries.astype(np.float64)):
        leaf = q_leaves[i]
        cand = order[starts[leaf]:starts[leaf + 1]]
        d = ((vecs[cand].astype(np.float64) - q) ** 2).sum(1)
        o = np.lexsort((cand, d))[:K]
        n = len(o)
        got = ids[i]
        if (got[:n] < 0).any() or (got[n:] >= 0).any() or \
                len(set(got[:n].tolist())) != n or \
                (row_leaf[got[:n]] != leaf).any():
            bad_id += 1
            continue
        exact = ((vecs[got[:n]].astype(np.float64) - q) ** 2).sum(1)
        tol = oracle_tolerance(np.sqrt((q * q).sum()),
                               np.sqrt((vecs[got[:n]].astype(np.float64)
                                        ** 2).sum(1)))
        err = np.abs(dists[i, :n] - exact)
        worst = max(worst, float((err / tol).max(initial=0.0)))
        bad_d += int((err > tol).any())
        bad_id += int((np.abs(exact - d[o]) > tol).any())
        swaps += int((got[:n] != cand[o]).sum())
    n_rows = len(queries)
    check(bad_id == 0, f"oracle ids: {n_rows - bad_id}/{n_rows} rows match "
          f"the float64 brute force ({swaps} tie swaps within tolerance)")
    check(bad_d == 0, f"oracle distances: {n_rows - bad_d}/{n_rows} rows "
          f"within tolerance (worst error {worst:.3f} of its bound)")


def _tolerances(vecs, queries, ids):
    """:func:`oracle_tolerance` for every (query row, returned id)."""
    qn = np.sqrt((queries.astype(np.float64) ** 2).sum(1))[:, None]
    pn = np.sqrt((vecs.astype(np.float64) ** 2).sum(1))
    return oracle_tolerance(qn, pn[np.clip(ids, 0, None)])


def _compare(check, name, ids, dists, want_ids, want_dists, tol):
    """The docs/kernels.md contract against the reference leg: exact
    ids, distances within 2e-4 relative. Where ``||p||^2 - 2 p.q +
    ||q||^2`` cancels (near-duplicates) two f32 summation orders differ
    by up to ``tol`` whatever the relative bound, so that is added."""
    same = (ids == want_ids).all(axis=1)
    check(bool(same.all()), f"{name} ids == reference: "
          f"{int(same.sum())}/{len(same)} rows exact")
    fin = np.isfinite(want_dists)
    err = np.abs(dists[fin] - want_dists[fin])
    bound = (KERNEL_RTOL * np.abs(want_dists) + tol)[fin]
    ok = (np.isfinite(dists) == np.isfinite(want_dists)).all() and \
        (err <= bound).all()
    rel = err / np.maximum(np.abs(want_dists[fin]), 1.0)
    check(bool(ok), f"{name} distances within {KERNEL_RTOL} relative + "
          f"f32 bound (max relative difference {rel.max(initial=0.0):.2e},"
          f" worst {(err / bound).max(initial=0.0):.3f} of the bound)")


def _kernel_leg(check, name, idx, queries, **kw):
    """One warmed 4096-row rung at ``kw``; its compiled program must hold
    a Pallas TPU kernel. Returns its ``(ids, dists)`` on ``queries``."""
    from repro.serving import SearchSession

    t0 = time.perf_counter()
    s = SearchSession(idx, k=K, buckets=(BUCKET,), **kw)
    warm = s.warmup()
    ids, dists = s.search(queries)
    check("tpu_custom_call" in _compiled_text(s),
          f"{name}: compiled program holds a Pallas TPU kernel")
    check(s.steady_state_recompiles() == 0, f"{name}: 0 recompiles")
    print(f"{name}: layout {s.serving_layout}, compile {warm / 1e3:.1f}s, "
          f"leg {time.perf_counter() - t0:.1f}s", flush=True)
    return ids, dists


def one_chip(check, seed: int) -> None:
    import jax

    from repro.core.tree import tree_assign
    from repro.distributed.meshutil import local_mesh
    from repro.serving import MicroBatcher, SearchSession, TraceLoadGenerator

    # -- 2. build -------------------------------------------------------------
    vecs = _corpus(seed)
    tree = _tree(vecs, seed)
    idx = _build(tree, vecs, local_mesh(), os.path.join(OUT_DIR, "index"), 1)
    print(f"peak HBM after build: {_peak_hbm(jax.devices())}", flush=True)

    # -- 3. serve -------------------------------------------------------------
    gen = TraceLoadGenerator(vecs, DESC_PER_IMAGE, seed=seed + 1)
    reqs = gen.from_trace(N_REQUESTS, N_IMAGES, skew="zipf", rate=200.0)
    session = SearchSession(idx, k=K, impl="xla")
    t0 = time.perf_counter()
    warm = session.warmup()
    done = MicroBatcher(session, max_wait_ms=5.0, max_queue=4096).run(reqs)
    m = session.metrics
    lat = m.latency.summary()
    print(f"serve: buckets {session.buckets}, layouts "
          f"{sorted({p['layout'] for p in session.plan_summary()})}, warmup "
          f"{warm / 1e3:.1f}s, {m.requests} requests in {m.engine_batches} "
          f"batches ({m.rejected} rejected, {m.shed} shed), "
          f"{time.perf_counter() - t0:.1f}s wall; engine "
          f"{m.ms_per_image:.3f} ms/image, p50 {lat['p50_ms']:.1f} ms "
          f"p95 {lat['p95_ms']:.1f} ms (host clock, smoke only)", flush=True)
    served = [c for c in done if c.source == "engine"]
    check(len(served) == len(reqs), f"serve: {len(served)}/{len(reqs)} "
          "requests answered by the engine")
    check(session.steady_state_recompiles() == 0,
          f"serve: {session.steady_state_recompiles()} steady-state "
          "recompiles")
    check(m.q_cap_overflow == 0, f"serve: q_cap_overflow {m.q_cap_overflow}")

    sample = sorted(served, key=lambda c: c.rid)[:CHECK_REQUESTS]
    queries = np.concatenate([gen.query_image(c.image_id) for c in sample])
    want_ids = np.concatenate([c.ids for c in sample])
    want_d = np.concatenate([c.dists for c in sample])
    q_leaves = np.asarray(jax.jit(tree_assign)(tree, queries))
    t0 = time.perf_counter()
    _oracle_check(check, vecs, queries, q_leaves, want_ids, want_d,
                  _leaf_rows(tree, vecs))
    print(f"oracle: {len(queries)} query rows in "
          f"{time.perf_counter() - t0:.1f}s", flush=True)

    # -- 4. kernels -----------------------------------------------------------
    for name, impl in (("l2topk", "pallas"), ("fusedscan", "fused")):
        ids, dists = _kernel_leg(check, name, idx, queries,
                                 layout="point_major", impl=impl)
        _compare(check, name, ids, dists, want_ids, want_d,
                 _tolerances(vecs, queries, want_ids))
    t0 = time.perf_counter()
    idx.enable_codes(seed=seed)
    print(f"codes: {idx.codes_stats()['bytes_per_row']} B/row trained + "
          f"encoded in {time.perf_counter() - t0:.1f}s", flush=True)
    ref = SearchSession(idx, k=K, buckets=(BUCKET,), layout="scan_codes",
                        impl="xla")
    ref.warmup()
    c_ids, c_d = ref.search(queries)
    for name, impl in (("adcscan", "pallas"), ("fusedadc", "fused")):
        ids, dists = _kernel_leg(check, name, idx, queries,
                                 layout="scan_codes", impl=impl)
        _compare(check, name, ids, dists, c_ids, c_d,
                 _tolerances(vecs, queries, c_ids))
    print(f"peak HBM: {_peak_hbm(jax.devices())}", flush=True)


def four_chips(check, seed: int) -> None:
    import jax
    from jax.sharding import Mesh

    from repro.distributed.meshutil import local_mesh
    from repro.index import Index
    from repro.serving import SearchSession, ShardedSearchSession
    from repro.serving import TraceLoadGenerator

    devices = jax.devices()
    vecs = _corpus(seed)
    tree = _tree(vecs, seed)
    mesh = local_mesh()
    check(mesh.shape["data"] == 4, f"build mesh {dict(mesh.shape)}")
    directory = os.path.join(OUT_DIR, "index4")
    idx = _build(tree, vecs, mesh, directory, 4)
    seg = idx.segments[0].index.vecs
    check(len(seg.devices()) == 4,
          f"build: a segment's rows span {len(seg.devices())} chips")

    gen = TraceLoadGenerator(vecs, DESC_PER_IMAGE, seed=seed + 1)
    reqs = gen.from_trace(CHECK_REQUESTS, N_IMAGES, skew="zipf")
    queries = np.concatenate([r.queries for r in reqs])
    sharded = ShardedSearchSession(idx, mesh=mesh, shards=4, k=K,
                                   buckets=(BUCKET,))
    t0 = time.perf_counter()
    warm = sharded.warmup()
    ids, dists = sharded.search(queries)
    print(f"sharded: 4 shards, compile {warm / 1e3:.1f}s, leg "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    homes = [
        {d.id for v in views for d in v.vecs.devices()}
        for views in (
            [v for _, v in shard] for shard in sharded.sharded.shard_views()
        )
    ]
    print(f"sharded: shard devices {homes}", flush=True)
    check(all(len(h) == 1 for h in homes) and
          len(set().union(*homes)) == 4,
          "sharded: each shard's rows on its own chip")
    check(sharded.steady_state_recompiles() == 0, "sharded: 0 recompiles")

    one = Mesh(np.array(devices[:1]).reshape(1, 1), ("data", "model"))
    single = SearchSession(Index.open(directory, mesh=one), k=K,
                           buckets=(BUCKET,))
    single.warmup()
    want_ids, want_d = single.search(queries)
    check(bool((ids == want_ids).all() and (dists == want_d).all()),
          "sharded == one-chip unsharded session (ids and distances "
          "bit-identical)")
    print(f"peak HBM: {_peak_hbm(devices)}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(HERE, "src"))
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    # -- 1. device ------------------------------------------------------------
    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    print(f"device: {platform} {kind} x {len(devices)}", flush=True)
    if platform != "tpu":
        print(f"no TPU: JAX found {platform} devices", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    check = Checks()
    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else one_chip)(check, args.seed)
    shutil.rmtree(OUT_DIR, ignore_errors=True)
    print(f"total {time.perf_counter() - t0:.1f}s", flush=True)
    if check.failed:
        print(f"{len(check.failed)} check(s) failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
