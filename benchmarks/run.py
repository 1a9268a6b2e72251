# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness: one module per paper table/figure (see DESIGN.md §8).

  workflow_steps  — Table 2 (workflow step times)
  indexing        — Tables 3/4 + Fig 1 (default vs tuned indexing)
  map_waves       — Table 5 + Figs 2/3 (wave stats, failures, balance)
  block_size      — Table 7 + Figs 6/8 (block-size study)
  scalability     — Fig 5 + Table 6 (shard scaling, modelled 10->100)
  quality         — Fig 4 (Copydays recall vs distractors)
  throughput      — Exp #5 (ms/image vs batch size)
  ann_retrieval   — beyond-paper: tree-ANN on the two-tower arch
  serving         — beyond-paper: online serving (latency percentiles,
                    micro-batching, hot-leaf cache) + plan observations JSON

Usage: PYTHONPATH=src python -m benchmarks.run [module ...]
"""

from __future__ import annotations

import sys
import time
import traceback

MODULES = [
    "workflow_steps",
    "indexing",
    "map_waves",
    "block_size",
    "scalability",
    "quality",
    "throughput",
    "ann_retrieval",
    "serving",
]


def smoke() -> int:
    """Tiny end-to-end serve runs on both layouts with multi-probe, the
    serving-session gate (2 warmed buckets, ~100 zipf requests, zero
    steady-state recompiles), the index-lifecycle gate (create →
    append ×2 → search → compact → search, identical results), the
    cost-model calibration round-trip gate, the sharded bit-identity
    gate, the SLO scheduling gate (fifo == edf results, EDF interactive
    p95 < batch p95), the compressed-codes gate (train → commit →
    reopen → auto plans scan_codes → ADC + rerank recall floor at ≥8x
    fewer resident bytes), the fused-kernel gate (fused == xla on a
    served trace, zero recompiles, ms/image within 1.5x), and the
    dynamicity gate (serve while a writer appends and compacts) — the
    per-PR gate wired into scripts/smoke.sh. Fails loudly, returns rc."""
    from benchmarks import indexing as indexing_bench
    from benchmarks import serving as serving_bench
    from repro.launch import serve

    base = [
        "--rows", "20000", "--dim", "32", "--images", "400",
        "--fanout", "16", "16", "--batches", "1", "--batch-images", "32",
        "--probes", "2",
    ]
    for layout in ("point_major", "query_routed"):
        print(f"# smoke: serve --layout {layout} --probes 2", file=sys.stderr)
        rc = serve.main(base + ["--layout", layout])
        if rc != 0:
            return rc
    print("# smoke: index lifecycle (append x2 / compact exactness)",
          file=sys.stderr)
    rc = indexing_bench.lifecycle_smoke()
    if rc != 0:
        return rc
    print("# smoke: serving session (2 buckets, zipf trace)", file=sys.stderr)
    rc = serving_bench.smoke()
    if rc != 0:
        return rc
    print("# smoke: calibration round-trip (record -> commit -> reopen -> "
          "fitted plan)", file=sys.stderr)
    rc = serving_bench.calibration_smoke()
    if rc != 0:
        return rc
    print("# smoke: sharded scatter-gather (bit-identity at shards 1/2/3)",
          file=sys.stderr)
    rc = serving_bench.sharded_smoke()
    if rc != 0:
        return rc
    print("# smoke: SLO scheduling (fifo == edf results, EDF interactive "
          "p95 < batch p95)", file=sys.stderr)
    rc = serving_bench.slo_smoke()
    if rc != 0:
        return rc
    print("# smoke: compressed codes (train -> commit -> reopen -> auto "
          "plans scan_codes -> ADC + rerank recall floor)", file=sys.stderr)
    rc = serving_bench.codes_smoke()
    if rc != 0:
        return rc
    print("# smoke: fused kernel (fused == xla on a served trace, "
          "0 recompiles, ms/image within 1.5x)", file=sys.stderr)
    rc = serving_bench.kernel_smoke()
    if rc != 0:
        return rc
    print("# smoke: dynamicity (serve while a writer appends + "
          "incrementally compacts: 0 drops, 0 recompiles, bounded p95, "
          "final == fresh open)", file=sys.stderr)
    return serving_bench.dynamicity_smoke()


def main() -> None:
    import importlib

    if "--smoke" in sys.argv[1:]:
        raise SystemExit(smoke())
    names = sys.argv[1:] or MODULES
    print("name,us_per_call,derived")
    failed = []
    for name in names:
        mod = importlib.import_module(f"benchmarks.{name}")
        t0 = time.perf_counter()
        try:
            rows = mod.run()
        except Exception as e:  # noqa: BLE001 - report, run the rest, fail
            traceback.print_exc()
            print(f"{name}_FAILED,0,{e!r}")
            failed.append(name)
            continue
        for r in rows:
            print(r)
        print(f"# {name} done in {time.perf_counter() - t0:.1f}s",
              file=sys.stderr)
    if failed:
        raise SystemExit(f"benchmark modules failed: {', '.join(failed)}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
