#!/usr/bin/env bash
# Per-PR smoke gate: the tier-1 suite plus a tiny end-to-end serve run on
# BOTH search layouts with multi-probe (--probes 2), so every future PR
# exercises the full engine serve path, not just unit tests.
#
# Usage: scripts/smoke.sh  (from the repo root)
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "== docs gate (cross-links + snippet files/flags) =="
python scripts/check_docs.py

echo "== tier-1 test suite =="
python -m pytest -x -q

# includes the index-lifecycle gate (create -> append x2 -> search ->
# compact -> search, exactness asserted; standalone: benchmarks.indexing
# --smoke), the cost-model calibration round-trip gate (record -> commit ->
# reopen -> plan(model="auto") uses the fit; standalone: benchmarks.serving
# --calibration-smoke), the sharded scatter-gather gate (shards 1/2/3
# bit-identical to unsharded; standalone: benchmarks.serving --sharded-smoke)
# and the SLO scheduling gate (same trace under fifo and edf returns
# bit-identical results, EDF interactive p95 < batch p95; standalone:
# benchmarks.serving --slo-smoke), the compressed-codes gate (train ->
# commit -> reopen -> plan(auto) picks scan_codes -> ADC scan + exact
# rerank meets the recall floor at >=8x fewer resident bytes; standalone:
# benchmarks.serving --codes-smoke), the fused-kernel gate (the same
# served trace through impl="xla" and impl="fused" sessions returns
# bit-identical ids+dists, zero steady-state recompiles, fused ms/image
# within 1.5x of xla; standalone: benchmarks.serving --kernel-smoke),
# the dynamicity gate (serve a trace
# while a writer thread appends + incrementally compacts: 0 dropped
# requests, 0 steady-state recompiles, p95 within 2x of a frozen baseline,
# final results bit-identical to a fresh open; standalone:
# benchmarks.serving --dynamicity-smoke)
echo "== serve smoke (both layouts, --probes 2) + lifecycle + session + calibration + shard + SLO + codes + dynamicity gates =="
python -m benchmarks.run --smoke

echo "== serving CLI smoke (zipf trace, hot-leaf cache, recompile gate) =="
python -m repro.launch.serve --rows 20000 --dim 32 --images 400 \
    --fanout 16 16 --trace zipf --requests 100 --buckets 512,1024 \
    --probes 2 --cache-leaves 256 --cache-admit 1 --rate 300 --no-recall \
    --cost-model auto

echo "== SLO serving CLI smoke (multi-tenant trace, p95 target, EDF) =="
python -m repro.launch.serve --rows 20000 --dim 32 --images 400 \
    --fanout 16 16 --trace multi --requests 120 --target-p95-ms 150 \
    --rate 400 --no-recall

echo "== sharded serving CLI smoke (scatter-gather, 2 shards) =="
python -m repro.launch.serve --rows 20000 --dim 32 --images 400 \
    --fanout 16 16 --trace zipf --requests 100 --buckets 512 \
    --shards 2 --shard-plan balanced --cache-leaves 256 --cache-admit 1 \
    --rate 300 --no-recall

echo "smoke OK"
