"""Device-phase names: the ``jax.named_scope`` vocabulary of the search
program.

Every executor (point-major, query-routed, codes, and their fused
variants) wraps each phase of its traced program in one of these scopes.
They are compile-time metadata only: the optimized HLO carries them in
each instruction's ``op_name`` (``.../repro.scan.select/top_k``), so a
profiler trace can be read as time per phase instead of per
recompile-dependent op number (docs/observability.md). They change no op,
no fusion and no result.
"""

from __future__ import annotations

LOOKUP = "repro.lookup"  # probe routing, leaf sort, lookup padding
SLICE = "repro.scan.slice"  # a wave's slices of points, query slab, carry
DISTANCE = "repro.scan.distance"  # norms, contraction, leaf mask; kernels
SELECT = "repro.scan.select"  # top-k, id gather, fold, probe-group merge
CARRY = "repro.scan.carry"  # writing the running best-k table
COUNT = "repro.scan.count"  # pair and slab-overflow accounting
MERGE = "repro.merge"  # cross-shard and cross-segment merges

PHASES = (LOOKUP, SLICE, DISTANCE, SELECT, CARRY, COUNT, MERGE)
