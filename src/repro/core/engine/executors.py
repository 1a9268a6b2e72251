"""The two search executors, built on the shared tile-scan core.

Point-major (paper §2.4): every shard sweeps its cluster-sorted index rows
in waves of ``block_rows`` against the replicated lookup table, visiting
only the tiles whose leaf span meets a lookup row
(``tilescan.live_waves``; the others could only fold ``inf``); the slab of
queries colliding with a tile is contiguous (both sides leaf-sorted), and a
running ``(rows, k)`` best table is folded per wave, then merged across
shards with one log-shaped top-k. The codes sweep skips tiles the same
way.

Query-routed (beyond-paper): the lookup rows are shuffled to the shard
owning their leaf (the same capacity-padded counting sort + all_to_all as
index creation), after which every query row is answered entirely locally —
one contiguous point slab per query tile, no running table, no cross-shard
merge.

Multi-probe: ``build_lookup(tree, queries, probes=T)`` expands each query
into ``T`` rows (one per probed leaf) whose ``qids`` are *flat slots*
``query_id * T + probe_rank``. Both executors treat rows independently; the
final ``merge_probe_groups`` folds each query's ``T`` disjoint candidate
rows into one ``k``-row (see tilescan.py for why no id-dedupe is needed).

Fused fast path (``plan.impl="fused"``, docs/kernels.md): the point-major
and codes scans dispatch to fused variants that never materialize a full
distance slab between scan and select. On TPU the whole shard goes
through one ``kernels/fusedscan`` launch with in-kernel k-selection; off
TPU the wave sweep is software-pipelined — the next wave's lookup/LUT
slab is fetched into the loop carry while the current wave scans, so the
gather and the GEMM have no data dependency and can overlap (double
buffering, structured for async device streams on hardware). Both
variants return ids+dists bit-identical to ``impl="xla"``.
"""

from __future__ import annotations

import dataclasses
import math
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import phases
from repro.core import route as route_lib
from repro.core.distance import PRECISION, sq_norms
from repro.core.engine import tilescan
from repro.core.engine.plan import SearchPlan
from repro.core.index_build import DistributedIndex
from repro.core.lookup import LookupTable
from repro.core.sentinels import INVALID_ID, LEAF_SENTINEL, PAD_QUERY_LEAF
from repro.distributed.meshutil import batch_axes, pcast_varying, round_up


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SearchResult:
    ids: jax.Array  # (Q, k) global descriptor ids, -1 where fewer than k
    dists: jax.Array  # (Q, k) true squared L2 distances (inf where id=-1)
    pairs: jax.Array  # () number of (point, query) distance pairs computed
    q_cap_overflow: jax.Array  # () slab-budget misses (0 == exact-in-cluster)
    # () tiles the executor scanned, summed over shards (wave_shape); a
    # session rung's merged result holds one entry per segment
    waves: jax.Array | None = None

    def tree_flatten(self):
        return (self.ids, self.dists, self.pairs, self.q_cap_overflow,
                self.waves), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


class _Carry(NamedTuple):
    best_d: jax.Array
    best_i: jax.Array
    pairs: jax.Array
    overflow: jax.Array


class _PipedCarry(NamedTuple):
    """Wave-loop carry for the pipelined fused executor: in addition to
    the running table it holds the *next* wave's prefetched lookup slab
    (``qv``/``qlf``/``slab_start``), so the slab gather issued at the end
    of wave ``i`` has no data dependency on wave ``i+1``'s scan and the
    two can overlap (double buffering)."""

    best_d: jax.Array
    best_i: jax.Array
    pairs: jax.Array
    overflow: jax.Array
    qv: jax.Array
    qlf: jax.Array
    slab_start: jax.Array


def _fused_wants_kernel() -> bool:
    """Whether ``impl="fused"`` should launch the Pallas fusedscan kernel.

    On TPU the whole-shard kernel is the point; elsewhere interpret-mode
    Pallas is an eval loop, so the fused executor runs the pipelined XLA
    wave sweep instead (bit-identical to ``impl="xla"``). Tests force the
    kernel off-TPU with ``REPRO_FUSED_FORCE_KERNEL=1``.
    """
    if os.environ.get("REPRO_FUSED_FORCE_KERNEL", "") == "1":
        return True
    return jax.default_backend() == "tpu"


@jax.named_scope(phases.COUNT)
def _leaf_pair_count(p_leaves, q_leaves, n_leaves: int):
    """Analytic (point, query) leaf-collision count for the whole-shard
    kernel path: the kernel scans every (tile, tile) cell but only
    leaf-matching pairs survive masking, so the histogram product equals
    the wave sweep's summed ``count_pairs`` whenever q_cap never
    overflowed (and is the honest pair count even when it would have)."""
    p_ok = ((p_leaves >= 0) & (p_leaves != LEAF_SENTINEL)).astype(jnp.float32)
    q_ok = ((q_leaves >= 0) & (q_leaves != LEAF_SENTINEL)).astype(jnp.float32)
    p_cnt = jnp.zeros((n_leaves,), jnp.float32).at[
        jnp.clip(p_leaves, 0, n_leaves - 1)
    ].add(p_ok)
    q_cnt = jnp.zeros((n_leaves,), jnp.float32).at[
        jnp.clip(q_leaves, 0, n_leaves - 1)
    ].add(q_ok)
    return jnp.sum(p_cnt * q_cnt)


@jax.named_scope(phases.LOOKUP)
def pad_lookup(lookup: LookupTable, q_total: int) -> LookupTable:
    """Pad the lookup table to ``q_total`` rows; padding never matches.

    Pad rows get fresh flat slot ids past the real ones so every scatter
    target stays a permutation of ``arange(q_total)``.
    """
    q = lookup.vecs.shape[0]
    if q_total < q:
        raise ValueError(f"{q_total=} < {q}")
    if q_total == q:
        return lookup
    pad = q_total - q
    return LookupTable(
        vecs=jnp.concatenate(
            [lookup.vecs, jnp.zeros((pad, lookup.vecs.shape[1]), lookup.vecs.dtype)]
        ),
        qids=jnp.concatenate([lookup.qids, jnp.arange(q, q_total, dtype=jnp.int32)]),
        leaves=jnp.concatenate(
            [lookup.leaves, jnp.full((pad,), PAD_QUERY_LEAF, jnp.int32)]
        ),
        offsets=lookup.offsets,
    )


def _shard_id(mesh: Mesh, axes) -> jax.Array:
    sid = jnp.int32(0)
    for a in axes:
        sid = sid * mesh.shape[a] + jax.lax.axis_index(a)
    return sid


@jax.named_scope(phases.MERGE)
def _merge_shard_tables(mesh, axes, plan, lookup, best_d, best_i, pairs,
                        overflow, waves, *, q_total, n_shards, width,
                        add_q_norms):
    """Merge per-shard ``(S, Q, width)`` k-NN tables into a SearchResult.

    (S, Q, w) sharded over S -> (Q, S*w) sharded over Q (all_to_all
    reshard), then a purely local per-row top-k. Never replicated: at pod
    scale the stacked table is tens of GB global. ``add_q_norms`` restores
    the deferred ``||q||^2`` term (dense scans only — ADC distances are
    already full squared estimates). Shared by the xla and fused
    executors so the merge is op-for-op identical across impls.
    """
    row_sh = NamedSharding(mesh, P(axes, None))
    all_d = jnp.transpose(best_d, (1, 0, 2)).reshape(q_total, n_shards * width)
    all_i = jnp.transpose(best_i, (1, 0, 2)).reshape(q_total, n_shards * width)
    all_d = jax.lax.with_sharding_constraint(all_d, row_sh)
    all_i = jax.lax.with_sharding_constraint(all_i, row_sh)
    neg, sel = jax.lax.top_k(-all_d, width)
    merged_d = -neg
    if add_q_norms:
        merged_d = merged_d + sq_norms(lookup.vecs)[:, None]
    merged_i = jnp.take_along_axis(all_i, sel, axis=1)
    merged_d = jnp.where(merged_i >= 0, merged_d, jnp.inf)
    # unsort to flat slot order, then merge probe groups
    out_d = jnp.full_like(merged_d, jnp.inf).at[lookup.qids].set(merged_d)
    out_i = jnp.full_like(merged_i, INVALID_ID).at[lookup.qids].set(merged_i)
    out_d, out_i = tilescan.merge_probe_groups(out_d, out_i, plan.probes)
    out_d = jax.lax.with_sharding_constraint(out_d, row_sh)
    out_i = jax.lax.with_sharding_constraint(out_i, row_sh)
    return SearchResult(ids=out_i, dists=out_d, pairs=pairs,
                        q_cap_overflow=overflow, waves=waves)


def _point_major_fn(mesh, plan: SearchPlan, *, n_leaves, shard_rows, q_total,
                    axes):
    block_rows, q_cap, k = plan.block_rows, plan.q_cap, plan.k
    n_shards = math.prod(mesh.shape[a] for a in axes)
    if shard_rows % block_rows != 0:
        raise ValueError(f"{shard_rows=} not divisible by {block_rows=}")
    if k > block_rows:
        raise ValueError(f"{k=} must be <= {block_rows=}")
    if q_cap > q_total:
        raise ValueError(f"{q_cap=} must be <= padded query count {q_total=}")

    def shard_fn(vecs, leaves, ids, lk_vecs, lk_leaves, lk_offsets):
        vecs, leaves, ids = vecs[0], leaves[0], ids[0]
        # only the tiles whose leaf span meets a lookup row can pair
        waves, n_live = tilescan.live_waves(
            leaves, lk_offsets, block_rows=block_rows, n_entries=n_leaves,
        )

        def wave(i, c: _Carry) -> _Carry:
            with jax.named_scope(phases.SLICE):
                start = waves[i] * block_rows
                pv = jax.lax.dynamic_slice(
                    vecs, (start, 0), (block_rows, vecs.shape[1])
                )
                plf = jax.lax.dynamic_slice(leaves, (start,), (block_rows,))
                pid = jax.lax.dynamic_slice(ids, (start,), (block_rows,))
                # contiguous query slab for this tile's leaf span
                slab = tilescan.leaf_slab(
                    lk_offsets, plf[0], n_entries=n_leaves,
                    total_rows=q_total, cap=q_cap,
                )
                qv = jax.lax.dynamic_slice(
                    lk_vecs, (slab.start, 0), (q_cap, lk_vecs.shape[1])
                )
                qlf = jax.lax.dynamic_slice(lk_leaves, (slab.start,), (q_cap,))
            cand_d, cand_i = tilescan.scan_tile(
                pv, plf, pid, qv, qlf, k=k, impl=plan.impl
            )
            # fold into the running per-query k-NN table
            best_d, best_i = tilescan.fold_rows(
                c.best_d, c.best_i, cand_d, cand_i, slab.start
            )
            # bookkeeping: pairs computed + slab-budget misses
            with jax.named_scope(phases.COUNT):
                pairs = c.pairs + tilescan.count_pairs(plf, qlf)
                overflow = c.overflow + tilescan.slab_overflow(
                    lk_offsets, tilescan.last_valid_leaf(plf), slab,
                    n_entries=n_leaves,
                )
            return _Carry(best_d, best_i, pairs, overflow)

        with jax.named_scope(phases.CARRY):
            init = _Carry(
                best_d=jnp.full((q_total, k), jnp.inf, jnp.float32),
                best_i=jnp.full((q_total, k), INVALID_ID, jnp.int32),
                pairs=jnp.zeros((), jnp.float32),
                overflow=jnp.zeros((), jnp.int32),
            )
            # the carry varies across shards (each shard scans its own rows)
            init = jax.tree.map(lambda x: pcast_varying(x, axes), init)
        # a trip count of its own per shard: no collective in the loop
        out = jax.lax.fori_loop(0, n_live, wave, init)
        with jax.named_scope(phases.COUNT):
            pairs = jax.lax.psum(out.pairs, axes)
            overflow = jax.lax.psum(out.overflow, axes)
            scanned = jax.lax.psum(n_live, axes)
        return out.best_d[None], out.best_i[None], pairs, overflow, scanned

    def pipeline(index: DistributedIndex, lookup: LookupTable) -> SearchResult:
        d = index.vecs.shape[-1]
        vecs = index.vecs.reshape(n_shards, shard_rows, d)
        leaves = index.leaves.reshape(n_shards, shard_rows)
        ids = index.ids.reshape(n_shards, shard_rows)
        row_spec = P(axes, None)
        flat_spec = P(axes)
        rep = P()
        best_d, best_i, pairs, overflow, scanned = jax.shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=(row_spec, flat_spec, flat_spec, rep, rep, rep),
            out_specs=(P(axes, None, None), P(axes, None, None), rep, rep,
                       rep),
        )(vecs, leaves, ids, lookup.vecs, lookup.leaves, lookup.offsets)
        return _merge_shard_tables(
            mesh, axes, plan, lookup, best_d, best_i, pairs, overflow,
            scanned, q_total=q_total, n_shards=n_shards, width=k,
            add_q_norms=True,
        )

    return pipeline


def _routed_query_cap(plan: SearchPlan, q_total: int, n_shards: int) -> int:
    """Query rows each shard holds after the query-routed shuffle."""
    return round_up(
        max(plan.q_tile,
            int(q_total / n_shards * plan.query_capacity_factor)),
        plan.q_tile,
    )


def _query_routed_fn(mesh, plan: SearchPlan, *, n_leaves, shard_rows, q_total,
                     axes):
    q_tile, p_cap, k = plan.q_tile, plan.p_cap, plan.k
    n_shards = math.prod(mesh.shape[a] for a in axes)
    if n_leaves % n_shards:
        raise ValueError(f"{n_leaves=} must divide over {n_shards} shards")
    lps = n_leaves // n_shards
    q_cap_shard = _routed_query_cap(plan, q_total, n_shards)
    n_qwaves = q_cap_shard // q_tile

    def shard_fn(vecs, leaves, ids, offsets, lk_vecs, lk_leaves, lk_qids):
        vecs, leaves, ids, offsets = vecs[0], leaves[0], ids[0], offsets[0]
        with jax.named_scope(phases.LOOKUP):
            leaf_base = _shard_id(mesh, axes) * lps
            # ---- shuffle: route query rows to their leaf's owner shard ----
            routed = route_lib.route_by_leaf(
                lk_vecs,
                lk_qids,
                lk_leaves,
                axis_name=axes,
                n_shards=n_shards,
                leaves_per_shard=lps,
                capacity=q_cap_shard // n_shards,
                wire_dtype=plan.wire_dtype,
            )
            qv_all, qids_all, qlf_all, _, _ = route_lib.cluster_sort(
                routed, leaf_base=leaf_base, leaves_per_shard=lps
            )
            # pad/trim the local query set to the static budget
            pad = q_cap_shard - qv_all.shape[0]
            if pad > 0:
                qv_all = jnp.concatenate(
                    [qv_all, jnp.zeros((pad, qv_all.shape[1]), qv_all.dtype)]
                )
                qids_all = jnp.concatenate(
                    [qids_all, jnp.full((pad,), INVALID_ID, jnp.int32)]
                )
                qlf_all = jnp.concatenate(
                    [qlf_all, jnp.full((pad,), LEAF_SENTINEL, jnp.int32)]
                )
            else:
                qv_all = qv_all[:q_cap_shard]
                qids_all = qids_all[:q_cap_shard]
                qlf_all = qlf_all[:q_cap_shard]

        def wave(w):
            with jax.named_scope(phases.SLICE):
                qs = w * q_tile
                qv = jax.lax.dynamic_slice(
                    qv_all, (qs, 0), (q_tile, qv_all.shape[1])
                )
                qlf = jax.lax.dynamic_slice(qlf_all, (qs,), (q_tile,))
                # contiguous local point slab covering this tile's leaf span
                slab = tilescan.leaf_slab(
                    offsets, qlf[0] - leaf_base, n_entries=lps,
                    total_rows=shard_rows, cap=p_cap,
                )
                pv = jax.lax.dynamic_slice(
                    vecs, (slab.start, 0), (p_cap, vecs.shape[1])
                )
                plf = jax.lax.dynamic_slice(leaves, (slab.start,), (p_cap,))
                pid = jax.lax.dynamic_slice(ids, (slab.start,), (p_cap,))
            cand_d, cand_i = tilescan.scan_tile(
                pv, plf, pid, qv, qlf, k=k, impl=plan.impl
            )
            with jax.named_scope(phases.DISTANCE):
                # true squared distance
                cand_d = cand_d + sq_norms(qv)[:, None]
            with jax.named_scope(phases.COUNT):
                ov = tilescan.slab_overflow(
                    offsets, tilescan.last_valid_leaf(qlf, base=leaf_base),
                    slab, n_entries=lps,
                )
                pairs = tilescan.count_pairs(plf, qlf)
            return cand_d, cand_i, ov, pairs

        cand_d, cand_i, ov, pairs = jax.lax.map(wave, jnp.arange(n_qwaves))
        with jax.named_scope(phases.COUNT):
            overflow = jax.lax.psum(jnp.sum(ov), axes) + jax.lax.psum(
                routed.overflow, axes
            )
            pairs = jax.lax.psum(jnp.sum(pairs), axes)
        return (
            cand_d.reshape(1, q_cap_shard, k),
            cand_i.reshape(1, q_cap_shard, k),
            qids_all[None],
            pairs,
            overflow,
        )

    def pipeline(index: DistributedIndex, lookup: LookupTable) -> SearchResult:
        d = index.vecs.shape[-1]
        vecs = index.vecs.reshape(n_shards, shard_rows, d)
        leaves = index.leaves.reshape(n_shards, shard_rows)
        ids = index.ids.reshape(n_shards, shard_rows)
        row_spec = P(axes, None)
        flat_spec = P(axes)
        rep = P()
        cand_d, cand_i, qids, pairs, overflow = jax.shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=(row_spec, flat_spec, flat_spec, row_spec, rep, rep, rep),
            out_specs=(P(axes, None, None), P(axes, None, None), P(axes, None),
                       rep, rep),
        )(vecs, leaves, ids, index.offsets, lookup.vecs, lookup.leaves,
          lookup.qids)
        # one global scatter back to flat slot order (each lookup row was
        # answered by exactly one shard — no cross-shard merge needed),
        # then merge each query's probe rows
        with jax.named_scope(phases.MERGE):
            flat_d = cand_d.reshape(-1, k)
            flat_i = cand_i.reshape(-1, k)
            flat_q = qids.reshape(-1)
            safe_q = jnp.where(flat_q >= 0, flat_q, q_total)
            out_d = jnp.full((q_total, k), jnp.inf, jnp.float32).at[
                safe_q].set(flat_d, mode="drop")
            out_i = jnp.full((q_total, k), INVALID_ID, jnp.int32).at[
                safe_q].set(flat_i, mode="drop")
            out_d, out_i = tilescan.merge_probe_groups(
                out_d, out_i, plan.probes
            )
            row_sh = NamedSharding(mesh, P(axes, None))
            out_d = jax.lax.with_sharding_constraint(out_d, row_sh)
            out_i = jax.lax.with_sharding_constraint(out_i, row_sh)
        return SearchResult(ids=out_i, dists=out_d, pairs=pairs,
                            q_cap_overflow=overflow,
                            waves=jnp.int32(n_qwaves * n_shards))

    return pipeline


@jax.named_scope(phases.DISTANCE)
def _build_adc_lut(lookup_vecs, codebooks, *, q_total: int, m: int,
                   n_centers: int):
    """Per-lookup-row ADC tables, flattened to (Q, m * n_centers):
    ``lut[q, j, c] = ||q_j - codebook[j, c]||^2``."""
    dsub = codebooks.shape[-1]
    sub = lookup_vecs.astype(jnp.float32).reshape(q_total, m, dsub)
    cb = codebooks.astype(jnp.float32)
    cross = jnp.einsum(
        "qmd,mcd->qmc", sub, cb, preferred_element_type=jnp.float32,
        precision=PRECISION,
    )
    return (
        jnp.sum(sub * sub, axis=-1)[:, :, None]
        - 2.0 * cross
        + jnp.sum(cb * cb, axis=-1)[None]
    ).reshape(q_total, m * n_centers)


def _scan_codes_fn(mesh, plan: SearchPlan, *, n_leaves, shard_rows, q_total,
                   axes):
    """Compressed-tier scan (docs/compressed_codes.md): a point-major wave
    sweep over uint8 PQ code slabs under the asymmetric distance. Each
    wave folds the adcscan kernel's candidates into a running
    ``(q_total, rerank)`` table; the emitted ``SearchResult`` carries
    *approximate* ADC distances over ``plan.rerank`` survivors per query —
    callers fetch those rows and rerank exactly
    (:func:`repro.codes.rerank_exact`)."""
    from repro.kernels.adcscan import ops as adc_ops

    block_rows, q_cap = plan.block_rows, plan.q_cap
    r, m = plan.rerank, plan.code_m
    n_centers = 1 << plan.code_bits
    n_shards = math.prod(mesh.shape[a] for a in axes)
    if shard_rows % block_rows != 0:
        raise ValueError(f"{shard_rows=} not divisible by {block_rows=}")
    if r > block_rows:
        raise ValueError(f"rerank {r} must be <= {block_rows=}")
    if q_cap > q_total:
        raise ValueError(f"{q_cap=} must be <= padded query count {q_total=}")
    from repro.core.sentinels import PAD_TILE_POINT_LEAF

    def shard_fn(codes, leaves, ids, lk_lut, lk_leaves, lk_offsets):
        codes, leaves, ids = codes[0], leaves[0], ids[0]
        waves, n_live = tilescan.live_waves(
            leaves, lk_offsets, block_rows=block_rows, n_entries=n_leaves,
        )

        def wave(i, c: _Carry) -> _Carry:
            with jax.named_scope(phases.SLICE):
                start = waves[i] * block_rows
                pc = jax.lax.dynamic_slice(codes, (start, 0), (block_rows, m))
                plf = jax.lax.dynamic_slice(leaves, (start,), (block_rows,))
                pid = jax.lax.dynamic_slice(ids, (start,), (block_rows,))
                slab = tilescan.leaf_slab(
                    lk_offsets, plf[0], n_entries=n_leaves,
                    total_rows=q_total, cap=q_cap,
                )
                lut = jax.lax.dynamic_slice(
                    lk_lut, (slab.start, 0), (q_cap, m * n_centers)
                ).reshape(q_cap, m, n_centers)
                qlf = jax.lax.dynamic_slice(lk_leaves, (slab.start,), (q_cap,))
            with jax.named_scope(phases.DISTANCE):
                # tombstoned rows keep their leaf for slab location but
                # must never match: codes can't carry the 1e15 vec mask the
                # dense scan uses, so mask the *match* leaves by id validity
                plf_m = jnp.where(pid >= 0, plf, PAD_TILE_POINT_LEAF)
                cand_d, cand_sel = adc_ops.adc_topk(
                    pc, plf_m, lut, qlf, k=r, impl=plan.impl
                )
            with jax.named_scope(phases.SELECT):
                cand_i = jnp.where(
                    cand_sel >= 0, pid[jnp.clip(cand_sel, 0)], INVALID_ID
                )
                cand_d = jnp.where(cand_i >= 0, cand_d, jnp.inf)
            best_d, best_i = tilescan.fold_rows(
                c.best_d, c.best_i, cand_d, cand_i, slab.start
            )
            with jax.named_scope(phases.COUNT):
                pairs = c.pairs + tilescan.count_pairs(plf_m, qlf)
                overflow = c.overflow + tilescan.slab_overflow(
                    lk_offsets, tilescan.last_valid_leaf(plf), slab,
                    n_entries=n_leaves,
                )
            return _Carry(best_d, best_i, pairs, overflow)

        with jax.named_scope(phases.CARRY):
            init = _Carry(
                best_d=jnp.full((q_total, r), jnp.inf, jnp.float32),
                best_i=jnp.full((q_total, r), INVALID_ID, jnp.int32),
                pairs=jnp.zeros((), jnp.float32),
                overflow=jnp.zeros((), jnp.int32),
            )
            init = jax.tree.map(lambda x: pcast_varying(x, axes), init)
        out = jax.lax.fori_loop(0, n_live, wave, init)
        with jax.named_scope(phases.COUNT):
            pairs = jax.lax.psum(out.pairs, axes)
            overflow = jax.lax.psum(out.overflow, axes)
            scanned = jax.lax.psum(n_live, axes)
        return out.best_d[None], out.best_i[None], pairs, overflow, scanned

    def pipeline(index: DistributedIndex, lookup: LookupTable,
                 codes: jax.Array, codebooks: jax.Array) -> SearchResult:
        lut = _build_adc_lut(lookup.vecs, codebooks, q_total=q_total, m=m,
                             n_centers=n_centers)
        with jax.named_scope(phases.SLICE):
            codes3 = codes.astype(jnp.int32).reshape(n_shards, shard_rows, m)
        leaves = index.leaves.reshape(n_shards, shard_rows)
        ids = index.ids.reshape(n_shards, shard_rows)
        row_spec = P(axes, None)
        flat_spec = P(axes)
        rep = P()
        best_d, best_i, pairs, overflow, scanned = jax.shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=(row_spec, flat_spec, flat_spec, rep, rep, rep),
            out_specs=(P(axes, None, None), P(axes, None, None), rep, rep,
                       rep),
        )(codes3, leaves, ids, lut, lookup.leaves, lookup.offsets)
        # ADC distances are *full* squared estimates (the LUT carries the
        # ||q_j - c||^2 terms), so unlike the dense scan no ||q||^2 add-back
        return _merge_shard_tables(
            mesh, axes, plan, lookup, best_d, best_i, pairs, overflow,
            scanned, q_total=q_total, n_shards=n_shards, width=r,
            add_q_norms=False,
        )

    return pipeline


def _point_major_fused_fn(mesh, plan: SearchPlan, *, n_leaves, shard_rows,
                          q_total, axes):
    """Fused point-major executor (docs/kernels.md).

    TPU (or forced): the whole shard goes through one
    ``fusedscan.fused_topk`` launch — per-tile top-k kept in VMEM and
    merged across point tiles in-kernel, so no (rows, q) distance slab or
    per-wave candidate list ever lands in HBM between scan and select.

    Off-TPU: a software-pipelined wave sweep with the same per-tile math
    as ``impl="xla"`` — the next wave's query slab is prefetched into the
    loop carry while the current wave scans (double buffering), keeping
    results bit-identical to the reference executor.
    """
    block_rows, q_cap, k = plan.block_rows, plan.q_cap, plan.k
    n_shards = math.prod(mesh.shape[a] for a in axes)
    if shard_rows % block_rows != 0:
        raise ValueError(f"{shard_rows=} not divisible by {block_rows=}")
    if k > block_rows:
        raise ValueError(f"{k=} must be <= {block_rows=}")
    if q_cap > q_total:
        raise ValueError(f"{q_cap=} must be <= padded query count {q_total=}")
    n_waves = shard_rows // block_rows
    use_kernel = _fused_wants_kernel()

    def kernel_shard_fn(vecs, leaves, ids, lk_vecs, lk_leaves, lk_offsets):
        from repro.kernels.fusedscan import ops as fused_ops

        vecs, leaves, ids = vecs[0], leaves[0], ids[0]
        with jax.named_scope(phases.DISTANCE):
            best_d, best_i = fused_ops.fused_topk(
                vecs, leaves, ids, lk_vecs, lk_leaves, k=k, impl="pallas",
            )
        with jax.named_scope(phases.COUNT):
            pairs = jax.lax.psum(
                _leaf_pair_count(leaves, lk_leaves, n_leaves), axes
            )
            # whole-shard scan: every leaf-matching query row is visible to
            # every point tile — the q_cap slab budget cannot be exceeded
            overflow = jax.lax.psum(jnp.zeros((), jnp.int32), axes)
        return best_d[None], best_i[None], pairs, overflow

    def piped_shard_fn(vecs, leaves, ids, lk_vecs, lk_leaves, lk_offsets):
        vecs, leaves, ids = vecs[0], leaves[0], ids[0]

        @jax.named_scope(phases.SLICE)
        def fetch(i):
            first = jax.lax.dynamic_slice(leaves, (i * block_rows,), (1,))[0]
            slab = tilescan.leaf_slab(
                lk_offsets, first, n_entries=n_leaves, total_rows=q_total,
                cap=q_cap,
            )
            qv = jax.lax.dynamic_slice(
                lk_vecs, (slab.start, 0), (q_cap, lk_vecs.shape[1])
            )
            qlf = jax.lax.dynamic_slice(lk_leaves, (slab.start,), (q_cap,))
            return qv, qlf, slab.start

        def wave(i, c: _PipedCarry) -> _PipedCarry:
            with jax.named_scope(phases.SLICE):
                start = i * block_rows
                pv = jax.lax.dynamic_slice(
                    vecs, (start, 0), (block_rows, vecs.shape[1])
                )
                plf = jax.lax.dynamic_slice(leaves, (start,), (block_rows,))
                pid = jax.lax.dynamic_slice(ids, (start,), (block_rows,))
            # scan the slab prefetched by the previous iteration
            cand_d, cand_i = tilescan.scan_tile(
                pv, plf, pid, c.qv, c.qlf, k=k, impl="xla"
            )
            best_d, best_i = tilescan.fold_rows(
                c.best_d, c.best_i, cand_d, cand_i, c.slab_start
            )
            with jax.named_scope(phases.COUNT):
                pairs = c.pairs + tilescan.count_pairs(plf, c.qlf)
                overflow = c.overflow + tilescan.slab_overflow(
                    lk_offsets, tilescan.last_valid_leaf(plf),
                    tilescan.Slab(start=c.slab_start, cap=q_cap),
                    n_entries=n_leaves,
                )
            # prefetch wave i+1's slab (clamped on the last wave)
            qv, qlf, slab_start = fetch(jnp.minimum(i + 1, n_waves - 1))
            return _PipedCarry(best_d, best_i, pairs, overflow, qv, qlf,
                               slab_start)

        with jax.named_scope(phases.CARRY):
            qv0, qlf0, start0 = fetch(0)
            init = _PipedCarry(
                best_d=jnp.full((q_total, k), jnp.inf, jnp.float32),
                best_i=jnp.full((q_total, k), INVALID_ID, jnp.int32),
                pairs=jnp.zeros((), jnp.float32),
                overflow=jnp.zeros((), jnp.int32),
                qv=qv0, qlf=qlf0, slab_start=start0,
            )
            init = jax.tree.map(lambda x: pcast_varying(x, axes), init)
        out = jax.lax.fori_loop(0, n_waves, wave, init)
        with jax.named_scope(phases.COUNT):
            pairs = jax.lax.psum(out.pairs, axes)
            overflow = jax.lax.psum(out.overflow, axes)
        return out.best_d[None], out.best_i[None], pairs, overflow

    shard_fn = kernel_shard_fn if use_kernel else piped_shard_fn

    def pipeline(index: DistributedIndex, lookup: LookupTable) -> SearchResult:
        d = index.vecs.shape[-1]
        vecs = index.vecs.reshape(n_shards, shard_rows, d)
        leaves = index.leaves.reshape(n_shards, shard_rows)
        ids = index.ids.reshape(n_shards, shard_rows)
        row_spec = P(axes, None)
        flat_spec = P(axes)
        rep = P()
        best_d, best_i, pairs, overflow = jax.shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=(row_spec, flat_spec, flat_spec, rep, rep, rep),
            out_specs=(P(axes, None, None), P(axes, None, None), rep, rep),
        )(vecs, leaves, ids, lookup.vecs, lookup.leaves, lookup.offsets)
        return _merge_shard_tables(
            mesh, axes, plan, lookup, best_d, best_i, pairs, overflow,
            jnp.int32(n_waves * n_shards), q_total=q_total,
            n_shards=n_shards, width=k, add_q_norms=True,
        )

    return pipeline


def _scan_codes_fused_fn(mesh, plan: SearchPlan, *, n_leaves, shard_rows,
                         q_total, axes):
    """Fused compressed-tier executor: same dispatch split as
    :func:`_point_major_fused_fn` but over PQ code slabs under the
    asymmetric distance — the kernel path is one whole-shard
    ``fusedscan.fused_adc_topk`` launch; the pipelined path prefetches
    the next wave's LUT slab into the loop carry."""
    from repro.core.sentinels import PAD_TILE_POINT_LEAF

    block_rows, q_cap = plan.block_rows, plan.q_cap
    r, m = plan.rerank, plan.code_m
    n_centers = 1 << plan.code_bits
    n_shards = math.prod(mesh.shape[a] for a in axes)
    if shard_rows % block_rows != 0:
        raise ValueError(f"{shard_rows=} not divisible by {block_rows=}")
    if r > block_rows:
        raise ValueError(f"rerank {r} must be <= {block_rows=}")
    if q_cap > q_total:
        raise ValueError(f"{q_cap=} must be <= padded query count {q_total=}")
    n_waves = shard_rows // block_rows
    use_kernel = _fused_wants_kernel()

    def kernel_shard_fn(codes, leaves, ids, lk_lut, lk_leaves, lk_offsets):
        from repro.kernels.fusedscan import ops as fused_ops

        codes, leaves, ids = codes[0], leaves[0], ids[0]
        with jax.named_scope(phases.DISTANCE):
            # tombstoned rows must never match (see _scan_codes_fn)
            plf_m = jnp.where(ids >= 0, leaves, PAD_TILE_POINT_LEAF)
            best_d, best_i = fused_ops.fused_adc_topk(
                codes, plf_m, ids, lk_lut.reshape(q_total, m, n_centers),
                lk_leaves, k=r, impl="pallas",
            )
        with jax.named_scope(phases.COUNT):
            pairs = jax.lax.psum(
                _leaf_pair_count(plf_m, lk_leaves, n_leaves), axes
            )
            overflow = jax.lax.psum(jnp.zeros((), jnp.int32), axes)
        return best_d[None], best_i[None], pairs, overflow

    def piped_shard_fn(codes, leaves, ids, lk_lut, lk_leaves, lk_offsets):
        codes, leaves, ids = codes[0], leaves[0], ids[0]

        @jax.named_scope(phases.SLICE)
        def fetch(i):
            first = jax.lax.dynamic_slice(leaves, (i * block_rows,), (1,))[0]
            slab = tilescan.leaf_slab(
                lk_offsets, first, n_entries=n_leaves, total_rows=q_total,
                cap=q_cap,
            )
            lut = jax.lax.dynamic_slice(
                lk_lut, (slab.start, 0), (q_cap, m * n_centers)
            )
            qlf = jax.lax.dynamic_slice(lk_leaves, (slab.start,), (q_cap,))
            return lut, qlf, slab.start

        def wave(i, c: _PipedCarry) -> _PipedCarry:
            from repro.kernels.adcscan import ops as adc_ops

            with jax.named_scope(phases.SLICE):
                start = i * block_rows
                pc = jax.lax.dynamic_slice(codes, (start, 0), (block_rows, m))
                plf = jax.lax.dynamic_slice(leaves, (start,), (block_rows,))
                pid = jax.lax.dynamic_slice(ids, (start,), (block_rows,))
            with jax.named_scope(phases.DISTANCE):
                plf_m = jnp.where(pid >= 0, plf, PAD_TILE_POINT_LEAF)
                cand_d, cand_sel = adc_ops.adc_topk(
                    pc, plf_m, c.qv.reshape(q_cap, m, n_centers), c.qlf, k=r,
                    impl="xla",
                )
            with jax.named_scope(phases.SELECT):
                cand_i = jnp.where(
                    cand_sel >= 0, pid[jnp.clip(cand_sel, 0)], INVALID_ID
                )
                cand_d = jnp.where(cand_i >= 0, cand_d, jnp.inf)
            best_d, best_i = tilescan.fold_rows(
                c.best_d, c.best_i, cand_d, cand_i, c.slab_start
            )
            with jax.named_scope(phases.COUNT):
                pairs = c.pairs + tilescan.count_pairs(plf_m, c.qlf)
                overflow = c.overflow + tilescan.slab_overflow(
                    lk_offsets, tilescan.last_valid_leaf(plf),
                    tilescan.Slab(start=c.slab_start, cap=q_cap),
                    n_entries=n_leaves,
                )
            lut, qlf, slab_start = fetch(jnp.minimum(i + 1, n_waves - 1))
            return _PipedCarry(best_d, best_i, pairs, overflow, lut, qlf,
                               slab_start)

        with jax.named_scope(phases.CARRY):
            lut0, qlf0, start0 = fetch(0)
            init = _PipedCarry(
                best_d=jnp.full((q_total, r), jnp.inf, jnp.float32),
                best_i=jnp.full((q_total, r), INVALID_ID, jnp.int32),
                pairs=jnp.zeros((), jnp.float32),
                overflow=jnp.zeros((), jnp.int32),
                qv=lut0, qlf=qlf0, slab_start=start0,
            )
            init = jax.tree.map(lambda x: pcast_varying(x, axes), init)
        out = jax.lax.fori_loop(0, n_waves, wave, init)
        with jax.named_scope(phases.COUNT):
            pairs = jax.lax.psum(out.pairs, axes)
            overflow = jax.lax.psum(out.overflow, axes)
        return out.best_d[None], out.best_i[None], pairs, overflow

    shard_fn = kernel_shard_fn if use_kernel else piped_shard_fn

    def pipeline(index: DistributedIndex, lookup: LookupTable,
                 codes: jax.Array, codebooks: jax.Array) -> SearchResult:
        lut = _build_adc_lut(lookup.vecs, codebooks, q_total=q_total, m=m,
                             n_centers=n_centers)
        with jax.named_scope(phases.SLICE):
            codes3 = codes.astype(jnp.int32).reshape(n_shards, shard_rows, m)
        leaves = index.leaves.reshape(n_shards, shard_rows)
        ids = index.ids.reshape(n_shards, shard_rows)
        row_spec = P(axes, None)
        flat_spec = P(axes)
        rep = P()
        best_d, best_i, pairs, overflow = jax.shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=(row_spec, flat_spec, flat_spec, rep, rep, rep),
            out_specs=(P(axes, None, None), P(axes, None, None), rep, rep),
        )(codes3, leaves, ids, lut, lookup.leaves, lookup.offsets)
        return _merge_shard_tables(
            mesh, axes, plan, lookup, best_d, best_i, pairs, overflow,
            jnp.int32(n_waves * n_shards), q_total=q_total,
            n_shards=n_shards, width=r, add_q_norms=False,
        )

    return pipeline


_LAYOUT_BUILDERS = {
    "point_major": _point_major_fn,
    "query_routed": _query_routed_fn,
    "scan_codes": _scan_codes_fn,
}

_FUSED_BUILDERS = {
    "point_major": _point_major_fused_fn,
    "scan_codes": _scan_codes_fused_fn,
}


def make_executor(
    mesh: Mesh,
    plan: SearchPlan,
    *,
    n_leaves: int,
    shard_rows: int,
    q_total: int,
    axes=None,
):
    """Build the jittable ``(index, lookup) -> SearchResult`` pipeline.

    ``q_total`` is the *padded lookup row* count (``n_queries * probes``
    rounded up); it must be a multiple of ``plan.probes`` so the final
    probe-group merge can reshape. Output tables have
    ``q_total // plan.probes`` rows (one per original query group).

    The ``scan_codes`` pipeline takes two extra arguments —
    ``(index, lookup, codes, codebooks)`` — and its result rows are
    ``plan.rerank`` *approximate* ADC candidates per query, which the
    caller reranks exactly (docs/compressed_codes.md).
    """
    plan = plan.resolved()
    axes = tuple(axes) if axes else batch_axes(mesh)
    if q_total % plan.probes:
        raise ValueError(f"{q_total=} must be a multiple of {plan.probes=}")
    if plan.impl == "fused":
        if plan.layout not in _FUSED_BUILDERS:
            raise ValueError(
                f"impl='fused' is not supported for layout {plan.layout!r}"
            )
        builder = _FUSED_BUILDERS[plan.layout]
    else:
        builder = _LAYOUT_BUILDERS[plan.layout]
    return builder(
        mesh, plan, n_leaves=n_leaves, shard_rows=shard_rows, q_total=q_total,
        axes=axes,
    )


def wave_shape(plan: SearchPlan, *, shard_rows: int, q_total: int,
               n_shards: int) -> tuple[int, int]:
    """``(waves, pairs per wave)`` of one run of ``plan``'s executor: the
    tiles it would scan with none skipped, summed over shards, and the
    distance pairs each evaluates, useful or not. ``SearchResult.waves``
    counts the tiles it did scan, so ``waves * pairs per wave`` is the
    run's computed pairs; ``SearchResult.pairs`` counts the same-leaf
    cells among them, so the two give the scan's pair yield.

    Point-major and codes sweeps tile the shard by ``block_rows`` against a
    ``q_cap`` query slab and skip the tiles no lookup row meets
    (``tilescan.live_waves``); query-routed visits every ``q_tile`` of its
    routed query rows against a ``p_cap`` point slab; the whole-shard fused
    kernel meets every point row with every lookup row (before tile
    padding).
    """
    plan = plan.resolved()
    if plan.layout == "query_routed":
        per_shard = _routed_query_cap(plan, q_total, n_shards) // plan.q_tile
        per_wave = plan.q_tile * plan.p_cap
    elif plan.impl == "fused" and _fused_wants_kernel():
        per_shard = shard_rows // plan.block_rows
        per_wave = plan.block_rows * q_total
    else:
        per_shard = shard_rows // plan.block_rows
        per_wave = plan.block_rows * plan.q_cap
    return int(per_shard) * int(n_shards), int(per_wave)
