"""Query lookup table (paper §2.4, step 1).

All query descriptors of a batch are assigned to their leaf cluster by
traversing the index tree, then reordered by leaf id; a CSR offset array per
leaf lets any index block find "which query descriptors have to be used in
distance calculations when a cluster identifier is given". The table is the
broadcast auxiliary data of the search phase — replicated across devices
(the paper ships it to every map task via HDFS).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import phases
from repro.core.distance import PRECISION, sq_norms
from repro.core.sentinels import PAD_QUERY_LEAF
from repro.core.tree import VocabTree, tree_assign


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class LookupTable:
    vecs: jax.Array  # (Q, d) query descriptors, sorted by leaf id
    qids: jax.Array  # (Q,) original query row ids (permutation)
    leaves: jax.Array  # (Q,) leaf id per sorted query
    offsets: jax.Array  # (n_leaves + 1,) CSR start offsets into vecs

    def tree_flatten(self):
        return (self.vecs, self.qids, self.leaves, self.offsets), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def n_queries(self) -> int:
        return self.vecs.shape[0]

    @property
    def n_leaves(self) -> int:
        return self.offsets.shape[0] - 1

    @property
    def nbytes(self) -> int:
        return sum(
            a.size * a.dtype.itemsize
            for a in (self.vecs, self.qids, self.leaves, self.offsets)
        )


def probe_leaves(tree: VocabTree, queries: jax.Array, probes: int) -> jax.Array:
    """(Q, probes) leaves per query: the hierarchical assignment first, then
    the next-nearest leaves (multi-probe soft assignment).

    Beam descent, not a dense scan over all ``n_leaves`` centroids: each
    level keeps the ``probes`` nearest nodes among the beam's children
    (O(Q * probes * fanout * d) per level — same shape as ``tree_assign``,
    beam-wide), so large-vocab trees (65k leaves) never materialise a
    (Q, n_leaves) distance matrix.

    Column 0 is exactly ``tree_assign``: the greedy chain is maintained
    *inside* the loop with the same arithmetic (one descent, not two), is
    force-kept in the beam, and is pinned to rank 0 — so ``probes=1``
    reproduces the hard assignment and widening ``probes`` only ever
    *adds* visited leaves (recall is monotone non-decreasing in T).
    """
    if probes == 1:
        return tree_assign(tree, queries).astype(jnp.int32)[:, None]
    qf = queries.astype(jnp.float32)
    n_q = qf.shape[0]
    roots = tree.levels[0].astype(jnp.float32)
    d2 = sq_norms(roots)[None, :] - 2.0 * jnp.einsum(
        "qd,md->qm", qf, roots, preferred_element_type=jnp.float32,
        precision=PRECISION,
    )  # (Q, f0) — same partial distance tree_assign's nearest() uses
    greedy = jnp.argmin(d2, axis=1).astype(jnp.int32)
    neg, nodes = jax.lax.top_k(-d2, min(probes, roots.shape[0]))
    has = (nodes == greedy[:, None]).any(axis=1)
    nodes = nodes.at[:, -1].set(jnp.where(has, nodes[:, -1], greedy))
    for lvl in tree.levels[1:]:
        f = lvl.shape[1]
        lf = lvl.astype(jnp.float32)
        cn = jnp.sum(lf * lf, axis=-1)  # (nodes, f) — loop-invariant
        gathered = lf[nodes]  # (Q, B, f, d)
        d2 = cn[nodes] - 2.0 * jnp.einsum(
            "qd,qbfd->qbf", qf, gathered, preferred_element_type=jnp.float32,
            precision=PRECISION,
        )
        cand = nodes[:, :, None] * f + jnp.arange(f, dtype=jnp.int32)
        neg, sel = jax.lax.top_k(-d2.reshape(n_q, -1), min(probes, cand[0].size))
        nodes = jnp.take_along_axis(cand.reshape(n_q, -1), sel, axis=1)
        # advance the greedy chain and force it into the beam (it can fall
        # out: beam score is centroid distance, which is not monotone down
        # the hierarchy) — replace the worst slot when missing
        g_children = lf[greedy]  # (Q, f, d)
        gd2 = cn[greedy] - 2.0 * jnp.einsum(
            "qd,qfd->qf", qf, g_children, preferred_element_type=jnp.float32,
            precision=PRECISION,
        )
        greedy = greedy * f + jnp.argmin(gd2, axis=1).astype(jnp.int32)
        has = (nodes == greedy[:, None]).any(axis=1)
        nodes = nodes.at[:, -1].set(jnp.where(has, nodes[:, -1], greedy))
    # pin the hard assignment (== greedy chain) to rank 0, keep the rest in
    # beam (ascending-distance) order
    is_primary = nodes == greedy[:, None]
    rank = jnp.where(is_primary, -1, jnp.arange(nodes.shape[1], dtype=jnp.int32))
    order = jnp.argsort(rank, axis=1, stable=True)
    return jnp.take_along_axis(nodes, order, axis=1).astype(jnp.int32)


@jax.named_scope(phases.LOOKUP)
def build_lookup(
    tree: VocabTree, queries: jax.Array, *, probes: int = 1
) -> LookupTable:
    """Assign queries to their ``probes`` nearest leaves and build the CSR
    table (jit-able; ``probes`` static).

    Args:
      tree: the vocabulary :class:`~repro.core.tree.VocabTree`.
      queries: ``(Q, d)`` query descriptors (any float dtype; routing
        arithmetic is f32).
      probes: leaves visited per query (multi-probe width T, static).

    Returns:
      A :class:`LookupTable` of ``Q * probes`` rows, leaf-sorted with CSR
      offsets. With multi-probe, each query expands into ``probes`` rows
      (same vector, one row per probed leaf); ``qids`` then hold *flat
      merge slots* ``query_id * probes + probe_rank`` — a permutation of
      ``arange(Q * probes)`` — which the engine executors scatter into
      and fold back to one k-row per query at merge time.

    Raises:
      ValueError: ``probes < 1`` or ``probes > tree.n_leaves``.
    """
    if probes < 1:
        raise ValueError(f"{probes=} must be >= 1")
    if probes > tree.n_leaves:
        raise ValueError(f"{probes=} must be <= n_leaves={tree.n_leaves}")
    # one implementation of the sort/CSR build: the fixed-shape serving
    # path with no masked rows and no tail padding IS the direct build
    leaves = probe_leaves(tree, queries, probes)
    return lookup_from_leaves(queries, leaves, n_leaves=tree.n_leaves)


def lookup_from_leaves(
    queries: jax.Array,
    leaves: jax.Array,
    *,
    n_leaves: int,
    n_valid: jax.Array | int | None = None,
    q_total: int | None = None,
) -> LookupTable:
    """Build a :class:`LookupTable` from precomputed ``(Q, probes)`` probe
    leaves, at a *fixed output shape* — the serving bucket path.

    ``n_valid`` (traced OK) marks how many leading query rows are real;
    rows ``>= n_valid`` get :data:`PAD_QUERY_LEAF`, so a padded bucket
    never routes garbage to a real leaf, never matches any point, and never
    changes a real query's slab — yet the jitted shapes are those of the
    full bucket, so varying request sizes within a bucket never recompile.
    ``q_total`` appends pad_lookup-style tail rows (fresh flat slots past
    the real ones) up to the executor's padded row count.

    Real rows keep exactly the ordering :func:`build_lookup` gives them
    (stable sort by leaf), so bucketed results are bit-identical to the
    direct path for the same plan budgets.
    """
    q, probes = leaves.shape
    q_rows = q * probes
    if q_total is None:
        q_total = q_rows
    if q_total < q_rows or q_total % probes:
        raise ValueError(
            f"{q_total=} must be >= {q_rows} and a multiple of {probes=}"
        )
    if n_valid is None:
        n_valid = q
    valid = jnp.arange(q, dtype=jnp.int32) < n_valid
    leaves = jnp.where(
        valid[:, None], leaves, jnp.int32(PAD_QUERY_LEAF)
    ).reshape(-1)
    vecs = jnp.repeat(queries, probes, axis=0) if probes > 1 else queries
    order = jnp.argsort(leaves, stable=True)
    sorted_leaves = leaves[order].astype(jnp.int32)
    # offsets over the q_rows sorted region only (tail pads appended after,
    # exactly like pad_lookup — they are outside every CSR span)
    offsets = jnp.searchsorted(
        sorted_leaves, jnp.arange(n_leaves + 1, dtype=jnp.int32)
    ).astype(jnp.int32)
    pad = q_total - q_rows
    svecs = vecs[order]
    qids = order.astype(jnp.int32)
    if pad:
        svecs = jnp.concatenate(
            [svecs, jnp.zeros((pad, svecs.shape[1]), svecs.dtype)]
        )
        qids = jnp.concatenate(
            [qids, jnp.arange(q_rows, q_total, dtype=jnp.int32)]
        )
        sorted_leaves = jnp.concatenate(
            [sorted_leaves, jnp.full((pad,), PAD_QUERY_LEAF, jnp.int32)]
        )
    return LookupTable(
        vecs=svecs, qids=qids, leaves=sorted_leaves, offsets=offsets
    )


@jax.named_scope(phases.LOOKUP)
def build_lookup_bucketed(
    tree: VocabTree,
    queries: jax.Array,
    n_valid: jax.Array | int,
    *,
    probes: int = 1,
    q_total: int | None = None,
) -> tuple[LookupTable, jax.Array]:
    """Bucket-shaped :func:`build_lookup`: queries are padded to a warmed
    bucket size and ``n_valid`` masks the tail. Returns the table plus the
    ``(Q, probes)`` probe-leaf matrix (the serving hot-leaf cache keys on
    it)."""
    leaves = probe_leaves(tree, queries, probes)
    lk = lookup_from_leaves(
        queries, leaves, n_leaves=tree.n_leaves, n_valid=n_valid,
        q_total=q_total,
    )
    return lk, leaves
