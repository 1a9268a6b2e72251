"""Hierarchical vocabulary tree (Nistér–Stewénius-style unstructured
quantization, paper §2.3), TPU-adapted.

The paper organises C random representatives in a hierarchy of L levels with
modest fanout. On TPU we make the fanout *wide and MXU-aligned* (e.g.
256 x 256 = 65k leaves in two levels): every level's assignment is then a
dense ``(n, d) @ (d, fanout)`` GEMM + argmin, the exact shape the MXU and the
``l2nn`` Pallas kernel want. Levels are kept (the paper's hierarchy matters:
it is what keeps assignment cost at ``O(sum(fanouts))`` instead of
``O(prod(fanouts))``), but L stays small (2-3) — DESIGN.md §2.

Tree layout (L levels, fanouts ``(f0, f1, ..)``):
  level 0: ``(f0, d)``  roots
  level i: ``(n_nodes_{i-1}, f_i, d)`` children per parent node
Leaf id of a descriptor = mixed-radix path ``((b0*f1)+b1)*f2+...``.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.core.distance import PRECISION, nearest, sq_norms


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class VocabTree:
    """Index tree: the paper's broadcast auxiliary data (§2.5)."""

    levels: tuple  # level 0: (f0, d); level i: (nodes_{i-1}, f_i, d)

    def tree_flatten(self):
        return (self.levels,), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(levels=children[0])

    @property
    def fanouts(self) -> tuple[int, ...]:
        f = [self.levels[0].shape[0]]
        f.extend(lvl.shape[1] for lvl in self.levels[1:])
        return tuple(f)

    @property
    def n_leaves(self) -> int:
        return math.prod(self.fanouts)

    @property
    def dim(self) -> int:
        return self.levels[0].shape[-1]

    @property
    def nbytes(self) -> int:
        return sum(lvl.size * lvl.dtype.itemsize for lvl in self.levels)


def _segmented_pick(order, starts, counts, fanout, fallback, key):
    """For each of ``n_nodes`` segments pick ``fanout`` member indices.

    Strided picks inside each segment; empty segments fall back to random
    global indices (the paper picks representatives at random, so a sparse
    branch simply re-samples).
    """
    n_nodes = starts.shape[0]
    j = jnp.arange(fanout)
    # (n_nodes, fanout) positions inside each segment (strided, wrap-safe)
    pos = starts[:, None] + (j[None, :] * jnp.maximum(counts, 1)[:, None]) // fanout
    pos = jnp.clip(pos, 0, order.shape[0] - 1)
    picked = order[pos]
    rnd = jax.random.randint(key, (n_nodes, fanout), 0, fallback)
    return jnp.where(counts[:, None] > 0, picked, rnd)


@partial(jax.jit, static_argnames=("fanouts", "refine_iters"))
def build_tree(
    vecs: jax.Array,
    fanouts: Sequence[int] = (64, 64),
    *,
    key: jax.Array,
    refine_iters: int = 0,
) -> VocabTree:
    """Create the index tree from a (sample of a) descriptor collection.

    Paper-faithful mode (``refine_iters=0``): representatives are random
    picks, hierarchically organised. ``refine_iters>0`` adds Lloyd (k-means)
    sweeps per level — a beyond-paper quality knob (the paper cites
    hierarchical k-means lineage but uses random picks for scale).
    """
    fanouts = tuple(int(f) for f in fanouts)
    n, d = vecs.shape
    keys = jax.random.split(key, 2 * len(fanouts))
    vf = vecs.astype(jnp.float32)

    # ---- level 0: random roots ------------------------------------------
    idx0 = jax.random.choice(keys[0], n, (fanouts[0],), replace=n < fanouts[0])
    roots = vf[idx0]
    levels = [roots]
    node_of = jnp.zeros((n,), jnp.int32)  # current node path per sample row
    n_nodes = 1

    for li, f in enumerate(fanouts):
        centroids = levels[li]
        if li == 0:
            branch, _ = nearest(vf, centroids)
        else:
            gathered = centroids[node_of]  # (n, f, d)
            d2 = (
                sq_norms(gathered)
                - 2.0
                * jnp.einsum("nd,nfd->nf", vf, gathered,
                             preferred_element_type=jnp.float32,
                             precision=PRECISION)
            )
            branch = jnp.argmin(d2, axis=1).astype(jnp.int32)
        node_of = node_of * f + branch
        n_nodes *= f

        # Lloyd refinement of this level's centroids (optional)
        for r in range(refine_iters):
            sums = jax.ops.segment_sum(vf, node_of, num_segments=n_nodes)
            cnts = jax.ops.segment_sum(
                jnp.ones((n,), jnp.float32), node_of, num_segments=n_nodes
            )
            means = sums / jnp.maximum(cnts, 1.0)[:, None]
            flat_old = levels[li].reshape(n_nodes, d)
            flat_new = jnp.where(cnts[:, None] > 0, means, flat_old)
            levels[li] = flat_new.reshape(levels[li].shape)
            # re-assign branch within the (unchanged) parent partition
            if li == 0:
                branch, _ = nearest(vf, levels[0])
                node_of = branch
            else:
                parent = node_of // f
                gathered = levels[li][parent]
                d2 = (
                    sq_norms(gathered)
                    - 2.0
                    * jnp.einsum("nd,nfd->nf", vf, gathered,
                                 preferred_element_type=jnp.float32,
                                 precision=PRECISION)
                )
                node_of = parent * f + jnp.argmin(d2, axis=1).astype(jnp.int32)

        # ---- pick children of every node for the next level --------------
        if li + 1 < len(fanouts):
            fnext = fanouts[li + 1]
            order = jnp.argsort(node_of)
            sorted_nodes = node_of[order]
            cnts = jax.ops.segment_sum(
                jnp.ones((n,), jnp.int32), node_of, num_segments=n_nodes
            )
            starts = jnp.concatenate(
                [jnp.zeros((1,), jnp.int32), jnp.cumsum(cnts)[:-1]]
            )
            del sorted_nodes
            pick = _segmented_pick(
                order, starts, cnts, fnext, n, keys[2 * li + 1]
            )  # (n_nodes, fnext) sample-row indices
            levels.append(vf[pick])  # (n_nodes, fnext, d)

    return VocabTree(levels=tuple(levels))


def tree_assign(tree: VocabTree, x: jax.Array) -> jax.Array:
    """Leaf id per row of x — the paper's map-side descriptor assignment.

    Level 0 is a dense GEMM+argmin (`l2nn` kernel shape); deeper levels
    gather each row's branch children and reduce. Bulk callers should chunk
    rows (the index pipeline does this per wave).
    """
    xf = x.astype(jnp.float32)
    node, _ = nearest(xf, tree.levels[0])
    for lvl in tree.levels[1:]:
        f = lvl.shape[1]
        # child norms from the (nodes, f, d) table — loop-invariant, so XLA
        # hoists it out of wave loops (vs norms of the per-row gathered
        # tensor, which cost O(rows * f * d) HBM traffic per wave)
        cn = jnp.sum(
            lvl.astype(jnp.float32) ** 2, axis=-1
        )  # (nodes, f)
        gathered = lvl[node]  # (n, f, d)
        d2 = cn[node] - 2.0 * jnp.einsum(
            "nd,nfd->nf", xf, gathered, preferred_element_type=jnp.float32,
            precision=PRECISION,
        )
        node = node * f + jnp.argmin(d2, axis=1).astype(jnp.int32)
    return node


def leaf_centroids(tree: VocabTree) -> jax.Array:
    """(n_leaves, d) flattened deepest-level centroids (for diagnostics)."""
    last = tree.levels[-1]
    return last.reshape(-1, last.shape[-1]) if last.ndim == 3 else last
