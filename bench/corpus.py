"""Data, queries and the vocabulary tree of a cell, all made from ``--seed``.

The descriptor generator is this benchmark's own copy of the program's
SIFT-like mixture (``repro.data.synth``): Gamma(2, 24) centres, per-centre
isotropic scales in [4, 18], Zipf(1.1) centre masses, values clipped to
[0, 255] and rounded like SIFT byte descriptors. It runs on the device in one
jitted call, so set-up does not pay for half a billion host-side normals.

Query rows are rounded too: a SIFT descriptor of a distorted copy is bytes
like the base. With byte-valued rows and byte-valued tree centres every
squared distance the search computes is an integer below 2**24, which f32
holds exactly, so the program and the float64 reference must agree exactly.

The tree is made here, not by the program, so that the reference takes
nothing the program made: the paper's random representatives (the
program's ``build_tree`` with ``refine_iters=0`` does the same), i.e.
``fanouts[0]`` rows of a seeded sample as roots and, per root, ``fanouts[1]``
strided picks among the sample rows that root holds.
"""

from __future__ import annotations

import numpy as np

MAX_VALUE = 255.0


def key_of(seed: int, stream: int):
    """A JAX key for ``(seed, stream)``; seeds past 32 bits are folded in."""
    import jax

    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, stream)


def make_descriptors(seed: int, n_rows: int, dim: int, n_centers: int):
    """``(n_rows, dim)`` f32 byte values on the device, drawn from the
    mixture. One jitted call."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gen(key):
        k_c, k_s, k_rows = jax.random.split(key, 3)
        centers = 24.0 * jax.random.gamma(k_c, 2.0, (n_centers, dim))
        scales = jax.random.uniform(k_s, (n_centers, 1), minval=4.0,
                                    maxval=18.0)
        w = 1.0 / jnp.arange(1, n_centers + 1, dtype=jnp.float32) ** 1.1
        cdf = jnp.cumsum(w / w.sum())
        k_u, k_n = jax.random.split(k_rows)
        u = jax.random.uniform(k_u, (n_rows,))
        comp = jnp.minimum(jnp.searchsorted(cdf, u), n_centers - 1)
        noise = jax.random.normal(k_n, (n_rows, dim))
        x = centers[comp] + noise * scales[comp]
        return jnp.round(jnp.clip(x, 0.0, MAX_VALUE))

    return gen(key_of(seed, 0))


def image_queries(corpus: np.ndarray, image_ids, desc_per_image: int,
                  noise: float, seed: int) -> np.ndarray:
    """Distorted copies of indexed images: each image's rows plus Gaussian
    noise seeded by ``(seed, image id)`` (the same photo always distorts
    the same way), clipped and rounded to bytes. ``(len(ids)*dpi, dim)``."""
    out = []
    for img in np.asarray(image_ids, np.int64):
        rows = corpus[img * desc_per_image:(img + 1) * desc_per_image]
        rng = np.random.default_rng([int(seed) & 0xFFFFFFFF,
                                     int(seed) >> 32, int(img)])
        q = rows + rng.standard_normal(rows.shape, np.float32) * noise
        out.append(np.rint(np.clip(q, 0.0, MAX_VALUE)))
    return np.concatenate(out).astype(np.float32)


def nearest_rows(x: np.ndarray, cents: np.ndarray) -> np.ndarray:
    """Index of the nearest row of ``cents`` for every row of ``x`` (first
    index on ties). Exact for byte-valued rows: see :func:`exact_dtype`."""
    dt = exact_dtype(x.shape[1])
    c = cents.astype(dt)
    cn = (c * c).sum(1)
    out = np.empty(len(x), np.int64)
    step = 1 << 15
    for s in range(0, len(x), step):
        part = cn[None, :] - 2.0 * (x[s:s + step].astype(dt) @ c.T)
        out[s:s + step] = part.argmin(1)
    return out


def exact_dtype(dim: int):
    """float32 where every partial sum of ``||c||^2 - 2 x.c`` over byte
    values is an integer below 2**24 (so BLAS computes it exactly, in any
    order), else float64."""
    return np.float32 if 2 * dim * MAX_VALUE**2 < 2**24 else np.float64


def make_tree(corpus: np.ndarray, fanouts, sample: int, seed: int):
    """Random-representative tree levels as numpy:
    ``[(f0, d), (f0, f1, d)]``."""
    if len(fanouts) != 2:
        raise ValueError(f"two-level trees only, got fanouts {fanouts}")
    f0, f1 = (int(f) for f in fanouts)
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 7])
    n = len(corpus)
    rows = np.sort(rng.choice(n, min(sample, n), replace=False))
    x = corpus[rows]
    roots = x[rng.choice(len(x), f0, replace=len(x) < f0)]
    node = nearest_rows(x, roots)
    order = np.argsort(node, kind="stable")
    counts = np.bincount(node, minlength=f0)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    j = np.arange(f1)
    pos = starts[:, None] + (j[None, :] * np.maximum(counts, 1)[:, None]) // f1
    picked = order[np.clip(pos, 0, len(x) - 1)]
    rnd = rng.integers(0, len(x), (f0, f1))
    picked = np.where(counts[:, None] > 0, picked, rnd)
    return [roots.astype(np.float32), x[picked].astype(np.float32)]


def leaves_of(levels, x: np.ndarray) -> np.ndarray:
    """Greedy descent (argmin per level, first index on ties) of every row
    of ``x``: the leaf id ``b0 * f1 + b1``."""
    roots, children = levels
    f1 = children.shape[1]
    b0 = nearest_rows(x, roots)
    leaf = np.empty(len(x), np.int64)
    order = np.argsort(b0, kind="stable")
    bounds = np.searchsorted(b0[order], np.arange(len(roots) + 1))
    for node in range(len(roots)):
        sel = order[bounds[node]:bounds[node + 1]]
        if sel.size:
            leaf[sel] = node * f1 + nearest_rows(x[sel], children[node])
    return leaf


def device_descent(levels, chunk: int = 8192):
    """:func:`leaves_of` on the accelerator, for the corpus-sized descents
    of the reference: returns ``rows -> leaves``. Byte values are exact in
    bfloat16, so a bf16 product with f32 accumulation computes every
    partial distance exactly (all are integers below 2**24): the same
    argmin, first index on ties."""
    import jax
    import jax.numpy as jnp

    roots, children = (np.asarray(lvl, np.float32) for lvl in levels)
    f1 = children.shape[1]
    rn = jnp.asarray((roots * roots).sum(1))
    cn = jnp.asarray((children * children).sum(2))
    r16 = jnp.asarray(roots, jnp.bfloat16)
    c16 = jnp.asarray(children, jnp.bfloat16)

    @jax.jit
    def step(xc):
        ok = jnp.all((xc == jnp.round(xc)) & (xc >= 0) & (xc <= MAX_VALUE))
        xb = xc.astype(jnp.bfloat16)
        b0 = jnp.argmin(rn[None, :] - 2.0 * jnp.dot(
            xb, r16.T, preferred_element_type=jnp.float32), axis=1)
        d1 = cn[b0] - 2.0 * jnp.einsum(
            "nd,nfd->nf", xb, c16[b0], preferred_element_type=jnp.float32)
        return b0 * f1 + jnp.argmin(d1, axis=1), ok

    def leaves(x: np.ndarray) -> np.ndarray:
        n = len(x)
        out = np.empty(n, np.int64)
        for s in range(0, n, chunk):
            part = x[s:s + chunk]
            pad = chunk - len(part)
            if pad:
                part = np.concatenate([part, np.zeros((pad, x.shape[1]),
                                                      part.dtype)])
            leaf, ok = step(jnp.asarray(part, jnp.float32))
            if not bool(ok):
                raise ValueError("rows must be byte values for an exact "
                                 "descent")
            out[s:s + chunk - pad] = np.asarray(leaf)[:chunk - pad]
        return out

    return leaves
