"""Per-kernel correctness: Pallas (interpret=True) vs pure-jnp oracle,
swept over shapes/dtypes (hypothesis + explicit grids)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.l2nn.ops import l2_nearest
from repro.kernels.l2nn.ref import l2_nearest_ref
from repro.kernels.l2topk.ops import l2_topk
from repro.kernels.l2topk.ref import l2_topk_ref


def _rand(key, shape, dtype):
    x = jax.random.normal(jax.random.PRNGKey(key), shape)
    return x.astype(dtype)


# ---------------------------------------------------------------------------
# l2nn
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "n,c,d,tn,tc",
    [
        (128, 64, 16, 64, 32),
        (200, 70, 8, 128, 64),  # padding on both axes
        (64, 512, 128, 64, 128),  # SIFT dim, many centroids
        (32, 8, 4, 32, 8),
    ],
)
def test_l2nn_matches_ref(n, c, d, tn, tc, dtype):
    x = _rand(1, (n, d), dtype)
    cen = _rand(2, (c, d), dtype)
    i_ref, d_ref = l2_nearest(x, cen, impl="xla")
    i_pal, d_pal = l2_nearest(x, cen, impl="pallas", tile_n=tn, tile_c=tc)
    np.testing.assert_array_equal(np.array(i_ref), np.array(i_pal))
    np.testing.assert_allclose(
        np.array(d_ref), np.array(d_pal), rtol=2e-5, atol=2e-5
    )


@settings(max_examples=25, deadline=None)
@given(
    n=st.integers(4, 150),
    c=st.integers(2, 90),
    d=st.sampled_from([4, 8, 32]),
    seed=st.integers(0, 2**30),
)
def test_l2nn_property_sweep(n, c, d, seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (n, d))
    cen = jax.random.normal(jax.random.PRNGKey(seed + 1), (c, d))
    i_pal, d_pal = l2_nearest(x, cen, impl="pallas", tile_n=64, tile_c=32)
    # oracle in numpy, full distances
    d2 = ((np.array(x)[:, None] - np.array(cen)[None]) ** 2).sum(-1)
    np.testing.assert_array_equal(np.array(i_pal), d2.argmin(1))
    np.testing.assert_allclose(np.array(d_pal), d2.min(1), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# l2topk
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "p,q,d,k,n_leaves",
    [
        (256, 128, 16, 4, 8),
        (300, 100, 8, 8, 5),  # padded tiles
        (128, 64, 128, 16, 3),  # SIFT dim
        (64, 32, 4, 1, 2),  # k=1
    ],
)
def test_l2topk_matches_ref(p, q, d, k, n_leaves, dtype):
    pts = _rand(3, (p, d), dtype)
    qrs = _rand(4, (q, d), dtype)
    plf = jax.random.randint(jax.random.PRNGKey(5), (p,), 0, n_leaves)
    qlf = jax.random.randint(jax.random.PRNGKey(6), (q,), 0, n_leaves)
    d_ref, i_ref = l2_topk(pts, plf, qrs, qlf, k=k, impl="xla")
    d_pal, i_pal = l2_topk(pts, plf, qrs, qlf, k=k, impl="pallas",
                           tile_p=128, tile_q=64)
    d_ref, i_ref, d_pal, i_pal = map(np.array, (d_ref, i_ref, d_pal, i_pal))
    finite = np.isfinite(d_ref)
    np.testing.assert_array_equal(finite, np.isfinite(d_pal))
    np.testing.assert_allclose(d_ref[finite], d_pal[finite], rtol=2e-4, atol=2e-4)
    np.testing.assert_array_equal(i_ref, i_pal)


def test_l2topk_no_matches_gives_invalid():
    pts = jax.random.normal(jax.random.PRNGKey(0), (64, 8))
    qrs = jax.random.normal(jax.random.PRNGKey(1), (32, 8))
    plf = jnp.zeros((64,), jnp.int32)
    qlf = jnp.ones((32,), jnp.int32)  # disjoint leaves: no matches at all
    for impl in ("xla", "pallas"):
        d, i = l2_topk(pts, plf, qrs, qlf, k=3, impl=impl)
        assert bool((np.array(i) == -1).all())
        assert bool(np.isinf(np.array(d)).all())


@settings(max_examples=20, deadline=None)
@given(
    p=st.integers(8, 200),
    q=st.integers(4, 100),
    k=st.sampled_from([1, 3, 5]),
    n_leaves=st.integers(1, 12),
    seed=st.integers(0, 2**30),
)
def test_l2topk_property_sweep(p, q, k, n_leaves, seed):
    d = 8
    pts = jax.random.normal(jax.random.PRNGKey(seed), (p, d))
    qrs = jax.random.normal(jax.random.PRNGKey(seed + 1), (q, d))
    plf = jax.random.randint(jax.random.PRNGKey(seed + 2), (p,), 0, n_leaves)
    qlf = jax.random.randint(jax.random.PRNGKey(seed + 3), (q,), 0, n_leaves)
    d_pal, i_pal = l2_topk(pts, plf, qrs, qlf, k=k, impl="pallas",
                           tile_p=64, tile_q=32)
    d_pal, i_pal = np.array(d_pal), np.array(i_pal)
    # numpy oracle
    P, Q = np.array(pts), np.array(qrs)
    pl, ql = np.array(plf), np.array(qlf)
    pn = (P * P).sum(1)
    for qi in range(q):
        cand = np.flatnonzero(pl == ql[qi])
        partial = pn[cand] - 2 * P[cand] @ Q[qi]
        order = cand[np.argsort(partial)][:k]
        got = i_pal[qi][i_pal[qi] >= 0]
        assert len(got) == min(k, len(cand))
        # distances must match the oracle's sorted top-k (ids may tie-swap)
        np.testing.assert_allclose(
            d_pal[qi][: len(got)],
            np.sort(partial)[: len(got)],
            rtol=1e-4,
            atol=1e-4,
        )
        assert set(got.tolist()) <= set(cand.tolist())


@pytest.mark.parametrize(
    "P,Q,k,d,dense,adc",
    [(8_400_000, 4096, 20, 128, (1024, 256), (512, 128)),
     (8_400_000, 4096, 128, 128, (2048, 128), (512, 128)),
     (1000, 256, 20, 768, (512, 256), (512, 128)),
     (300, 40, 4, 32, (256, 128), (256, 128))],
)
def test_kernel_tiles_fit_the_vmem_budget(P, Q, k, d, dense, adc):
    """The largest lane-aligned tiles within the VMEM plan, never past
    the lane-rounded operand."""
    from repro.kernels import tiles

    assert tiles.dense_tiles(P, Q, k=k, d=d, itemsize=4) == dense
    assert tiles.adc_tiles(P, Q, k=k, m=8, n_centers=256) == adc
    for tp, tq, row in ((*dense, dict(p_row_bytes=8 * d,
                                      q_row_bytes=8 * d)),
                        (*adc, dict(p_row_bytes=1024 + 8192,
                                    q_row_bytes=16384))):
        assert tiles.vmem_estimate(tp, tq, k=k, **row) <= tiles.VMEM_BUDGET


def test_kernel_tiles_refuse_what_cannot_fit():
    from repro.kernels import tiles

    with pytest.raises(ValueError, match="VMEM"):
        tiles.choose_tiles(4096, 4096, k=20, p_row_bytes=1 << 20,
                           q_row_bytes=1 << 20)
