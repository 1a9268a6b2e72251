"""The main search kernels compile for a TPU v5e at the benchmark's widths.

Each dense kernel is compiled for a described, unattached v5e (one chip of
a ``v5e:2x2`` topology) at the tiles the executors choose for the
``copydays-sift`` shapes: its rows padded 2x for routing in one segment,
d=128, 4,096-row buckets, k=20.
"""

import json
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.engine import plan
from repro.core.search import lookup_q_total
from repro.kernels.fusedscan.kernel import fusedscan_pallas
from repro.kernels.l2topk.kernel import l2topk_pallas
from repro.kernels.tiles import dense_tiles, l2topk_tiles

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")
BUCKET = 4096


def _config(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        cfg = json.load(f)
    data = cfg["data"]
    rows = data["n_images"] * data["desc_per_image"]
    f0, f1 = cfg["tree"]["fanouts"]
    return cfg, 2 * rows, f0 * f1, data["dim"]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """Compiles for a described chip can be written to the persistent
    cache but never read back without one: keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _plan(name, layout, impl):
    cfg, rows, n_leaves, dim = _config(name)
    p = plan(rows=rows, n_leaves=n_leaves, n_queries=BUCKET, n_shards=1,
             k=cfg["search"]["k"], probes=cfg["search"]["probes"],
             layout=layout, impl=impl)
    return cfg, rows, dim, p, lookup_q_total(p, BUCKET, 1)


def _assert_kernel(one_chip, fn, shapes, **kw):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    text = jax.jit(lambda *a: fn(*a, **kw)).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _row_args(tp, tq, p_cols, p_dtype, q_cols):
    """Two tiles a side: enough grid for the compiler, fast to build."""
    return [((2 * tp, p_cols), p_dtype), ((1, 2 * tp), jnp.int32),
            ((2 * tq, q_cols), jnp.float32), ((2 * tq, 1), jnp.int32)]


def test_l2topk_copydays(one_chip, no_compile_cache):
    cfg, _, dim, p, _ = _plan("copydays-sift", "point_major", "pallas")
    tp, tq = l2topk_tiles(p.block_rows, p.q_cap)
    _assert_kernel(one_chip, l2topk_pallas,
                   _row_args(tp, tq, dim, jnp.float32, dim),
                   k=cfg["search"]["k"], tile_p=tp, tile_q=tq)


def test_fusedscan_copydays(one_chip, no_compile_cache):
    cfg, rows, dim, _, q_total = _plan("copydays-sift", "point_major",
                                       "fused")
    k = cfg["search"]["k"]
    tp, tq = dense_tiles(rows, q_total, k=k, d=dim, itemsize=4)
    _assert_kernel(one_chip, fusedscan_pallas,
                   _row_args(tp, tq, dim, jnp.float32, dim),
                   k=k, tile_p=tp, tile_q=tq)
