"""The search path's Pallas kernels compile for a TPU v5e.

Everywhere else the suite runs the kernels in interpret mode, which
accepts tiles the chip's compiler refuses (a kernel that needs more VMEM
than it may use, a slice off the tiling). Here each kernel is compiled
for a described, unattached v5e at the shapes ``chip_smoke.py`` serves
(d=128, k=20, 4.2M rows in one segment, 4096-row buckets) and at the
tiles the executors choose for them, and must come out as a TPU kernel.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.engine import plan
from repro.core.search import lookup_q_total
from repro.kernels.adcscan.kernel import adcscan_pallas
from repro.kernels.fusedscan.kernel import fusedadc_pallas, fusedscan_pallas
from repro.kernels.l2topk.kernel import l2topk_pallas
from repro.kernels.tiles import adc_tiles, dense_tiles, l2topk_tiles

DIM, K, BUCKET, N_LEAVES = 128, 20, 4096, 65536
SEGMENT_ROWS = 2 * 4_200_000  # a one-chip build pads to routing capacity
CODE_M, CODE_BITS = 8, 8
N_CENTERS = 1 << CODE_BITS


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


def _plan(layout, impl):
    kw = {}
    if layout == "scan_codes":
        kw = dict(dim=DIM, code_m=CODE_M, code_bits=CODE_BITS)
    p = plan(rows=SEGMENT_ROWS, n_leaves=N_LEAVES, n_queries=BUCKET,
             n_shards=1, k=K, probes=1, layout=layout, impl=impl, **kw)
    return p, lookup_q_total(p, BUCKET, 1)


def _assert_kernel(one_chip, fn, shapes, **kw):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    text = jax.jit(lambda *a: fn(*a, **kw)).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _row_args(tp, tq, p_cols, p_dtype, q_cols):
    """Two tiles a side: enough grid for the compiler, fast to build."""
    return [((2 * tp, p_cols), p_dtype), ((1, 2 * tp), jnp.int32),
            ((2 * tq, q_cols), jnp.float32), ((2 * tq, 1), jnp.int32)]


def test_l2topk_compiles_at_executor_tiles(one_chip, no_compile_cache):
    p, _ = _plan("point_major", "pallas")  # one wave: block_rows x q_cap
    tp, tq = l2topk_tiles(p.block_rows, p.q_cap)
    _assert_kernel(one_chip, l2topk_pallas,
                   _row_args(tp, tq, DIM, jnp.float32, DIM),
                   k=K, tile_p=tp, tile_q=tq)


def test_fusedscan_compiles_at_executor_tiles(one_chip, no_compile_cache):
    _, q_total = _plan("point_major", "fused")  # the whole shard at once
    tp, tq = dense_tiles(SEGMENT_ROWS, q_total, k=K, d=DIM, itemsize=4)
    _assert_kernel(one_chip, fusedscan_pallas,
                   _row_args(tp, tq, DIM, jnp.float32, DIM),
                   k=K, tile_p=tp, tile_q=tq)


def test_fusedadc_compiles_at_executor_tiles(one_chip, no_compile_cache):
    p, q_total = _plan("scan_codes", "fused")
    tp, tq = adc_tiles(SEGMENT_ROWS, q_total, k=p.rerank, m=CODE_M,
                       n_centers=N_CENTERS)
    _assert_kernel(one_chip, fusedadc_pallas,
                   _row_args(tp, tq, CODE_M, jnp.int32,
                             CODE_M * N_CENTERS),
                   k=p.rerank, n_centers=N_CENTERS, tile_p=tp, tile_q=tq)


def test_adcscan_compiles_at_executor_tiles(one_chip, no_compile_cache):
    p, _ = _plan("scan_codes", "pallas")  # one wave: block_rows x q_cap
    tp, tq = adc_tiles(p.block_rows, p.q_cap, k=p.rerank, m=CODE_M,
                       n_centers=N_CENTERS)
    _assert_kernel(one_chip, adcscan_pallas,
                   _row_args(tp, tq, CODE_M, jnp.int32, CODE_M * N_CENTERS),
                   k=p.rerank, n_centers=N_CENTERS, tile_p=tp, tile_q=tq)
