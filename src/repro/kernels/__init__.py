"""Pallas TPU kernels for the compute hot-spots of the paper's workflow.

  l2nn     — fused L2 distance + argmin  (index build: descriptor -> leaf)
  l2topk   — fused L2 distance + top-k   (search: tile x query-slab k-NN)
  adcscan  — PQ asymmetric-distance scan + top-k (compressed tier)
  fusedscan — whole-shard scan with in-kernel k-selection (dense and ADC)

Each subpackage: kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd
wrapper with impl selection), ref.py (pure-jnp oracle).
"""

from __future__ import annotations

import jax


def interpret_mode():
    """The ``interpret`` argument every kernel wrapper passes to Pallas.

    On a TPU the kernel is compiled (``False``). Elsewhere it runs in the
    TPU interpreter, which, unlike the generic one, tracks the mesh axes
    values vary over and so runs inside the executors' ``shard_map``.
    """
    if jax.default_backend() == "tpu":
        return False
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.InterpretParams()
