"""Tile sizes of the search kernels, chosen to fit a TPU v5e's VMEM.

Interpret mode accepts any tile; the chip's compiler refuses a kernel
whose scoped VMEM passes its limit. Each kernel wrapper takes its
default ``(tile_p, tile_q)`` from here, and ``tests/test_tpu_compile.py``
compiles every kernel for a described v5e at the tiles chosen for the
shapes ``chip_smoke.py`` serves.
"""

from __future__ import annotations


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


#: Scoped VMEM a tile choice may plan for: three quarters of the 16 MiB
#: a TPU v5e's compiler grants one Pallas kernel by default, the rest
#: left for the compiler's own staging.
VMEM_BUDGET = 12 << 20

# Per-tile VMEM of the fused and ADC kernels, fitted to the scoped sizes
# the v5e compiler reports for them. The k-selection keeps (TQ, 1) columns, each
# padded to 128 lanes (512 B per query row), two per rank; past ~32 ranks
# the compiler reuses them. A query row carries ~4 KiB of further
# lane-padded state, and the masked distance tile and its temporaries
# take ~8 B per (query, point) pair.
_VMEM_PER_QUERY_RANK = 1024
_VMEM_RANKS_HELD = 32
_VMEM_PER_QUERY = 4096
_VMEM_PER_PAIR = 8
_TILE_Q = (256, 128)
_TILE_P = (2048, 1024, 512, 256, 128)


def vmem_estimate(tile_p: int, tile_q: int, *, k: int, p_row_bytes: int,
                  q_row_bytes: int) -> int:
    """Planned scoped VMEM bytes of one kernel launch.

    ``p_row_bytes``/``q_row_bytes``: VMEM per point/query row of the
    tiles, double-buffered input blocks and per-row working set included.
    """
    ranks = min(k, _VMEM_RANKS_HELD)
    return (tile_p * p_row_bytes + tile_q * q_row_bytes
            + tile_q * (_VMEM_PER_QUERY + ranks * _VMEM_PER_QUERY_RANK)
            + tile_p * tile_q * _VMEM_PER_PAIR)


def choose_tiles(P: int, Q: int, *, k: int, p_row_bytes: int,
                 q_row_bytes: int) -> tuple[int, int]:
    """The largest ``(tile_p, tile_q)`` within :data:`VMEM_BUDGET`.

    Tiles never exceed the lane-rounded operand, so a small scan is not
    padded up to a big tile; among equal areas the taller query tile
    wins, since every query tile streams all point tiles.

    Raises:
      ValueError: not even a 128 x 128 tile fits.
    """
    fits = [
        (tp * tq, tq, tp)
        for tq in _TILE_Q if tq <= max(128, _round_up(Q, 128))
        for tp in _TILE_P if tp <= max(128, _round_up(P, 128))
        if vmem_estimate(tp, tq, k=k, p_row_bytes=p_row_bytes,
                         q_row_bytes=q_row_bytes) <= VMEM_BUDGET
    ]
    if not fits:
        raise ValueError(f"no kernel tile fits VMEM at {k=}")
    _, tq, tp = max(fits)
    return tp, tq


def dense_tiles(P: int, Q: int, *, k: int, d: int, itemsize: int
                ) -> tuple[int, int]:
    """:func:`choose_tiles` for the dense kernel (``d``-wide rows)."""
    row = 2 * d * itemsize  # double-buffered input block
    return choose_tiles(P, Q, k=k, p_row_bytes=row, q_row_bytes=row)


def adc_tiles(P: int, Q: int, *, k: int, m: int, n_centers: int
              ) -> tuple[int, int]:
    """:func:`choose_tiles` for the ADC kernels (adcscan, fusedadc): a
    point row is an int32 code row padded to 128 lanes, double-buffered,
    plus its m one-hot (C,) rows; a query row is its double-buffered
    (m * C) f32 LUT."""
    return choose_tiles(
        P, Q, k=k, p_row_bytes=2 * 512 + 4 * m * n_centers,
        q_row_bytes=2 * 4 * m * n_centers,
    )


def l2topk_tiles(P: int, Q: int) -> tuple[int, int]:
    """``(tile_p, tile_q)`` of the per-wave l2topk kernel: lane-aligned,
    at most 512 x 256 (its running table is (TQ, k); compiled at k=20)."""
    return min(512, _round_up(P, 128)), min(256, _round_up(Q, 128))
