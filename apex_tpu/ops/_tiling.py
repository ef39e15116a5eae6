"""Shared Mosaic-legal row-tile selection for row-wise kernels.

One source of truth for the tiling rule every row-tiled kernel
(softmax family, xentropy, layer/rms norm) must satisfy on TPU: the
last-two block dims must be divisible by (8, 128) or equal the array
dims (the Pallas TPU lowering refuses anything else). A returned tile
divides ``rows``, is a multiple of 8 (or equals ``rows``), and keeps
the (tile, cols) fp32 block inside the VMEM ``budget``; ``None`` means
no legal tile exists — callers fall back to their XLA paths (ragged
row counts, huge trailing dims, empty inputs).
"""

from __future__ import annotations

from typing import Optional


def row_tile(rows: int, cols: int, cap: int = 256,
             budget: int = 2 * 1024 * 1024) -> Optional[int]:
    from apex_tpu.ops.mosaic_limits import MAX_BLOCK_BYTES, block_ok

    if rows <= 0:
        return None
    # clamp a caller-supplied budget below the block size the compiler
    # refuses (ops/mosaic_limits.py): a tuner or caller can never push
    # a selector past it
    budget = min(budget, MAX_BLOCK_BYTES - cols * 4)
    want = min(cap, budget // max(cols * 4, 1))
    if rows <= want:
        return rows          # single block == full dim, always legal
    tile = (want // 8) * 8   # tiles must be sublane-aligned
    while tile >= 8:
        if rows % tile == 0:
            assert block_ok(tile, cols)
            return tile
        tile -= 8
    return None


__all__ = ["row_tile"]
