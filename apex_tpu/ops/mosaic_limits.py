"""The block size the TPU compiler is known to refuse — encoded, not prose.

A kernel's blocks are double-buffered in VMEM, of which a kernel gets
16 MiB unless it asks for more. One block of 4 MiB (at its compute
itemsize) therefore never fits once a kernel streams two operands:
compiled for a described v5e with jax 0.9.0 / libtpu 0.0.34, a
fused-engine tile of 8192 x 128 fp32, a layer-norm tile of 1024 x 1024
fp32 and one of 256 x 4096 fp32 are all refused with
``RESOURCE_EXHAUSTED ... memory space vmem``. The tile selectors and
the tuner's candidate lists consult this limit so that they never
propose such a block.

It is a bound, not a promise: a kernel that holds more blocks is
refused earlier (layer-norm backward at hidden 4096 is refused at a
2 MiB tile, flash attention at 2048 x 2048 blocks by its fp32 score
block), and the compiler's own error names the allocation. An earlier
cap of 1024 sublanes per block is gone: the same compiler takes
2048 x 128 and 4096 x 128 engine tiles.
"""

from __future__ import annotations

# one block of this many bytes cannot be double-buffered beside a
# second operand inside the 16 MiB a kernel gets; stay strictly below
MAX_BLOCK_BYTES = 4 * 1024 * 1024


def block_ok(rows: int, cols: int, itemsize: int = 4) -> bool:
    """True iff a (rows, cols) block at ``itemsize`` is under the size
    the compiler is known to refuse."""
    return rows * cols * itemsize < MAX_BLOCK_BYTES


def max_rows(cols: int, itemsize: int = 4) -> int:
    """Largest admissible sublane count for a block with ``cols``
    lanes (multiple of 8, >= 8)."""
    rows = (MAX_BLOCK_BYTES - 1) // max(cols * itemsize, 1)
    return max(8, (rows // 8) * 8)


def check_block(rows: int, cols: int, itemsize: int = 4,
                what: str = "block") -> None:
    """Raise before a block the compiler refuses ever reaches it."""
    if not block_ok(rows, cols, itemsize):
        raise ValueError(
            f"{what} ({rows}, {cols}) @ {itemsize}B is >= "
            f"{MAX_BLOCK_BYTES} bytes, which the TPU compiler refuses "
            f"(VMEM) — the largest admissible row count for {cols} "
            f"lanes is {max_rows(cols, itemsize)}.")


__all__ = ["MAX_BLOCK_BYTES", "block_ok", "max_rows", "check_block"]
