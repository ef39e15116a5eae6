"""The held experts' grouped product: ``lax.ragged_dot`` as one Pallas
kernel, entered through one jit.

``moe_grouped(rows, w, sizes)`` computes what ``lax.ragged_dot(rows,
w, sizes)`` does for ``rows (m, k)`` sorted by group, ``w (g, k, n)``
and ``sizes (g,)`` int32: group ``i``'s rows times ``w[i]``, bf16
operands (or whatever the caller hands in) under float32 accumulation,
the result in the operands' dtype. Rows past ``sum(sizes)`` are not
computed and hold whatever the result's memory held: the caller drops
them (``moe/held.py`` ``grouped_experts`` masks them by a select).

On a TPU the compiler's own grouped matmul behind ``ragged_dot`` ran at
about 17% of its bytes' bound at a Mellum2 chunk's shapes (8192 pairs
over 64 experts of 2304 x 896: 1.84 ms a product for 0.32 ms of
weights; PERF.md section 5). The kernel here is megablox's scheme:

- the grid walks (column tile of ``w``, visit), a *visit* being one row
  tile of one group; the visits are the row tiles the groups cover, in
  group order, a tile that two groups share visited once by each. The
  visit -> (group, row tile) map and the group offsets are computed in
  the program from ``sizes`` and handed over as prefetched scalars, and
  the grid's second extent is the number of visits, known only on the
  device: an empty group costs nothing, and nothing past ``sum(sizes)``
  is walked;
- a block of ``w`` is a whole column tile, every ``k`` at once, so the
  visits of one group ask for the same block of weights one after
  another and the pipeline fetches it once: each touched expert's
  weights are read once a call (once a column tile);
- a visit writes the rows of its tile that are its group's and leaves
  the others to the visits before and after it, which see the same
  output block.

The row tile follows the call's static shape: 128 rows, or all of them
in 16-row steps where there are fewer (Trinity's decode call: 64 pairs
over 32 held experts, one tile each touched expert visits once). The
MXU loads a group's weights once a visit whatever the rows, so the
fewer visits the better up to the MXU's own 128 rows.

*One jit, one kernel body a shape.* A serving program is unrolled
(``models/decoder.py``), and a bare ``pallas_call`` is traced and
lowered to Mosaic at every call site, three products times every
expert layer times every program, again on every warm start: jax
lowers a program to compute its compile cache key (40-80 ms a site at
Mellum2's chunk shapes on a sandbox's CPU; PR 38 paid 10-19 s of
``setup_s`` a cell for it). Entered through :func:`_moe_grouped`, a
module-level ``jax.jit``, the call sites of one shape share one traced
body and one lowered function: a program lowers one kernel body per
distinct ``(m, k, n, g)``, two in an expert layer (gate and up share
one), ~2.7 s a warm start of Mellum2's cell on the chip's host (PERF.md
section 5).

Off the TPU the ``impl`` convention holds (``apex_tpu/_backend.py``):
``xla`` is ``lax.ragged_dot`` itself, the reference; ``interpret`` runs
the kernel interpreted. The kernel is named ``moe_grouped`` in the
compiled program and in a device trace.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu._backend import resolve_impl

#: the most rows a visit works on: the MXU's width on a v5e
ROW_TILE = 128
#: the bytes of one block of weights (a column tile, every ``k``); it
#: is double-buffered
WEIGHT_BLOCK_BYTES = 8 * 1024 * 1024
#: what the kernel asks of VMEM: two weight blocks, two row blocks and
#: two output blocks at the widest (Trinity's 3072 x 1024: ~15 MB)
VMEM_LIMIT_BYTES = 48 * 1024 * 1024


def row_tile(m: int) -> int:
    """Rows a visit: ``ROW_TILE``, or ``m`` rounded up to 16 (a bf16
    tile's rows) where that is fewer."""
    return min(ROW_TILE, -(-m // 16) * 16)


def column_tile(k: int, n: int, itemsize: int) -> int:
    """Columns of ``w`` a block: all ``n`` where the block fits
    ``WEIGHT_BLOCK_BYTES``, else the widest multiple of 128 that
    divides ``n`` and fits, else 128."""
    for tn in (n, *range(n // 128 * 128, 0, -128)):
        if (tn == n or n % tn == 0) and k * tn * itemsize <= \
                WEIGHT_BLOCK_BYTES:
            return tn
    return 128


def visits(sizes, m: int, tm: int):
    """The walk over the row tiles the groups cover: ``(offsets (g+1,),
    group (v,), tile (v,), count)`` with ``v = m/tm (rounded up) + g -
    1`` the most visits there can be; visit ``i < count`` is row tile
    ``tile[i]`` for group ``group[i]``, in group order, and an empty
    group has none."""
    g = sizes.shape[0]
    tiles_m = -(-m // tm)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    first = starts // tm
    spans = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    last_visit = jnp.cumsum(spans)                          # (g,)
    at = jnp.arange(tiles_m + g - 1, dtype=jnp.int32)
    group = jnp.minimum((at[:, None] >= last_visit[None, :]).sum(1),
                        g - 1).astype(jnp.int32)
    tile = first[group] + at - (last_visit - spans)[group]
    return (offsets, group, jnp.clip(tile, 0, tiles_m - 1).astype(jnp.int32),
            last_visit[-1])


def _kernel(offsets_ref, group_ref, tile_ref, rows_ref, w_ref, out_ref):
    """One visit: the group's rows of one row tile times one column
    tile of its weights; the tile's other rows are left as they are."""
    i = pl.program_id(1)
    g = group_ref[i]
    tm = rows_ref.shape[0]
    row = tile_ref[i] * tm + lax.broadcasted_iota(jnp.int32, out_ref.shape,
                                                  0)
    mine = (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])
    prod = jnp.dot(rows_ref[...], w_ref[...],
                   preferred_element_type=jnp.float32)
    out_ref[...] = jnp.where(mine, prod,
                             out_ref[...].astype(jnp.float32)
                             ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("impl",))
def _moe_grouped(rows, w, sizes, impl: str):
    """The one entry point (module docstring): jitted, so that every
    call site of one shape in a program shares one body."""
    if impl == "xla":
        return lax.ragged_dot(rows, w, sizes)
    m, k = rows.shape
    n = w.shape[2]
    tm = row_tile(m)
    tn = column_tile(k, n, w.dtype.itemsize)
    offsets, group, tile, count = visits(sizes.astype(jnp.int32), m, tm)
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            # a call with no held pair still walks one visit, whose
            # group owns no row of it: nothing is written
            grid=(-(-n // tn), jnp.maximum(count, 1)),
            in_specs=[
                pl.BlockSpec((tm, k), lambda j, i, o, gr, t: (t[i], 0)),
                pl.BlockSpec((None, k, tn),
                             lambda j, i, o, gr, t: (gr[i], 0, j)),
            ],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda j, i, o, gr, t: (t[i], j))),
        out_shape=jax.ShapeDtypeStruct((m, n), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=impl == "interpret",
        name="moe_grouped",
    )(offsets, group, tile, rows, w)


def moe_grouped(rows, w, sizes, *, impl=None):
    """``lax.ragged_dot(rows, w, sizes)``: ``rows (m, k)`` sorted by
    group, ``w (g, k, n)`` of the same dtype, ``sizes (g,)`` int32 ->
    ``(m, n)`` in that dtype; rows past ``sum(sizes)`` are undefined.
    ``impl`` as ``apex_tpu/_backend.py`` resolves it (the kernel on a
    TPU)."""
    return _moe_grouped(rows, w, sizes, impl=resolve_impl(impl))


__all__ = ["moe_grouped", "row_tile", "column_tile", "visits"]
