"""Gather one layer of the paged pools into the flash kernel's layout.

The paged pool keeps a block as ``(block_size, kv_heads, head_dim)``
rows (serving/kv_cache.py); the flash kernel reads keys as
``(batch, kv_heads, L, head_dim)``. Between the two lies a gather
through the block table and a swap of the block's two leading dims.
XLA needs a pass over the context for each (and, for a layer scanned
out of the pools, a third that copies the layer's whole slice first);
the kernel here does it in one: the blocks named by the table are
copied from the pool, which stays in HBM, into VMEM by DMA, a group of
them a grid step with the next group's copies in flight behind the
current one, turned there, and written out as the output block. The
pool is an operand as a whole and ``layer`` picks the slice inside the
kernel, so a layer scan that closes over the pools slices nothing out
of them.

It copies as far as each lane's keys go and no further. A table is as
wide as the largest reservation's power-of-two bucket, trash-padded
and filled out with dummy lanes, and the attention mask hides every
position at or past a lane's length: for a dead block no copy is
started or waited for, nothing is turned, and a grid step past the
lane's last live group revisits that group's output block, which is
then written back once and the rest of the lane's row not at all. The
results alias the arrays the caller hands in, so what is not written
keeps their bytes. That makes the tail's content the caller's to
answer for, and it has to be finite (``kv_gather``'s docstring).

The result is pinned to HBM. Left to itself the TPU compiler keeps a
context that fits (8 lanes of 1024 positions: 34 MB) in the v5e's
128 MiB of VMEM, and the attention kernel then reads it faster than
HBM could deliver it: 3.6 ms a decode step faster at that size (PR
27's chip runs), and 106% of the HBM roofline that
``benchmark/costs.py`` holds the attention kernel to, which the
benchmark refuses as an impossible reading. ``PERF.md`` section 7 has
what it takes to let go of the pin.

The kernel is named ``kv_gather`` in the compiled program (the trace
reader's ``^attention`` must not take it for the attention kernel).
"""

from __future__ import annotations

import functools

import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu._backend import interpret_flag, resolve_impl
from apex_tpu.ops.attention import _pick_block


def _pack(pool) -> int:
    """Rows of the pool's type in one 32-bit sublane."""
    return max(1, 4 // pool.dtype.itemsize)


def _one_head(pool) -> bool:
    """Whether the pool has one head whose blocks the kernel copies as
    ``(block_size, head_dim)``: the TPU compiler lays such a pool out
    with its unit head dimension outside the block's rows (a latent
    pool, ``serving/kv_cache.py``, is one), so those are whole tiles
    where ``block_size`` fills a tile's sublanes."""
    bs, kv, d = pool.shape[2:]
    return kv == 1 and d % 128 == 0 and bs % (8 * _pack(pool)) == 0


def _whole_tiles(pool) -> bool:
    """Whether one block's ``(kv_heads, head_dim)`` rows are whole
    tiles of the pool as it lies in HBM, which the kernel's copy of a
    block needs: every lane of a row, and all the rows packed into one
    sublane (two of bf16); or a pool of one head (:func:`_one_head`)."""
    kv, d = pool.shape[3:]
    return _one_head(pool) or (d % 128 == 0 and kv % _pack(pool) == 0)


def _gather_xla(pool, layer, tables):
    # the layer's slice first: indexing the pool by (layer, block) at
    # once makes the TPU compiler re-lay the whole pool out, every layer
    g = lax.dynamic_index_in_dim(pool, layer, 0, keepdims=False)
    g = g.at[tables].get(mode="promise_in_bounds")   # (b, w, bs, kv, d)
    b, w, bs, kv, d = g.shape
    return g.transpose(0, 3, 1, 2, 4).reshape(b, kv, w * bs, d)


def live_blocks(lens, block_size, width):
    """How many blocks of a lane's ``width``-block table hold keys,
    ``lens`` positions of it being written: the blocks the kernel
    copies. The one statement of the rule, for arrays on the device
    (``kv_gather``) and numpy's on the host (the engine's count)."""
    return (-(-lens // block_size)).clip(0, width)


def _live_groups(blocks, group):
    """How many groups of a lane's table the kernel writes: those that
    hold a live block, and the first one always (the output block a
    lane's dead grid steps revisit has to be some block of the lane).
    ``lax`` calls here and in the kernel, not operators: every program
    traces them, and through ``jnp``'s operators on traced scalars this
    kernel took the host twice as long to lower."""
    return lax.max(lax.div(lax.add(blocks, group - 1), group), 1)


def _kernel(tbl_ref, lyr_ref, live_ref, *refs, group, pools):
    # refs: the ``pools`` pools, the arrays the results alias, the
    # results, a VMEM buffer a pool and the semaphores. Grid step n
    # gathers blocks tbl[n * group : (n + 1) * group] of the flattened
    # table: lane n // (w / group), its next `group` blocks, as many of
    # them as are live (live_ref[lane] blocks from the lane's first).
    # What this kernel does not write of a result keeps the bytes of
    # the array it aliases.
    # Loops, not unrolled copies: tracing an operation costs the host
    # more than a loop costs the chip, and every program traces this
    # body twice (the layer scan does).
    pool_refs = refs[:pools]
    outs = refs[2 * pools:3 * pools]
    bufs, sem = refs[3 * pools:4 * pools], refs[4 * pools]
    lane, j, groups = pl.program_id(0), pl.program_id(1), pl.num_programs(1)
    n = lax.add(lax.mul(lane, groups), j)
    ahead = lax.add(n, 1)
    last = lax.mul(pl.num_programs(0), groups)
    layer, bs = lyr_ref[0], bufs[0].shape[2]

    def live(lane, j):
        return lax.clamp(0, lax.sub(live_ref[lane], lax.mul(j, group)), group)

    def each_copy(step, slot, count, act):
        first = lax.mul(step, group)

        def body(t, carry):
            blk = tbl_ref[lax.add(first, t)]
            for pool, buf, which in zip(pool_refs, bufs, range(pools)):
                act(pltpu.make_async_copy(pool.at[layer, blk],
                                          buf.at[slot, t],
                                          sem.at[which, slot]))
            return carry
        lax.fori_loop(0, count, body, None)

    slot, mine = lax.rem(n, 2), live(lane, j)

    @pl.when(n == 0)
    def _first():
        each_copy(n, 0, mine, lambda c: c.start())

    # step n + 1's copies by step n + 1's liveness: what is started
    # here is what that step waits for
    @pl.when(ahead < last)
    def _ahead():
        each_copy(ahead, lax.rem(ahead, 2),
                  live(lax.div(ahead, groups), lax.rem(ahead, groups)),
                  lambda c: c.start())

    each_copy(n, slot, mine, lambda c: c.wait())

    def at(t):
        return pl.ds(pl.multiple_of(lax.mul(t, bs), bs), bs)

    def turn(t, carry):
        # (block_size, kv, d) -> (kv, block_size, d): the block's rows
        # of one head become contiguous keys of that head; a block of
        # a one-head pool (block_size, d) is its head's already
        for buf, out in zip(bufs, outs):
            if buf.ndim == 4:
                out[0, 0, at(t), :] = buf[slot, t]
            else:
                out[0, :, at(t), :] = jnp.swapaxes(buf[slot, t], 0, 1)
        return carry

    def blank(t, carry):
        for out in outs:
            out[0, :, at(t), :] = jnp.zeros(
                (out.shape[1], bs, out.shape[3]), out.dtype)
        return carry

    # a step past the lane's live groups revisits the last of them
    # (out_spec) and leaves it as it is; the last live group's dead
    # blocks would go out as whatever VMEM held
    lax.fori_loop(0, mine, turn, None)
    owns = j < _live_groups(live_ref[lane], group)
    lax.fori_loop(mine, lax.select(owns, group, 0), blank, None)


def _uses_kernel(pool, impl) -> bool:
    """Whether ``kv_gather`` runs the kernel for this pool (and so
    writes into the arrays it is handed), not XLA's gather."""
    impl = resolve_impl(impl)
    return impl == "interpret" or (impl == "pallas" and _whole_tiles(pool))


def _zero(*outs):
    for out in outs:
        out[...] = jnp.zeros(out.shape, out.dtype)


def zero_context(pools, tables, *, impl=None):
    """What the first ``kv_gather`` of a program call writes into: one
    array of zeros a pool (K and V, or a latent pool's rows alone) in
    the gathered layout for ``tables``; nothing, ``()``, where XLA
    gathers (it writes into nothing). ``pools`` are read for their
    count, shape and type alone. From a kernel,
    because its results are pinned to HBM as the gather's are: a layer
    scan that starts from XLA's own zeros keeps the arrays it carries
    in VMEM and copies them out and back around every layer's gather.
    Under a mesh it needs an ``on_shards`` island, as the gather does."""
    pool, n = pools[0], len(pools)
    if not _uses_kernel(pool, impl):
        return ()
    _, _, bs, kv, d = pool.shape
    b, w = tables.shape
    # blocks of about 2 MB, a power of two rows
    rows = _pick_block(w * bs, max(bs, 1 << (
        2**21 // (kv * d * pool.dtype.itemsize)).bit_length() - 1))
    spec = pl.BlockSpec((1, kv, rows, d), lambda i, j: (i, 0, j, 0))
    out = pltpu.HBM((b, kv, w * bs, d), pool.dtype)
    return tuple(pl.pallas_call(
        _zero,
        grid=(b, w * bs // rows),
        out_specs=[spec] * n,
        out_shape=[out] * n,
        interpret=interpret_flag(resolve_impl(impl)),
        name="zero_context",
    )())


def kv_gather(pools, layer, tables, lens, into=(), *, impl=None,
              group: int = 16):
    """``pool[layer, tables]`` for each of ``pools`` (K and V, or a
    latent pool alone: ``serving/kv_cache.py``), as a tuple of
    ``(batch, kv_heads, width * block_size, head_dim)`` arrays, as far
    as each lane's keys go.

    The pools (layers, blocks, block_size, kv_heads, head_dim), of one
    shape;
    ``layer`` an int32 scalar (traced inside a layer scan); ``tables``
    (batch, width) int32 block ids, every one of them a block of the
    pool (nothing is clamped or filled); ``lens`` (batch,) int32, how
    many positions of each lane's table hold keys some query may see.
    ``group`` blocks are gathered a grid step.

    Pure data movement. The *live* blocks of lane ``i``, the first
    ``ceil(lens[i] / block_size)`` of its table, come back bitwise.
    The rest is zeros or stale K/V and has to be masked by the caller:
    the kernel neither reads nor writes the groups past a lane's last
    live one (a lane's first group counts as live whatever its length),
    so they keep the bytes of ``into``, the arrays the results
    are written into in place (``zero_context``'s where none is given;
    a caller with several layers makes them itself and hands each
    layer the last one's results, ``models/cached_attention.py``), and
    it zeroes the dead blocks of the last live group. Never-written
    memory must not come back: a masked key still meets the attention
    kernel's ``p @ v`` as ``0 x v``, and a NaN there poisons the row.

    Pools whose blocks are not whole tiles in HBM (a head size that
    is no multiple of 128, say) are gathered by XLA, in two passes and
    through the whole table (``lens`` and ``into`` unused): the
    compiler refuses the kernel's copies of such blocks.
    """
    impl = resolve_impl(impl)
    layer = jnp.asarray(layer, jnp.int32)
    pools = tuple(pools)
    pool = pools[0]
    if not _uses_kernel(pool, impl):
        return tuple(_gather_xla(p, layer, tables) for p in pools)
    _, _, bs, kv, d = pool.shape
    b, w = tables.shape
    group = _pick_block(w, group)
    out = pltpu.HBM((b, kv, w * bs, d), pool.dtype)
    if not into:
        into = zero_context(pools, tables, impl=impl)
    blocks = live_blocks(lens.astype(jnp.int32), bs, w)
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    out_spec = pl.BlockSpec(
        (1, kv, group * bs, d),
        lambda i, j, tbl, lyr, blocks: (
            i, 0, lax.min(j, lax.sub(_live_groups(blocks[i], group), 1)), 0))
    if _one_head(pool):
        # blocks as (block_size, d): the unit head dimension lies
        # outside the rows in HBM, so these are the same bytes
        pools = tuple(p.reshape(*p.shape[:3], d) for p in pools)
        buf = pltpu.VMEM((2, group, bs, d), pool.dtype)
    else:
        buf = pltpu.VMEM((2, group, bs, kv, d), pool.dtype)
    n = len(pools)
    return tuple(pl.pallas_call(
        functools.partial(_kernel, group=group, pools=n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, w // group),
            in_specs=[anywhere] * (2 * n),
            out_specs=[out_spec] * n,
            scratch_shapes=[buf] * n + [pltpu.SemaphoreType.DMA((n, 2))]),
        out_shape=[out] * n,
        # operands count the prefetched scalars: into -> the results
        input_output_aliases={3 + n + i: i for i in range(n)},
        # the next group's copies are started a step ahead: in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret_flag(impl),
        name="kv_gather",
    )(tables.reshape(-1), layer.reshape(1), blocks, *pools, *into))


__all__ = ["kv_gather", "live_blocks", "zero_context"]
