"""Latent attention's decode in its absorbed form: one kernel that reads
each lane's live latent rows out of the pool, once, as keys and as
values.

A latent-attention layer (DeepSeek-V2/V3's MLA, ``models/decoder.py``
``LatentAttention``) caches one row a token, ``[c_kv | k_r | 0]``: the
normed latent ``c_kv`` (``kv_lora_rank`` wide), the rotated key shared
by every head ``k_r`` (``qk_rope_head_dim``), and zeros up to a whole
number of 128-lane tiles. Folding the key's up-projection into the
query (``q_lat = q_nope W_UK^T``) and the value's into the output
leaves every head scoring ``[q_lat | q_rope | 0] . row`` against the
same rows, its value being ``c_kv`` itself: attention with ONE key of
the row's width and one value of ``c_kv``'s, for all the heads.

``mla_decode(q, pool, layer, tables, lens, row_new)`` computes, for
each lane ``i`` and head ``h``, ``softmax_j(scale q[i, h] . key_j)
key_j[:v]`` in float32 over the keys ``j <= lens[i]``: the lane's
cached rows ``pool[layer, tables[i]]`` below ``lens[i]``, then the
token's own row ``row_new[i]`` as key ``lens[i]`` (it reaches the pool
only after the layers have run).

The kernel takes the pool whole, where it lies in HBM, and the block
tables and lengths as prefetched scalars. A grid step is a lane: it
walks the lane's live blocks (``ops/kv_gather.py`` ``live_blocks``,
the rule the engine counts by) in groups of ``GROUP``, copying a
group's blocks into one of two VMEM buffers by DMA while the group
before is scored out of the other; the last group of a lane starts the
next lane's first. A block is one copy of 20 KB, so starting the
copies on the scalar core sets the pace: their loop is unrolled
(``UNROLL``), and a whole group is waited for at once. A block is read once and
serves as keys and as values of every head; no copy is started and no
step taken for a block past a lane's length. Rows past ``lens`` inside
the last live block are read and masked; what a group's buffer holds
past its live blocks is zeroed, never scored as whatever VMEM held.

Entered through one module-level ``jax.jit`` (:func:`_mla_decode`), so
that a serving program, unrolled over its layers, lowers one kernel
body a shape and not one a call site (``ops/moe_grouped.py``'s rule).

Off the TPU the ``impl`` convention holds (``apex_tpu/_backend.py``):
``xla`` is the plain ``jnp`` form, the whole table gathered,
``interpret`` runs the kernel interpreted. The kernel is named
``mla_decode`` in the compiled program and in a device trace.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu._backend import interpret_flag, resolve_impl
from apex_tpu.ops.kv_gather import _live_groups, live_blocks

#: the blocks a group copies: 128 blocks of 16 rows of 640 bf16 are
#: 2.6 MB, double-buffered
GROUP = 128
#: copies started (or waited for) an iteration of their loop: a 20 KB
#: block's copy costs the scalar core about what its loop's turn does
UNROLL = 8
_NEG = -1e30


def _xla(q, pool, layer, tables, lens, row_new, scale, value_dim):
    f32 = jnp.float32
    b = tables.shape[0]
    rows = lax.dynamic_index_in_dim(pool, layer, 0, keepdims=False)[tables]
    keys = jnp.concatenate(
        [rows.reshape(b, -1, rows.shape[-1]), row_new.reshape(b, 1, -1)],
        axis=1).astype(f32)
    # the cached rows below lens, and the own row after them
    slot = jnp.arange(keys.shape[1])
    live = (slot[None, :] < lens[:, None]) | (slot == keys.shape[1] - 1)
    keys = jnp.where(live[..., None], keys, 0.0)
    s = jnp.einsum("bhw,blw->bhl", q.astype(f32), keys,
                   precision=lax.Precision.HIGHEST) * scale
    p = jax.nn.softmax(jnp.where(live[:, None], s, _NEG), axis=-1)
    p = jnp.where(live[:, None], p, 0.0)
    return jnp.einsum("bhl,blv->bhv", p, keys[..., :value_dim],
                      precision=lax.Precision.HIGHEST)


def _kernel(tbl_ref, lyr_ref, lens_ref, live_ref, q_ref, pool_ref, row_ref,
            out_ref, buf, sem, slot_ref, m_ref, l_ref, acc_ref, *,
            scale, value_dim, group, width):
    # Grid step i is lane i: its live blocks, group by group, then its
    # own row. slot_ref holds the buffer its first group was copied
    # into (by the step before, or by the prologue). ``lax`` calls, not
    # ``jnp`` operators, on scalars: tracing is the host's set-up time
    # (``ops/kv_gather.py``).
    lane, lanes = pl.program_id(0), pl.num_programs(0)
    layer, bs = lyr_ref[0], pool_ref.shape[2]
    rows = group * bs

    def live(i, g):
        # the live blocks of lane i's group g
        return lax.clamp(0, lax.sub(live_ref[i], lax.mul(g, group)), group)

    def at(t):
        return pl.ds(pl.multiple_of(lax.mul(t, bs), bs), bs)

    def each_copy(i, g, slot, act):
        first = lax.add(lax.mul(i, width), lax.mul(g, group))

        def one(t):
            act(pltpu.make_async_copy(
                pool_ref.at[layer, tbl_ref[lax.add(first, t)]],
                buf.at[slot, at(t)], sem.at[slot]))

        def body(t, carry):
            one(t)
            return carry

        def unrolled(k, carry):
            for u in range(UNROLL):
                one(lax.add(lax.mul(k, UNROLL), u))
            return carry

        n = live(i, g)
        whole = lax.div(n, UNROLL)
        lax.fori_loop(0, whole, unrolled, None)
        lax.fori_loop(lax.mul(whole, UNROLL), n, body, None)

    @pl.when(lane == 0)
    def _prologue():
        slot_ref[0] = 0
        each_copy(0, 0, 0, lambda c: c.start())

    m_ref[...] = jnp.full(m_ref.shape, _NEG, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
    q = q_ref[0]                                        # (heads, W)
    base, groups = slot_ref[0], _live_groups(live_ref[lane], group)

    def update(m_prev, m_new, p, values):
        # the online softmax over one more set of keys: p their
        # exp(score - m_new), or 0 where masked
        alpha = jnp.exp(m_prev - m_new)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + values

    def step(g, carry):
        slot = lax.rem(lax.add(base, g), 2)
        ahead = lax.add(g, 1)

        # the next group's copies behind this one's: the lane's own,
        # or past its last the next lane's first
        @pl.when(lax.lt(ahead, groups))
        def _next():
            each_copy(lane, ahead, lax.sub(1, slot), lambda c: c.start())

        @pl.when(lax.bitwise_and(lax.eq(ahead, groups),
                                 lax.lt(lax.add(lane, 1), lanes)))
        def _next_lane():
            each_copy(lax.add(lane, 1), 0, lax.sub(1, slot),
                      lambda c: c.start())

        n = live(lane, g)
        full = lax.eq(n, group)

        # a whole group in one wait: a DMA semaphore counts bytes, and
        # the group's copies fill its buffer
        @pl.when(full)
        def _whole():
            pltpu.make_async_copy(buf.at[slot], buf.at[slot],
                                  sem.at[slot]).wait()

        @pl.when(lax.bitwise_not(full))
        def _each():
            each_copy(lane, g, slot, lambda c: c.wait())

        @pl.when(lax.gt(n, 0))
        def _score():
            def blank(t, carry):
                buf[slot, at(t), :] = jnp.zeros((bs, buf.shape[2]),
                                                buf.dtype)
                return carry
            lax.fori_loop(n, group, blank, None)
            keys = buf[slot].astype(q.dtype)            # (rows, W)
            s = lax.dot_general(q, keys, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
            col = lax.mul(g, rows) + lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            mask = col < lens_ref[lane]
            s = jnp.where(mask, s, _NEG)
            m_prev = m_ref[:, :1]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            update(m_prev, m_new, p, jnp.dot(
                p.astype(keys.dtype), keys[:, :value_dim],
                preferred_element_type=jnp.float32))
        return carry

    lax.fori_loop(0, groups, step, None)
    slot_ref[0] = lax.rem(lax.add(base, groups), 2)

    # the token's own row, key lens[lane]: its score on the VPU
    own = row_ref[0, 0].astype(jnp.float32)             # (1, W)
    s = jnp.sum(q.astype(jnp.float32) * own, axis=-1, keepdims=True) * scale
    m_prev = m_ref[:, :1]
    m_new = jnp.maximum(m_prev, s)
    p = jnp.exp(s - m_new)
    update(m_prev, m_new, p, p * own[:, :value_dim])
    out_ref[0] = acc_ref[...] / l_ref[:, :1]


@functools.partial(jax.jit, static_argnames=("scale", "value_dim", "impl"))
def _mla_decode(q, pool, layer, tables, lens, row_new, *, scale: float,
                value_dim: int, impl: str):
    """The one entry point (module docstring)."""
    lens = lens.astype(jnp.int32)
    layer = jnp.asarray(layer, jnp.int32)
    if impl == "xla":
        return _xla(q, pool, layer, tables, lens, row_new, scale, value_dim)
    b, heads, _ = q.shape
    _, _, bs, _, d = pool.shape
    w = tables.shape[1]
    group = min(GROUP, w)
    # blocks as (block_size, d): a pool of one head lies with its unit
    # head dimension outside the rows in HBM (``ops/kv_gather.py``
    # ``_one_head``), so these are the same bytes
    pool = pool.reshape(*pool.shape[:3], d)
    lane = lambda i, *_: (i, 0, 0)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, value_dim=value_dim,
                          group=group, width=w),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, heads, d), lane),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec((1, 1, 1, d), lambda i, *_: (i, 0, 0, 0))],
            out_specs=pl.BlockSpec((1, heads, value_dim), lane),
            scratch_shapes=[pltpu.VMEM((2, group * bs, d), pool.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SMEM((1,), jnp.int32),
                            pltpu.VMEM((heads, 128), jnp.float32),
                            pltpu.VMEM((heads, 128), jnp.float32),
                            pltpu.VMEM((heads, value_dim), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, heads, value_dim), jnp.float32),
        # a lane's last group starts the next lane's first: in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        # interpreted, the copies land when waited for and a wait takes
        # its bytes: a read before its wait, or a wait for bytes never
        # sent, shows off the chip
        interpret=(pltpu.InterpretParams(dma_execution_mode="on_wait")
                   if interpret_flag(impl) else False),
        name="mla_decode",
    )(tables.reshape(-1), layer.reshape(1), lens, live_blocks(lens, bs, w),
      q, pool, row_new.astype(q.dtype))


def mla_decode(q, pool, layer, tables, lens, row_new, *, value_dim: int,
               scale: float, impl=None):
    """Each lane's heads against its latent rows, absorbed form.

    ``q`` (b, heads, W): each head's ``[q_lat | q_rope | 0]``, as wide
    as a row; ``pool`` the latent pool (layers, blocks, block_size, 1,
    W) whole (``serving/kv_cache.py``), ``layer`` an int32 scalar;
    ``tables`` (b, width) int32 block ids, every one a block of the
    pool; ``lens`` (b,) how many of each lane's rows are written keys;
    ``row_new`` (b, 1, 1, W) the token's own row, key ``lens``. Returns
    (b, heads, ``value_dim``) float32: the softmax over ``scale q .
    key`` times the keys' first ``value_dim`` columns (``c_kv``).
    ``impl`` as ``apex_tpu/_backend.py`` resolves it."""
    return _mla_decode(q, pool, layer, tables, lens, row_new,
                       scale=float(scale), value_dim=int(value_dim),
                       impl=resolve_impl(impl))


__all__ = ["mla_decode"]
