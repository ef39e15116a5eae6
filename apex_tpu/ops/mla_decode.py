"""Latent attention's decode in its absorbed form: one kernel that reads
each lane's cached latent rows once, as keys and as values.

A latent-attention layer (DeepSeek-V2/V3's MLA, ``models/decoder.py``
``LatentAttention``) caches one row a token, ``[c_kv | k_r | 0]``: the
normed latent ``c_kv`` (``kv_lora_rank`` wide), the rotated key shared
by every head ``k_r`` (``qk_rope_head_dim``), and zeros up to a whole
number of 128-lane tiles. Folding the key's up-projection into the
query (``q_lat = q_nope W_UK^T``) and the value's into the output
leaves every head scoring ``[q_lat | q_rope | 0] . row`` against the
same rows, its value being ``c_kv`` itself: attention with ONE key of
the row's width and one value of ``c_kv``'s, for all the heads.

``mla_decode(q, rows, lens)`` computes, for each lane ``i`` and head
``h``, ``softmax_j(scale q[i, h] . rows[i, 0, j]) rows[i, 0, j, :v]``
over the keys ``j < lens[i]``, in float32. The kernel walks each lane's
rows in blocks of ``block_k`` with an online softmax; the lane's heads
are the rows of one tile, so a block of latent rows is read from HBM
once and serves as keys and as values of every head. It reads every
row it is handed, dead ones past ``lens`` included, which the mask
drops (``benchmark/costs_glm.py`` prices every row handed).

Entered through one module-level ``jax.jit`` (:func:`_mla_decode`), so
that a serving program, unrolled over its layers, lowers one kernel
body a shape and not one a call site (``ops/moe_grouped.py``'s rule).

Off the TPU the ``impl`` convention holds (``apex_tpu/_backend.py``):
``xla`` is the plain ``jnp`` form, ``interpret`` runs the kernel
interpreted. The kernel is named ``mla_decode`` in the compiled
program and in a device trace.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu._backend import resolve_impl
from apex_tpu.ops.attention import _pick_block

#: the latent rows a grid step reads: 1024 rows of 640 bf16 are 1.3 MB,
#: double-buffered
BLOCK_K = 1024
_NEG = -1e30


def _xla(q, rows, lens, scale, value_dim):
    f32 = jnp.float32
    s = jnp.einsum("bhw,blw->bhl", q.astype(f32), rows[:, 0].astype(f32),
                   precision=lax.Precision.HIGHEST) * scale
    live = jnp.arange(rows.shape[2])[None, None, :] < lens[:, None, None]
    p = jax.nn.softmax(jnp.where(live, s, _NEG), axis=-1)
    p = jnp.where(live, p, 0.0)
    return jnp.einsum("bhl,blv->bhv", p, rows[:, 0, :, :value_dim].astype(f32),
                      precision=lax.Precision.HIGHEST)


def _kernel(lens_ref, q_ref, rows_ref, out_ref, m_ref, l_ref, acc_ref, *,
            scale, value_dim, block_k):
    lane, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, _NEG, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    q = q_ref[0]                                        # (heads, W)
    keys = rows_ref[0, 0]                               # (block_k, W)
    s = lax.dot_general(q, keys, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
    col = j * block_k + lax.broadcasted_iota(jnp.int32, s.shape, 1)
    live = col < lens_ref[lane]
    s = jnp.where(live, s, _NEG)
    m_prev = m_ref[:, :1]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(live, jnp.exp(s - m_new), 0.0)
    l_ref[...] = alpha * l_ref[...] + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
        p.astype(keys.dtype), keys[:, :value_dim],
        preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(j == pl.num_programs(1) - 1)
    def _done():
        l = l_ref[:, :1]
        out_ref[0] = acc_ref[...] / jnp.where(l > 0, l, 1.0)


@functools.partial(jax.jit, static_argnames=("scale", "value_dim", "impl"))
def _mla_decode(q, rows, lens, *, scale: float, value_dim: int, impl: str):
    """The one entry point (module docstring)."""
    lens = lens.astype(jnp.int32)
    if impl == "xla":
        return _xla(q, rows, lens, scale, value_dim)
    b, heads, width = q.shape
    length = rows.shape[2]
    block_k = _pick_block(length, BLOCK_K)
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, value_dim=value_dim,
                          block_k=block_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, length // block_k),
            in_specs=[
                pl.BlockSpec((1, heads, width), lambda i, j, n: (i, 0, 0)),
                pl.BlockSpec((1, 1, block_k, width),
                             lambda i, j, n: (i, 0, j, 0))],
            out_specs=pl.BlockSpec((1, heads, value_dim),
                                   lambda i, j, n: (i, 0, 0)),
            scratch_shapes=[pltpu.VMEM((heads, 128), jnp.float32),
                            pltpu.VMEM((heads, 128), jnp.float32),
                            pltpu.VMEM((heads, value_dim), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, heads, value_dim), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=impl == "interpret",
        name="mla_decode",
    )(lens, q, rows.astype(q.dtype))


def mla_decode(q, rows, lens, *, value_dim: int, scale: float, impl=None):
    """Each lane's heads against its latent rows, absorbed form.

    ``q`` (b, heads, W): each head's ``[q_lat | q_rope | 0]``, as wide
    as a row; ``rows`` (b, 1, L, W) the lane's gathered latent rows
    (``ops/kv_gather.py`` out of a latent pool, the token's own row in
    its slot); ``lens`` (b,) how many of them are keys. Returns (b,
    heads, ``value_dim``) float32: the softmax over ``scale q . row``
    times the rows' first ``value_dim`` columns (``c_kv``). ``impl`` as
    ``apex_tpu/_backend.py`` resolves it."""
    return _mla_decode(q, rows, lens, scale=float(scale),
                       value_dim=int(value_dim), impl=resolve_impl(impl))


__all__ = ["mla_decode"]
