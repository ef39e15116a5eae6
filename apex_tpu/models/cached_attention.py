"""Attention of new tokens over a paged cache: the ``kv_ctx`` hook's
body, shared by ``models/gpt.py`` and ``models/decoder.py``.

A layer gathers its own context out of the pools (``ops/kv_gather``)
and attends with the flash kernel. A *full* layer gathers through the
lane's whole block table. A *window* layer gathers through the table's
tail: the blocks that hold the last ``window`` positions (built on the
host, ``KVCache.window_table_array``), so what it gathers and attends
stops growing at the window. Each lane's tail starts at a block edge
of its own, so the window layer's masks come from true positions, not
from where a key lies in the gathered array. A *latent* layer gathers
its rows for a chunk, and in a decode call gathers nothing: its kernel
reads the pool through the table (:func:`cached_latent_attention`).

The gather stops at what a lane has written (``lens``), so what lies
past it in the gathered arrays is whatever was there before. The layers
of one program call therefore gather into one pair of arrays a table
shape: the model makes the first (``first_context``: zeros), hands it
to its first layer as ``into``, and hands each later layer what the
last one of its kind gave back, whose live prefix that layer
overwrites in place. Every slot a mask hides thus holds zeros or what
was some layer's K/V, never memory nobody wrote: the flash kernel's
mask is additive and a hidden key still meets ``p @ v`` as ``0 x v``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

# a key position no query sees: before every window, after no query
_NEVER = -(2 ** 30)


def _into_slot(ctx, new, at):
    """``new`` (b, kv, 1, d) into slot ``at[b]`` of ``ctx`` (b, kv, L,
    d): an update in place a lane (one scatter for all lanes makes the
    TPU compiler transpose the whole context there and back)."""
    for i in range(ctx.shape[0]):
        ctx = lax.dynamic_update_slice(
            ctx, lax.slice_in_dim(new, i, i + 1),
            (i, 0, lax.index_in_dim(at, i, keepdims=False), 0))
    return ctx


def first_context(kv_ctx, *, zero, window=None):
    """``into`` for the first of a program call's layers with this
    ``window``: zeros in the gathered layout for the tables such layers
    gather through. ``kv_ctx`` as the model takes it, ``(pools,
    tables, ctx_lens[, win])``; ``zero(pools, tables)`` the caller's
    ``kv_gather.zero_context`` (under a mesh, its ``on_shards``
    island), which gives nothing where nothing is gathered into."""
    pools, tables, _, *win = kv_ctx
    if window is not None:
        tables = win[0][0]
    with jax.named_scope("cache"):
        return zero(pools, tables)


def _decode(flash, q, k_all, v_all, kv_seg):
    """One query a lane against its gathered keys. The query heads
    that share a kv head go in as the rows of one query block: the
    mask is per key, the same for all of them, and the keys are then
    read once a kv head, not once a query head."""
    b, h, _, d = q.shape
    kv = k_all.shape[1]
    out = flash(q.reshape(b, kv, h // kv, d), k_all, v_all, causal=False,
                kv_segment_ids=kv_seg)
    return out.reshape(b, h, 1, d)


def cached_attention(q, k_new, v_new, kv_ctx, *, flash, gather, dtype,
                     window=None, sow=None):
    """``q`` (b, heads, s, d) of the new tokens against their cached
    context plus themselves; ``k_new`` / ``v_new`` (b, kv_heads, s, d)
    their own keys and values. Returns (b, heads, s, d) and what the
    next layer of this kind gathers into.

    ``kv_ctx = (layer, into, (k_pool, v_pool), tables, ctx_lens[,
    win])``: the pools whole, this layer's index into them, the block
    tables (b, w) and how many positions of each lane are written. A
    window layer also needs ``win = (win_tables, win_first)``: the tail
    of each lane's table (b, ww) and the index, in the lane's own
    table, of the tail's first block (b,). ``into`` is the (K, V) this
    layer
    gathers into (module docstring): ``first_context``'s or the last
    such layer's second result, and ``()`` where that was.

    ``s == 1`` is decode: the token's K/V goes into its slot of the
    gathered context (where ``append_kv`` writes it once the layers
    have run). ``s > 1`` is a prefill chunk over ``[ctx | chunk]``.
    ``flash`` and ``gather`` are the caller's ``flash_attention`` and
    ``kv_gather`` (under a mesh, their ``on_shards`` islands)."""
    layer, into, pools, tables, ctx_lens, *rest = kv_ctx
    k_pool = pools[0]
    win = rest[0] if rest else None
    b, _, s, _ = q.shape
    if window is not None and win is None:
        raise ValueError(
            "a window layer needs the tail tables in kv_ctx: "
            "(layer, into, (k_pool, v_pool), tables, ctx_lens, "
            "(win_tables, win_first))")
    # the cache's machinery is the ``cache`` part of the model
    # (telemetry.compiled.PARTS); the masks and the kernel below are
    # the caller's ``attention``
    with jax.named_scope("cache"):
        if window is not None:
            tables, first = win
            base = first * k_pool.shape[2]       # the tail's first position
        # positions of the gathered arrays that hold written keys: none
        # behind the tail's first, and the masks below hide the rest
        lens = ctx_lens if window is None else ctx_lens - base
        k_all, v_all = gather(pools, layer, tables, lens, into)
        if sow is not None:
            sow((k_all, v_all))
        k_all, v_all = k_all.astype(dtype), v_all.astype(dtype)
        if s == 1:
            k_all = _into_slot(k_all, k_new, lens)
            v_all = _into_slot(v_all, v_new, lens)
        # the arrays as they are now: the token's slot is updated in
        # place, and handing on what they were before would keep a copy
        # of that
        held = ()
        if into:
            held = k_all.astype(k_pool.dtype), v_all.astype(k_pool.dtype)
    slot = jnp.arange(k_all.shape[2], dtype=jnp.int32)[None, :]
    if window is None and s == 1:
        # segment masking only: the written prefix and the token
        # itself are 0, everything else 1
        kv_seg = (slot > ctx_lens[:, None]).astype(jnp.int32)
        return _decode(flash, q, k_all, v_all, kv_seg), held
    if window is None:
        # causal=True with sk > sq gives query i the keys j <= i + L
        # (all of ctx + the chunk's own causal prefix); the segment
        # ids drop ctx slots past the written prefix; chunk padding
        # keys sit after every real query
        k_all = jnp.concatenate([k_all, k_new], axis=2)
        v_all = jnp.concatenate([v_all, v_new], axis=2)
        kv_seg = jnp.concatenate(
            [(slot >= ctx_lens[:, None]).astype(jnp.int32),
             jnp.zeros((b, s), jnp.int32)], axis=1)
        return flash(q, k_all, v_all, causal=True,
                     kv_segment_ids=kv_seg), held
    pos = base[:, None] + slot                   # (b, Lw) true positions
    with jax.named_scope("attention_window"):
        if s == 1:
            t = ctx_lens[:, None]
            kv_seg = ((pos > t) | (pos <= t - window)).astype(jnp.int32)
            return _decode(flash, q, k_all, v_all, kv_seg), held
        k_all = jnp.concatenate([k_all, k_new], axis=2)
        v_all = jnp.concatenate([v_all, v_new], axis=2)
        q_pos = ctx_lens[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
        k_pos = jnp.concatenate(
            [jnp.where(pos < ctx_lens[:, None], pos, _NEVER), q_pos], axis=1)
        return flash(q, k_all, v_all, causal=True, window_size=window,
                     q_positions=q_pos, kv_positions=k_pos), held


def cached_latent_attention(row_new, kv_ctx, *, expanded, absorbed, gather,
                            dtype):
    """A ``latent`` layer's new tokens against their cached context
    plus themselves (``models/decoder.py`` ``LatentAttention``).
    ``row_new`` (b, 1, s, W) is their rows ``[c_kv | k_r | 0]``;
    ``kv_ctx = (layer, into, pools, tables, ctx_lens)`` as
    :func:`cached_attention` takes it, with the latent
    pool alone in ``pools``. What the layer does with its rows is the
    caller's:

    - ``s == 1`` is decode: ``absorbed(pools, layer, tables, ctx_lens,
      row_new)`` attends over each lane's ``ctx_lens`` rows where they
      lie in the pool, then the token's own row (``ops/mla_decode.py``):
      nothing is gathered, and nothing is handed to the next layer
      (``into`` is ``()``: ``models/decoder.py`` makes none for a
      decode call);
    - ``s > 1`` is a chunk over ``[ctx | chunk]``: the layer gathers its
      rows (one array, key and value both) and ``expanded(rows,
      kv_segment_ids=...)`` up-projects them and attends causally, the
      segment ids dropping ctx slots past the written prefix.

    Returns what the caller's form returns and what the next latent
    layer gathers into."""
    layer, into, pools, tables, ctx_lens = kv_ctx
    s = row_new.shape[2]
    if s == 1:
        return absorbed(pools, layer, tables, ctx_lens, row_new), ()
    with jax.named_scope("cache"):
        (rows,) = gather(pools, layer, tables, ctx_lens, into)
        rows = rows.astype(dtype)
        held = (rows.astype(pools[0].dtype),) if into else ()
    slot = jnp.arange(rows.shape[2], dtype=jnp.int32)[None, :]
    kv_seg = jnp.concatenate(
        [(slot >= ctx_lens[:, None]).astype(jnp.int32),
         jnp.zeros((row_new.shape[0], s), jnp.int32)], axis=1)
    return expanded(jnp.concatenate([rows, row_new], axis=2),
                    kv_segment_ids=kv_seg), held
