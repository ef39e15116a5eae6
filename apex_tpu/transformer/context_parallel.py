"""Context parallelism — ring attention and Ulysses (all-to-all) attention.

The reference implements Megatron sequence parallelism only and has **no
ring attention / context parallelism / Ulysses** (SURVEY.md §5
"Long-context": apex/transformer/tensor_parallel/mappings.py:205-260 is
the whole story; apex/contrib/fmha is capped at seqlen 512). Long
sequences are first-class in the TPU build, so this module provides the
two standard sequence-scaling schemes over the mesh's "context" axis:

  - **Ring attention** (`ring_attention`): Q stays put; (K, V) chunks
    rotate around the context-axis ring via ``lax.ppermute`` while an
    online-softmax accumulator merges each visiting chunk — exact
    attention with per-device score memory O(s_local^2) instead of
    O(S^2), and comms that ride ICI neighbor links. Causality is
    enforced from *global* token positions, which also makes zig-zag
    load balancing (`zigzag_indices`) a pure input permutation.
  - **Ulysses attention** (`ulysses_attention`): two ``lax.all_to_all``
    switches seq-sharding <-> head-sharding so each device runs the
    full-sequence Pallas flash kernel (apex_tpu/ops/attention.py) on
    its own head slice. Cheaper comms than the ring for moderate S,
    bounded by num_heads % cp == 0.

Both are called *inside* ``shard_map`` on local shards laid out
(batch, heads, seq_local, head_dim); ``*_sharded`` convenience wrappers
apply the shard_map for the common mesh layout. Both are reverse-mode
differentiable. Ring attention carries a **recompute backward**
(custom VJP): the forward saves only the local shards plus (out, lse) —
O(s_local) per device — and the backward re-rotates KV around the ring,
recomputing each chunk's gradient contribution against the *global*
(lse, delta) statistics. Differentiating through the forward scan
instead would stack per-step KV/out residuals into O(S) per device,
erasing exactly the memory advantage ring attention exists for.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from apex_tpu.ops.attention import NEG_INF, flash_attention
from apex_tpu.transformer.parallel_state import CONTEXT_AXIS, DATA_AXIS


# --------------------------------------------------------------------------
# zig-zag load balancing
# --------------------------------------------------------------------------


def zigzag_indices(seq_len: int, cp_size: int) -> Tuple[np.ndarray, np.ndarray]:
    """Permutation (and its inverse) that balances causal work over the ring.

    With plain block sharding device 0 holds the earliest tokens and is
    masked out for most ring steps while the last device does full work.
    The zig-zag layout gives device i the chunk pair (i, 2*cp-1-i) so
    every device owns one "early" and one "late" chunk and the causal
    work is even. Returns (perm, inv): ``x[perm]`` is the balanced
    order to shard; ``y[inv]`` restores the original order.
    """
    if seq_len % (2 * cp_size):
        raise ValueError(
            f"zig-zag needs seq_len divisible by 2*cp ({2 * cp_size}); "
            f"got {seq_len}")
    piece = seq_len // (2 * cp_size)
    chunks = np.arange(seq_len).reshape(2 * cp_size, piece)
    order = []
    for i in range(cp_size):
        order.append(chunks[i])
        order.append(chunks[2 * cp_size - 1 - i])
    perm = np.concatenate(order)
    inv = np.argsort(perm)
    return perm, inv


# --------------------------------------------------------------------------
# ring attention
# --------------------------------------------------------------------------


def _chunk_attn(q, k_c, v_c, qpos, kpos, scale, causal, impl=None):
    """One ring step: local Q against a visiting KV chunk through the
    flash kernel, returning (out fp32, lse) partials.

    Chunk pairs merge exactly via logaddexp (``_merge``): a fully-masked
    row (a chunk entirely in this query's causal future) carries
    lse = NEG_INF — zero mass — so its zero output never survives.
    Causality comes from *global* positions (``q_positions`` /
    ``kv_positions`` on the kernel), which is what makes zig-zag
    balancing a pure input permutation.
    """
    out, lse = flash_attention(
        q, k_c, v_c, causal=causal,
        q_positions=qpos if causal else None,
        kv_positions=kpos if causal else None,
        softmax_scale=scale, return_lse=True, impl=impl)
    return out.astype(jnp.float32), lse


def _merge(a, p):
    o_a, l_a = a
    o_p, l_p = p
    l_new = jnp.logaddexp(l_a, l_p)
    return (o_a * jnp.exp(l_a - l_new)[..., None]
            + o_p * jnp.exp(l_p - l_new)[..., None], l_new)


def _skip_future_tile(kpos_b, q_max_b, run, zero):
    """The ring's causal tile skip, shared by forward and backward: a
    (q-block, kv-block) pair wholly in the q-block's causal future is
    skipped via ``lax.cond`` (per-device predicate, collective-free, so
    divergent branches across the ring are fine)."""
    return lax.cond(jnp.min(kpos_b) > q_max_b, zero, run)


def _ring_forward(q, k, v, q_positions, kv_positions, axis_name, causal,
                  scale, ng, impl):
    """The ring sweep: returns fp32 (out, lse) of the local Q shard
    against the full sequence. KV (and positions) rotate via ppermute;
    the online-softmax carry merges chunks exactly as the Pallas flash
    kernel does across KV blocks."""
    cp = lax.axis_size(axis_name)
    b, h, s_local, d = q.shape
    perm = [(i, (i + 1) % cp) for i in range(cp)]

    def compute(k_c, v_c, kpos):
        """(out, lse) partials of local Q against one visiting KV shard.

        Under causal masking the shard is processed in ``ng`` x ``ng``
        (q-block, kv-block) sub-tiles; a tile wholly in the q-block's
        causal future is skipped via ``lax.cond`` so no kernel launch is
        issued for it (the predicate is per-device and collective-free,
        so divergent branches across the ring are fine)."""
        if not causal:
            return _chunk_attn(q, k_c, v_c, q_positions, kpos, scale,
                               False, impl)
        qs, ks = s_local // ng, k_c.shape[2] // ng
        o_rows, l_rows = [], []
        for qb in range(ng):
            qsl = slice(qb * qs, (qb + 1) * qs)
            q_b, qpos_b = q[:, :, qsl], q_positions[qsl]
            q_max_b = jnp.max(qpos_b)
            acc = None
            for kb in range(ng):
                ksl = slice(kb * ks, (kb + 1) * ks)
                k_b, v_b, kpos_b = k_c[:, :, ksl], v_c[:, :, ksl], kpos[ksl]
                part = _skip_future_tile(
                    kpos_b, q_max_b,
                    run=lambda k_b=k_b, v_b=v_b, kpos_b=kpos_b, q_b=q_b,
                    qpos_b=qpos_b: _chunk_attn(
                        q_b, k_b, v_b, qpos_b, kpos_b, scale, True, impl),
                    zero=lambda: (jnp.zeros((b, h, qs, d), jnp.float32),
                                  jnp.full((b, h, qs), NEG_INF,
                                           jnp.float32)),
                )
                acc = part if acc is None else _merge(acc, part)
            o_rows.append(acc[0])
            l_rows.append(acc[1])
        return (jnp.concatenate(o_rows, axis=2),
                jnp.concatenate(l_rows, axis=2))

    # chunk 0 is the local KV shard — computed before any rotation, so
    # the ring does exactly cp-1 ppermutes (none wasted).
    acc = compute(k, v, kv_positions)

    def step(carry, _):
        acc, k_c, v_c, kpos = carry
        k_c = lax.ppermute(k_c, axis_name, perm)
        v_c = lax.ppermute(v_c, axis_name, perm)
        kpos = lax.ppermute(kpos, axis_name, perm)
        acc = _merge(acc, compute(k_c, v_c, kpos))
        return (acc, k_c, v_c, kpos), None

    (acc, _, _, _), _ = lax.scan(
        step, (acc, k, v, kv_positions), None, length=cp - 1)
    return acc            # chunks arrive normalized; nothing to divide


def _chunk_grads(q, k_c, v_c, qpos, kpos, g, lse, delta, scale, causal,
                 impl, bq=1024, bk=1024):
    """Gradient contribution of one visiting KV chunk, evaluated against
    the *global* softmax statistics.

    With P = exp(S - lse_global) restricted to this chunk and
    delta = rowsum(out_global * g), the per-chunk flash backward yields
    exactly this chunk's share of (dq, dk_c, dv_c): summed over chunks,
    rowsum(P) = 1 restores the full softmax backward. This is the
    identity that lets the ring backward recompute instead of saving
    per-step residuals.

    The XLA path returns fp32 so per-chunk contributions accumulate
    without intermediate rounding; the kernel path rounds once per
    chunk to the input dtype (the kernels' output dtype) — one extra
    rounding per ring step vs single-device flash.
    """
    if impl is None:
        from apex_tpu._backend import default_impl
        impl = default_impl()
    if impl != "xla":
        from apex_tpu.ops.attention import (_flash_bwd_pallas,
                                            interpret_flag)
        core = (q, k_c, v_c, None, None, None, None, lse)
        return _flash_bwd_pallas(
            core, g, delta, None, scale, causal, None, 0.0, bq, bk,
            interpret_flag(impl),
            q_pos=qpos if causal else None,
            k_pos=kpos if causal else None)

    b, h, sq, d = q.shape
    hk = k_c.shape[1]
    group = h // hk
    s = jnp.einsum("bkgqd,bkcd->bkgqc",
                   (q.astype(jnp.float32) * scale).reshape(
                       b, hk, group, sq, d),
                   k_c.astype(jnp.float32))
    if causal:
        masked = kpos[None, :] > qpos[:, None]
        s = jnp.where(masked[None, None, None], NEG_INF, s)
    # rows whose global lse is NEG_INF (fully masked everywhere) get 0
    p = jnp.exp(s - jnp.maximum(lse, NEG_INF * 0.5).reshape(
        b, hk, group, sq, 1))
    if causal:
        p = jnp.where(masked[None, None, None], 0.0, p)
    gf = g.astype(jnp.float32).reshape(b, hk, group, sq, d)
    dv_c = jnp.einsum("bkgqc,bkgqd->bkcd", p, gf)
    dp = jnp.einsum("bkgqd,bkcd->bkgqc", gf, v_c.astype(jnp.float32))
    ds = p * (dp - delta.reshape(b, hk, group, sq, 1))
    dq = (jnp.einsum("bkgqc,bkcd->bkgqd", ds, k_c.astype(jnp.float32))
          * scale).reshape(b, h, sq, d)
    dk_c = jnp.einsum("bkgqc,bkgqd->bkcd", ds,
                      (q.astype(jnp.float32) * scale).reshape(
                          b, hk, group, sq, d))
    return dq, dk_c, dv_c     # fp32: callers accumulate across chunks


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def _ring_core(q, k, v, qpos, kpos, axis_name, causal, scale, ng, impl,
               bwd_bq, bwd_bk):
    out, _ = _ring_forward(q, k, v, qpos, kpos, axis_name, causal, scale,
                           ng, impl)
    return out.astype(q.dtype)


def _ring_fwd_rule(q, k, v, qpos, kpos, axis_name, causal, scale, ng,
                   impl, bwd_bq, bwd_bk):
    out, lse = _ring_forward(q, k, v, qpos, kpos, axis_name, causal,
                             scale, ng, impl)
    out = out.astype(q.dtype)
    # O(s_local) residuals: local shards + (out, lse). Nothing scales
    # with the ring size — the backward re-rotates KV instead.
    return out, (q, k, v, qpos, kpos, out, lse)


def _ring_bwd_rule(axis_name, causal, scale, ng, impl, bwd_bq, bwd_bk,
                   res, g):
    q, k, v, qpos, kpos, out, lse = res
    cp = lax.axis_size(axis_name)
    b, h, s_local, d = q.shape
    perm = [(i, (i + 1) % cp) for i in range(cp)]
    delta = jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32),
                    axis=-1)

    def chunk_bwd(k_c, v_c, kpos_c):
        """(dq_part, dk_c, dv_c) of local Q vs one visiting shard, with
        the same ng x ng causal-future tile skip as the forward — the
        backward is ~2.5x the forward's FLOPs, so keeping the zig-zag
        skip here is most of the schedule's causal saving."""
        if not causal:
            dq_p, dkc_p, dvc_p = _chunk_grads(
                q, k_c, v_c, qpos, kpos_c, g, lse, delta, scale, False,
                impl, bwd_bq, bwd_bk)
            return (dq_p.astype(jnp.float32), dkc_p.astype(jnp.float32),
                    dvc_p.astype(jnp.float32))
        qs, ks = s_local // ng, k_c.shape[2] // ng
        dq_rows = []
        dk_cols = [None] * ng
        dv_cols = [None] * ng
        for qb in range(ng):
            qsl = slice(qb * qs, (qb + 1) * qs)
            q_b, g_b = q[:, :, qsl], g[:, :, qsl]
            lse_b, delta_b = lse[:, :, qsl], delta[:, :, qsl]
            qpos_b = qpos[qsl]
            q_max_b = jnp.max(qpos_b)
            dq_acc = jnp.zeros((b, h, qs, d), jnp.float32)
            for kb in range(ng):
                ksl = slice(kb * ks, (kb + 1) * ks)
                k_b, v_b, kpos_b = (k_c[:, :, ksl], v_c[:, :, ksl],
                                    kpos_c[ksl])

                def run(k_b=k_b, v_b=v_b, kpos_b=kpos_b, q_b=q_b,
                        g_b=g_b, lse_b=lse_b, delta_b=delta_b,
                        qpos_b=qpos_b):
                    dq_p, dk_p, dv_p = _chunk_grads(
                        q_b, k_b, v_b, qpos_b, kpos_b, g_b, lse_b,
                        delta_b, scale, True, impl, bwd_bq, bwd_bk)
                    return (dq_p.astype(jnp.float32),
                            dk_p.astype(jnp.float32),
                            dv_p.astype(jnp.float32))

                def skip(k_b=k_b, v_b=v_b):
                    return (jnp.zeros((b, h, qs, d), jnp.float32),
                            jnp.zeros(k_b.shape, jnp.float32),
                            jnp.zeros(v_b.shape, jnp.float32))

                dq_p, dk_p, dv_p = _skip_future_tile(
                    kpos_b, q_max_b, run=run, zero=skip)
                dq_acc = dq_acc + dq_p
                dk_cols[kb] = dk_p if dk_cols[kb] is None else dk_cols[kb] + dk_p
                dv_cols[kb] = dv_p if dv_cols[kb] is None else dv_cols[kb] + dv_p
            dq_rows.append(dq_acc)
        return (jnp.concatenate(dq_rows, axis=2),
                jnp.concatenate(dk_cols, axis=2),
                jnp.concatenate(dv_cols, axis=2))

    def step(carry, _):
        dq, k_c, v_c, kpos_c, dk_c, dv_c = carry
        dq_p, dkc_p, dvc_p = chunk_bwd(k_c, v_c, kpos_c)
        dq = dq + dq_p
        dk_c = dk_c + dkc_p
        dv_c = dv_c + dvc_p
        # rotate the chunk together with its accumulated gradients; after
        # cp steps both are back on the chunk's home device
        k_c = lax.ppermute(k_c, axis_name, perm)
        v_c = lax.ppermute(v_c, axis_name, perm)
        kpos_c = lax.ppermute(kpos_c, axis_name, perm)
        dk_c = lax.ppermute(dk_c, axis_name, perm)
        dv_c = lax.ppermute(dv_c, axis_name, perm)
        return (dq, k_c, v_c, kpos_c, dk_c, dv_c), None

    init = (jnp.zeros(q.shape, jnp.float32), k, v, kpos,
            jnp.zeros(k.shape, jnp.float32), jnp.zeros(v.shape, jnp.float32))
    (dq, _, _, _, dk, dv), _ = lax.scan(step, init, None, length=cp)

    def int_ct(a):
        import numpy as _np
        return _np.zeros(a.shape, dtype=jax.dtypes.float0)

    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            int_ct(qpos), int_ct(kpos))


_ring_core.defvjp(_ring_fwd_rule, _ring_bwd_rule)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str = CONTEXT_AXIS,
    causal: bool = False,
    softmax_scale: Optional[float] = None,
    q_positions: Optional[jax.Array] = None,
    kv_positions: Optional[jax.Array] = None,
    skip_granularity: int = 1,
    impl: Optional[str] = None,
    bwd_block_q: int = 1024,
    bwd_block_k: int = 1024,
) -> jax.Array:
    """Exact ring attention over the ``axis_name`` device ring.

    Call inside ``shard_map``; ``q``/``k``/``v`` are the local sequence
    shards, (batch, heads, s_local, head_dim). ``q_positions`` /
    ``kv_positions`` are the *global* token positions of the local shard
    (s_local,) — defaults assume contiguous block sharding; pass the
    zig-zag positions when the inputs were permuted with
    :func:`zigzag_indices`. KV (and its positions) rotate ring-wise via
    ``ppermute``; the online-softmax carry merges chunks exactly as the
    Pallas flash kernel does across KV blocks, so the result matches
    single-device attention to fp32 accumulation order.

    ``skip_granularity`` splits Q and KV into that many contiguous
    sub-blocks and, under causal masking, skips the score matmul for any
    (q-block, kv-block) pair wholly in the causal future via ``lax.cond``
    (TPU executes only the taken branch, so skipped pairs are ~free).
    With contiguous sharding 1 suffices (whole visiting chunks skip);
    with zig-zag each shard is two chunks, so pass 2 — that is what
    recovers the ~2x causal FLOP saving that zig-zag balancing is for.

    Reverse-mode differentiation uses a **recompute backward**: forward
    residuals are O(s_local) (local shards + out + lse) and the backward
    re-rotates KV around the ring, evaluating each chunk's flash
    backward against the global (lse, delta) — the standard ring
    attention backward, vs. AD-through-the-scan which would stack
    O(ring) KV/out residuals per device.
    """
    cp = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    b, h, s_local, d = q.shape
    scale = softmax_scale if softmax_scale is not None else d ** -0.5
    if q_positions is None:
        q_positions = idx * s_local + jnp.arange(s_local, dtype=jnp.int32)
    if kv_positions is None:
        kv_positions = idx * k.shape[2] + jnp.arange(k.shape[2], dtype=jnp.int32)

    ng = skip_granularity
    if ng < 1 or s_local % ng or k.shape[2] % ng:
        raise ValueError(
            f"skip_granularity {ng} must divide q ({s_local}) and kv "
            f"({k.shape[2]}) shard lengths")
    del cp
    return _ring_core(q, k, v,
                      jnp.asarray(q_positions, jnp.int32),
                      jnp.asarray(kv_positions, jnp.int32),
                      axis_name, causal, scale, ng, impl,
                      bwd_block_q, bwd_block_k)


def ring_attention_sharded(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh,
    *,
    axis_name: str = CONTEXT_AXIS,
    batch_axis: Optional[str] = DATA_AXIS,
    causal: bool = False,
    softmax_scale: Optional[float] = None,
    zigzag: bool = False,
    impl: Optional[str] = None,
) -> jax.Array:
    """shard_map convenience wrapper: global (b, h, S, d) in/out, sequence
    sharded over ``axis_name`` (and batch over ``batch_axis`` if given).

    With ``zigzag=True`` the sequence is permuted to the balanced layout
    before sharding and un-permuted after — causality stays exact because
    :func:`ring_attention` masks from global positions, and the ring runs
    with ``skip_granularity=2`` so each shard's two chunks skip their
    causal-future tiles independently (the actual work balancing).
    """
    cp = mesh.shape[axis_name]
    S = q.shape[2]
    if S % cp:
        raise ValueError(f"seq len {S} not divisible by cp={cp}")

    pos = np.arange(S, dtype=np.int32)
    if zigzag:
        perm, inv = zigzag_indices(S, cp)
        q, k, v = q[:, :, perm], k[:, :, perm], v[:, :, perm]
        pos = pos[perm]
    pos = jnp.asarray(pos)

    spec_x = P(batch_axis, None, axis_name, None)
    spec_p = P(axis_name)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(spec_x, spec_x, spec_x, spec_p),
        out_specs=spec_x, check_vma=False,
    )
    def run(ql, kl, vl, posl):
        return ring_attention(
            ql, kl, vl, axis_name=axis_name, causal=causal,
            softmax_scale=softmax_scale,
            q_positions=posl, kv_positions=posl,
            skip_granularity=2 if zigzag else 1, impl=impl,
        )

    out = run(q, k, v, pos)
    if zigzag:
        out = out[:, :, inv]
    return out


# --------------------------------------------------------------------------
# Ulysses (all-to-all head<->sequence resharding)
# --------------------------------------------------------------------------


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str = CONTEXT_AXIS,
    causal: bool = False,
    softmax_scale: Optional[float] = None,
    impl: Optional[str] = None,
    block_q: int = 1024,
    block_k: int = 1024,
) -> jax.Array:
    """DeepSpeed-Ulysses-style attention: all_to_all seq->heads, local
    full-sequence flash attention, all_to_all heads->seq.

    Call inside ``shard_map`` with local shards (b, h, s_local, d);
    requires ``h % cp == 0``. The inner kernel is the Pallas flash
    attention (apex_tpu/ops/attention.py), so per-device memory is the
    flash kernel's, and the MXU sees full-length attention matmuls.
    """
    cp = lax.axis_size(axis_name)
    h = q.shape[1]
    if h % cp:
        raise ValueError(f"num heads {h} not divisible by cp={cp}")
    if k.shape[1] % cp:
        raise ValueError(
            f"kv heads ({k.shape[1]}) must be divisible by cp={cp} for "
            f"the all_to_all head resharding (kv head counts not "
            f"divisible by cp need ring attention instead)")

    def to_seq(x):  # (b, h, s/cp, d) -> (b, h/cp, S, d)
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    def to_heads(x):  # (b, h/cp, S, d) -> (b, h, s/cp, d)
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    qh, kh, vh = to_seq(q), to_seq(k), to_seq(v)
    out = flash_attention(
        qh, kh, vh, causal=causal, softmax_scale=softmax_scale,
        impl=impl, block_q=block_q, block_k=block_k,
    )
    return to_heads(out)


def ulysses_attention_sharded(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh,
    *,
    axis_name: str = CONTEXT_AXIS,
    batch_axis: Optional[str] = DATA_AXIS,
    causal: bool = False,
    softmax_scale: Optional[float] = None,
    impl: Optional[str] = None,
    block_q: int = 1024,
    block_k: int = 1024,
) -> jax.Array:
    """shard_map wrapper for :func:`ulysses_attention` (global arrays in/out)."""
    spec_x = P(batch_axis, None, axis_name, None)

    @functools.partial(
        shard_map, mesh=mesh, in_specs=(spec_x, spec_x, spec_x),
        out_specs=spec_x, check_vma=False,
    )
    def run(ql, kl, vl):
        return ulysses_attention(
            ql, kl, vl, axis_name=axis_name, causal=causal,
            softmax_scale=softmax_scale, impl=impl,
            block_q=block_q, block_k=block_k,
        )

    return run(q, k, v)


__all__ = [
    "ring_attention",
    "ring_attention_sharded",
    "ulysses_attention",
    "ulysses_attention_sharded",
    "zigzag_indices",
]
