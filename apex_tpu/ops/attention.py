"""Flash attention — Pallas TPU kernels with custom VJP.

TPU re-design of the reference's two attention kernel families:

  - ``apex/contrib/fmha`` (fixed-seqlen sm80 flash attention over packed
    varlen batches, ref: apex/contrib/fmha/fmha.py:33-74,
    apex/contrib/csrc/fmha/) — superseded here by a seqlen-generic
    flash kernel with segment-id masking for packed varlen.
  - ``apex/contrib/multihead_attn`` CUDA softmax/GEMM fusions
    (ref: apex/contrib/csrc/multihead_attn/, 8438 LoC) — the module
    layer on top lives in apex_tpu/contrib/multihead_attn.

Design (standard TPU flash attention, "How to Scale Your Model" ch. on
attention): online softmax over KV blocks streamed through VMEM; the
MXU sees (block_q, d) x (d, block_k) and (block_q, block_k) x
(block_k, d) matmuls; stats (running max m, normalizer l) live in VMEM
scratch broadcast across 128 lanes. Backward recomputes P from the
saved logsumexp (no O(S^2) residuals) with two kernels: dq
(parallel over Q blocks) and dk/dv (parallel over KV blocks).

Layout: (batch, heads, seq, head_dim) ("bhsd"). fp32 accumulation
throughout, output in the input dtype.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu._backend import interpret_flag, resolve_impl

NEG_INF = -1e30


def _bias_index_map(b_b: int, h_b: int, h: int):
    """Flat-bias index for grid step bh, honoring size-1 broadcast dims.

    bias is stored (b_b*h_b, sq, sk) with b_b in {1, b}, h_b in {1, h};
    grid step bh = ib*h + ih reads bias block (ib % b_b)*h_b + ih % h_b.
    """
    def bmap(bh):
        return (bh // h) % b_b * h_b + (bh % h) % h_b
    return bmap


def _pick_block(seq: int, want: int) -> int:
    """Largest power-of-two block <= want that divides seq."""
    b = min(want, seq)
    while seq % b:
        b //= 2
    return max(b, 1)


def _kv_pad(sk: int, want: int) -> int:
    """Keys to append so the k block obeys the TPU tiling rule.

    A block's last two dims must be multiples of (8, 128) or span the
    whole array, and the k block size sits in the LANE dim of the
    segment-id / position / bias blocks. ``_pick_block`` shrinks the
    block until it divides ``sk``; for an ``sk`` like 1025 (a decode
    step's cached prefix + the new token) that ends at 1, which the
    Pallas TPU lowering refuses. Such an ``sk`` is padded up to the
    next multiple of 128 instead, and the kernels mask the tail by
    index (``_mask_block``'s ``k_len``). A caller-chosen block that is
    itself not lane-aligned (interpret-mode tests) is left alone."""
    blk = _pick_block(sk, want)
    if blk == sk or blk % 128 == 0 or want % 128:
        return 0
    return -sk % 128


def _mask_block(iq, ik, bq, bk, sq, sk, causal, window, q_seg, k_seg,
                q_pos=None, k_pos=None, k_len=None):
    """fp32 additive mask (bq, bk) for the (iq, ik) block pair.

    ``q_seg``/``k_seg`` are column (bq, 1) / row (1, bk) int32 blocks
    (the kernel segment layouts); the XLA path masks segments itself.
    ``q_pos``/``k_pos`` (same layouts) carry global token positions for
    ring/blockwise chunks, replacing the static causal/window geometry.
    ``sk`` is the number of REAL keys (the causal offset's geometry);
    ``k_len`` is set to it when the k axis was padded past it
    (``_kv_pad``), and masks the padded tail by key index.
    """
    if q_pos is not None:
        # dynamic GLOBAL positions (ring/blockwise chunks): causal and
        # window tests compare position values, not block indices
        row, col = q_pos, k_pos           # (bq, 1) / (1, bk)
        off = 0
    else:
        row = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        col = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        off = sk - sq
    neg = jnp.zeros((bq, bk), jnp.float32)
    if causal:
        # query i attends to keys j <= i + (sk - sq) (supports sk >= sq)
        neg = jnp.where(col > row + off, NEG_INF, neg)
    if window is not None:
        # sliding window: the last `window` keys up to the diagonal
        neg = jnp.where(col <= row + off - window, NEG_INF, neg)
    if q_seg is not None:
        neg = jnp.where(q_seg != k_seg, NEG_INF, neg)
    if k_len is not None:
        kidx = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        neg = jnp.where(kidx >= k_len, NEG_INF, neg)
    return neg


def _dropout_keep(seed, bh, row, col, rate):
    """Deterministic keep mask from a murmur3-finalizer hash of
    (seed, batch*head index, row, col).

    Counter-based (no carried RNG state), so the forward and both
    backward kernels regenerate the identical mask from the same seed —
    the fusion the reference gets from its softmax+dropout CUDA kernels
    (ref: apex/contrib/csrc/multihead_attn/). The same math runs in the
    XLA path, so cross-impl gradient parity is exact for a given seed.

    ``row``/``col``/``bh`` broadcast against each other; returns bool.
    """
    x = (row.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
         ^ col.astype(jnp.uint32) * jnp.uint32(0x85EBCA77))
    x = x ^ (jnp.asarray(bh).astype(jnp.uint32) * jnp.uint32(0xC2B2AE3D))
    x = x ^ jnp.asarray(seed).astype(jnp.uint32)
    # murmur3 fmix32: full avalanche so neighboring (row, col) decorrelate
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    thresh = jnp.uint32(min(int(rate * 2.0 ** 32), 2 ** 32 - 1))
    return x >= thresh


def _block_live(iq, ik, bq, bk, sq, sk, causal, window):
    """Whether the (iq, ik) block pair can contain any unmasked score."""
    run = True
    if causal:
        run = (ik * bk) <= (iq * bq + bq - 1 + (sk - sq))
    if window is not None:
        run = jnp.logical_and(
            run, (ik * bk + bk - 1) >= (iq * bq + (sk - sq) - (window - 1)))
    return run


def _block_live_dynamic(qp_ref, kp_ref, causal, window):
    """Position-based analog of `_block_live`: bounds of the loaded
    position blocks decide whether any (q, k) pair can be unmasked —
    ring attention's causal-future chunks skip their matmuls just like
    the static path skips upper-triangle blocks."""
    run = True
    if causal:
        run = jnp.max(qp_ref[...]) >= jnp.min(kp_ref[...])
    if window is not None:
        run = jnp.logical_and(
            run, jnp.max(kp_ref[...]) > jnp.min(qp_ref[...]) - window)
    return run


def _pos_block(ref):
    """A position block as `_mask_block` takes it: (bq, 1) / (1, bk).
    Per-lane positions (the serving paths) come as (1, bq, 1) /
    (1, 1, bk) blocks of a (batch, ...) array."""
    if ref is None:
        return None
    return ref[0] if len(ref.shape) == 3 else ref[...]


def _band_k_lo(iq, bq, bk, off, window):
    """First k-block index intersecting q-block ``iq``'s sliding window."""
    return jnp.maximum(0, (iq * bq + off - (window - 1)) // bk)


def _band_q_lo(ik, bq, bk, off):
    """First q-block index whose window reaches k-block ``ik``."""
    return jnp.maximum(0, (ik * bk - off) // bq)


def _band_steps(span_block, other_block, window):
    """Blocks of size ``other_block`` overlapped by a window band swept
    across one ``span_block``: ceil((span + window - 1)/other) + 1."""
    return (span_block + window - 1 + other_block - 1) // other_block + 1


def _band(window, span_block, other_block, n_other, dynamic=False):
    """Host-side band setup for one inner grid dim: (banded, n_steps).

    Shared by the fwd/dq/dkv pallas builders so the grid sizing logic
    exists once. ``dynamic`` (positions-based masking) disables static
    banding — block geometry is meaningless under dynamic positions."""
    if window is None or dynamic:
        return False, n_other
    steps = _band_steps(span_block, other_block, window)
    return steps < n_other, min(steps, n_other)


def _band_pos(lo, j, n):
    """Clamped block index and validity of band step ``j`` from ``lo``.

    Shared by the kernels and the BlockSpec index maps: steps past the
    last block clamp to it (redundant DMA) and are masked via the
    returned validity."""
    return jnp.minimum(lo + j, n - 1), lo + j < n


# --------------------------------------------------------------------------
# forward kernel
# --------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, bias_ref, qs_ref, ks_ref, seed_ref,
                qp_ref, kp_ref,
                o_ref, lse_ref, acc_sc, m_sc, l_sc,
                *, scale, causal, window, rate, nk, n_inner, banded,
                bq, bk, sq, sk, k_len=None):
    j = pl.program_id(2)
    iq = pl.program_id(1)
    bh = pl.program_id(0)   # hoisted: program_id inside a pl.when branch
    # leaks into the cond jaxpr, which interpret mode can't substitute
    if banded:
        # sliding window: the inner dim walks only the band's k blocks
        ik, in_range = _band_pos(_band_k_lo(iq, bq, bk, sk - sq, window),
                                 j, nk)
    else:
        ik, in_range = j, True

    @pl.when(j == 0)
    def _init():
        acc_sc[...] = jnp.zeros_like(acc_sc)
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)

    # whole blocks above the diagonal / below the window are skipped;
    # with dynamic positions the static block geometry is meaningless,
    # so every in-range block runs and masking is purely additive
    live = (_block_live_dynamic(qp_ref, kp_ref, causal, window)
            if qp_ref is not None
            else _block_live(iq, ik, bq, bk, sq, sk, causal, window))
    run = jnp.logical_and(live, in_range)

    @pl.when(run)
    def _step():
        # matmuls run in the input dtype (bf16 hits the MXU's fast path)
        # with fp32 accumulation; softmax math stays fp32. The scale is
        # applied to the fp32 scores, not the inputs, so no bits are
        # lost pre-matmul.
        q = q_ref[0]                               # (bq, d)
        k = k_ref[0]                               # (bk, d)
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # (bq, bk)
        if bias_ref is not None:
            s = s + bias_ref[0].astype(jnp.float32)
        q_seg = qs_ref[0] if qs_ref is not None else None
        k_seg = ks_ref[0] if ks_ref is not None else None
        s = s + _mask_block(
            iq, ik, bq, bk, sq, sk, causal, window, q_seg, k_seg,
            q_pos=_pos_block(qp_ref), k_pos=_pos_block(kp_ref),
            k_len=k_len)

        m_prev = m_sc[:, :1]                       # (bq, 1)
        l_prev = l_sc[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                     # (bq, bk)
        corr = jnp.exp(m_prev - m_new)             # (bq, 1)
        # l accumulates the UNdropped sum (the softmax normalizer);
        # dropout applies to the normalized probabilities, i.e. only to
        # the p @ v accumulation below
        l_new = corr * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        if rate > 0.0:
            row = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            col = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            keep = _dropout_keep(seed_ref[0], bh, row, col, rate)
            p = jnp.where(keep, p / (1.0 - rate), 0.0)
        acc_sc[...] = acc_sc[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_sc[...] = jnp.broadcast_to(m_new, m_sc.shape)
        l_sc[...] = jnp.broadcast_to(l_new, l_sc.shape)

    @pl.when(j == n_inner - 1)
    def _fin():
        l = l_sc[:, :1]
        m = m_sc[:, :1]
        # fully-masked rows (e.g. a q segment with no matching kv
        # segment): every logit carries the NEG_INF additive mask, so m
        # sits near NEG_INF. Emit 0 there, and set lse=0 so the backward's
        # p = exp(s - lse) = exp(~NEG_INF) underflows to exactly 0.
        valid = m > NEG_INF * 0.5
        safe = jnp.where(l > 0.0, l, 1.0)
        o_ref[0] = jnp.where(valid, acc_sc[...] / safe, 0.0).astype(o_ref.dtype)
        # lse block is (1, bq, 1): a column vector per q block. Fully
        # masked rows emit NEG_INF — zero mass under logaddexp merging
        # (ring attention combines chunk (out, lse) pairs); the backward
        # kernels clamp it so p = exp(s - lse) still underflows to 0.
        lse_ref[0] = jnp.where(valid, m + jnp.log(safe), NEG_INF)


def _flash_fwd_pallas(q, k, v, bias, q_seg, k_seg, seed, scale, causal,
                      window, rate, bq, bk, interpret,
                      q_pos=None, k_pos=None, kv_len=None):
    b, h, sq, d = q.shape
    hk = k.shape[1]
    group = h // hk          # GQA: q heads per shared kv head
    skp = k.shape[2]         # k axis as stored: padded when kv_len is set
    sk = skp if kv_len is None else kv_len
    k_len = None if sk == skp else sk
    bq = _pick_block(sq, bq)
    bk = _pick_block(skp, bk)
    nq, nk = sq // bq, skp // bk
    # banded sliding window: the inner grid dim covers only the k blocks
    # a q block's window can touch, so DMA traffic is O(S*w) not O(S^2)
    banded, n_inner = _band(window, bq, bk, nk, dynamic=q_pos is not None)

    def ik_of(iq, j):
        if not banded:
            return j
        return _band_pos(_band_k_lo(iq, bq, bk, sk - sq, window), j, nk)[0]
    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * hk, skp, d)
    vf = v.reshape(b * hk, skp, d)

    in_specs = [
        pl.BlockSpec((1, bq, d), lambda bh, iq, j: (bh, iq, 0)),
        # kv heads are shared across each group of q heads — the index
        # map reads the same kv block for the whole group, so GQA costs
        # no materialized repeat
        pl.BlockSpec((1, bk, d), lambda bh, iq, j: (bh // group, ik_of(iq, j), 0)),
        pl.BlockSpec((1, bk, d), lambda bh, iq, j: (bh // group, ik_of(iq, j), 0)),
    ]
    args = [qf, kf, vf]
    if bias is not None:
        # keep ALL broadcast (size-1) dims: batch/head via the index map,
        # sq/sk via size-1 blocks that broadcast inside the kernel.
        b_b, h_b, sq_b, sk_b = bias.shape
        bias_f = bias.reshape(b_b * h_b, sq_b, sk_b)
        bmap = _bias_index_map(b_b, h_b, h)
        in_specs.append(pl.BlockSpec(
            (1, bq if sq_b > 1 else 1, bk if sk_b > 1 else 1),
            lambda bh, iq, j: (bmap(bh),
                               iq if sq_b > 1 else 0,
                               ik_of(iq, j) if sk_b > 1 else 0)))
        args.append(bias_f)
    else:
        in_specs.append(None)
        args.append(None)
    if q_seg is not None:
        # (b, seq) read per grid step via bh // h — no h-fold copy.
        # Layouts: q segs as a (b, sq, 1) column, k segs as a (b, 1, sk)
        # row, so the size-1 block dims equal the array dims (Mosaic's
        # last-two-dims tiling rule rejects 2-D (1, blk) blocks).
        in_specs.append(
            pl.BlockSpec((1, bq, 1), lambda bh, iq, j: (bh // h, iq, 0)))
        in_specs.append(
            pl.BlockSpec((1, 1, bk),
                         lambda bh, iq, j: (bh // h, 0, ik_of(iq, j))))
        args += [q_seg.reshape(*q_seg.shape, 1),
                 k_seg.reshape(k_seg.shape[0], 1, k_seg.shape[1])]
    else:
        in_specs += [None, None]
        args += [None, None]
    if rate > 0.0:
        # dropout seed rides in SMEM (whole (1,) array each grid step)
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(jnp.asarray(seed, jnp.uint32).reshape(1))
    else:
        in_specs.append(None)
        args.append(None)
    if q_pos is not None and jnp.ndim(q_pos) == 2:
        # a lane's own positions (batch, seq), read per grid step via
        # bh // h as the segment ids are, in their layouts
        in_specs.append(
            pl.BlockSpec((1, bq, 1), lambda bh, iq, j: (bh // h, iq, 0)))
        in_specs.append(
            pl.BlockSpec((1, 1, bk),
                         lambda bh, iq, j: (bh // h, 0, ik_of(iq, j))))
        args += [jnp.asarray(q_pos, jnp.int32).reshape(b, sq, 1),
                 jnp.asarray(k_pos, jnp.int32).reshape(b, 1, skp)]
    elif q_pos is not None:
        # global positions: q as an (sq, 1) column, k as a (1, sk) row
        in_specs.append(pl.BlockSpec((bq, 1), lambda bh, iq, j: (iq, 0)))
        in_specs.append(
            pl.BlockSpec((1, bk), lambda bh, iq, j: (0, ik_of(iq, j))))
        args += [jnp.asarray(q_pos, jnp.int32).reshape(sq, 1),
                 jnp.asarray(k_pos, jnp.int32).reshape(1, skp)]
    else:
        in_specs += [None, None]
        args += [None, None]

    live_specs = [s for s in in_specs if s is not None]
    live_args = [a for a in args if a is not None]

    def kernel(*refs):
        it = iter(refs[:len(live_specs)])
        q_ref = next(it)
        k_ref = next(it)
        v_ref = next(it)
        bias_ref = next(it) if bias is not None else None
        qs_ref = next(it) if q_seg is not None else None
        ks_ref = next(it) if q_seg is not None else None
        seed_ref = next(it) if rate > 0.0 else None
        qp_ref = next(it) if q_pos is not None else None
        kp_ref = next(it) if q_pos is not None else None
        o_ref, lse_ref, acc_sc, m_sc, l_sc = refs[len(live_specs):]
        _fwd_kernel(q_ref, k_ref, v_ref, bias_ref, qs_ref, ks_ref, seed_ref,
                    qp_ref, kp_ref,
                    o_ref, lse_ref, acc_sc, m_sc, l_sc,
                    scale=scale, causal=causal, window=window, rate=rate,
                    nk=nk, n_inner=n_inner, banded=banded,
                    bq=bq, bk=bk, sq=sq, sk=sk, k_len=k_len)

    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h, nq, n_inner),
        in_specs=live_specs,
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, iq, j: (bh, iq, 0)),
            pl.BlockSpec((1, bq, 1), lambda bh, iq, j: (bh, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*live_args)
    return out.reshape(b, h, sq, d), lse.reshape(b, h, sq)  # lse drops the lane dim


# --------------------------------------------------------------------------
# backward kernels (recompute P from saved lse)
# --------------------------------------------------------------------------


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                   bias_ref, qs_ref, ks_ref, seed_ref, glse_ref,
                   qp_ref, kp_ref, dq_ref, dq_sc,
                   *, scale, causal, window, rate, nk, n_inner, banded,
                   bq, bk, sq, sk, k_len=None):
    j = pl.program_id(2)
    iq = pl.program_id(1)
    bh = pl.program_id(0)   # hoisted out of the pl.when branch (see fwd)
    if banded:
        ik, in_range = _band_pos(_band_k_lo(iq, bq, bk, sk - sq, window),
                                 j, nk)
    else:
        ik, in_range = j, True

    @pl.when(j == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    live = (_block_live_dynamic(qp_ref, kp_ref, causal, window)
            if qp_ref is not None
            else _block_live(iq, ik, bq, bk, sq, sk, causal, window))
    run = jnp.logical_and(live, in_range)

    @pl.when(run)
    def _step():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        # clamp: fully-masked rows carry lse = NEG_INF (merge-friendly);
        # exp(s - NEG_INF) would explode, exp(s - NEG_INF/2) underflows
        lse = jnp.maximum(lse_ref[0], NEG_INF * 0.5)   # (bq, 1) column
        delta = dl_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if bias_ref is not None:
            s = s + bias_ref[0].astype(jnp.float32)
        q_seg = qs_ref[0] if qs_ref is not None else None
        k_seg = ks_ref[0] if ks_ref is not None else None
        s = s + _mask_block(
            iq, ik, bq, bk, sq, sk, causal, window, q_seg, k_seg,
            q_pos=qp_ref[...] if qp_ref is not None else None,
            k_pos=kp_ref[...] if kp_ref is not None else None,
            k_len=k_len)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if rate > 0.0:
            # dP flows only through kept probabilities: dD = dO V^T,
            # dP = keep/(1-r) * dD; delta = rowsum(dO*O) still equals
            # rowsum(P*dP) because the dropout scale cancels in the sum
            row = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            col = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            keep = _dropout_keep(seed_ref[0], bh, row, col, rate)
            dp = jnp.where(keep, dp / (1.0 - rate), 0.0)
        ds = p * (dp - delta)
        if glse_ref is not None:
            # lse is also an output: dlse_i/ds_ij = p_ij (undropped)
            ds = ds + p * glse_ref[0]
        ds = ds.astype(k.dtype)
        dq_sc[...] = dq_sc[...] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(j == n_inner - 1)
    def _fin():
        dq_ref[0] = dq_sc[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                    bias_ref, qs_ref, ks_ref, seed_ref, glse_ref,
                    qp_ref, kp_ref, dk_ref, dv_ref, dk_sc, dv_sc,
                    *, scale, causal, window, rate, nq, nq_inner, banded,
                    h, hk, bq, bk, sq, sk, k_len=None):
    # inner grid dim sweeps (q-head of the GQA group) x (q block):
    # t = g * nq_inner + j. The kv block stays resident; dk/dv accumulate
    # in VMEM across the whole group — no materialized kv repeat. With a
    # sliding window, j walks only the band's q blocks (see fwd).
    t = pl.program_id(2)
    j = t % nq_inner
    ik = pl.program_id(1)
    bhk = pl.program_id(0)  # hoisted out of the pl.when branch (see fwd)
    n_inner = (h // hk) * nq_inner
    if banded:
        iq, in_range = _band_pos(_band_q_lo(ik, bq, bk, sk - sq), j, nq)
    else:
        iq, in_range = j, True

    @pl.when(t == 0)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    live = (_block_live_dynamic(qp_ref, kp_ref, causal, window)
            if qp_ref is not None
            else _block_live(iq, ik, bq, bk, sq, sk, causal, window))
    run = jnp.logical_and(live, in_range)

    @pl.when(run)
    def _step():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = jnp.maximum(lse_ref[0], NEG_INF * 0.5)   # (bq, 1) column
        delta = dl_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if bias_ref is not None:
            s = s + bias_ref[0].astype(jnp.float32)
        q_seg = qs_ref[0] if qs_ref is not None else None
        k_seg = ks_ref[0] if ks_ref is not None else None
        s = s + _mask_block(
            iq, ik, bq, bk, sq, sk, causal, window, q_seg, k_seg,
            q_pos=qp_ref[...] if qp_ref is not None else None,
            k_pos=kp_ref[...] if kp_ref is not None else None,
            k_len=k_len)
        p = jnp.exp(s - lse)                       # (bq, bk)
        p_v = p                                    # what multiplied V
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if rate > 0.0:
            # flat q-head index for the mask: this kv head's group,
            # offset by the inner sweep's q-head g = t // nq_inner
            bh = (bhk // hk) * h + (bhk % hk) * (h // hk) + t // nq_inner
            row = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            col = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            keep = _dropout_keep(seed_ref[0], bh, row, col, rate)
            p_v = jnp.where(keep, p / (1.0 - rate), 0.0)   # dropped probs
            dp = jnp.where(keep, dp / (1.0 - rate), 0.0)
        dv_sc[...] = dv_sc[...] + jax.lax.dot_general(
            p_v.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)    # (bk, d)
        ds = p * (dp - delta)
        if glse_ref is not None:
            ds = ds + p * glse_ref[0]
        ds = ds.astype(q.dtype)
        dk_sc[...] = dk_sc[...] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(t == n_inner - 1)
    def _fin():
        dk_ref[0] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[...].astype(dv_ref.dtype)


def _flash_bwd_pallas(res, g, delta, seed, scale, causal, window, rate,
                      bq, bk, interpret, glse=None,
                      q_pos=None, k_pos=None, kv_len=None):
    q, k, v, bias, q_seg, k_seg, out, lse = res
    b, h, sq, d = q.shape
    hk = k.shape[1]
    group = h // hk          # GQA: q heads per shared kv head
    skp = k.shape[2]         # see _flash_fwd_pallas
    sk = skp if kv_len is None else kv_len
    k_len = None if sk == skp else sk
    bq = _pick_block(sq, bq)
    bk = _pick_block(skp, bk)
    nq, nk = sq // bq, skp // bk

    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * hk, skp, d)
    vf = v.reshape(b * hk, skp, d)
    dof = g.reshape(b * h, sq, d)
    lsef = lse.reshape(b * h, sq, 1)     # column layout (Mosaic tiling)
    dlf = delta.reshape(b * h, sq, 1)
    if bias is not None:
        b_b, h_b, sq_b, sk_b = bias.shape
        bias_f = bias.reshape(b_b * h_b, sq_b, sk_b)

    def build(iq_of, ik_of, qh_of, kvh_of, batch_of):
        """Block specs for (q, k, v, do, lse, dl [, bias][, segs]).

        ``*_of`` map grid indices -> q-block / k-block / flat-q-head /
        flat-kv-head / batch index; the dq and dkv passes differ only in
        those maps.
        """
        qi = lambda *g_: (qh_of(*g_), iq_of(*g_), 0)   # noqa: E731
        ki = lambda *g_: (kvh_of(*g_), ik_of(*g_), 0)  # noqa: E731
        specs = [
            pl.BlockSpec((1, bq, d), qi),
            pl.BlockSpec((1, bk, d), ki),
            pl.BlockSpec((1, bk, d), ki),
            pl.BlockSpec((1, bq, d), qi),
            pl.BlockSpec((1, bq, 1), qi),
            pl.BlockSpec((1, bq, 1), qi),
        ]
        arr = [qf, kf, vf, dof, lsef, dlf]
        if bias is not None:
            def bias_idx(*g_):
                ib = batch_of(*g_)
                ih = qh_of(*g_) - ib * h      # head within the batch
                return (ib % b_b * h_b + ih % h_b,
                        iq_of(*g_) if sq_b > 1 else 0,
                        ik_of(*g_) if sk_b > 1 else 0)
            specs.append(pl.BlockSpec(
                (1, bq if sq_b > 1 else 1, bk if sk_b > 1 else 1),
                bias_idx))
            arr.append(bias_f)
        if q_seg is not None:
            specs.append(pl.BlockSpec(
                (1, bq, 1), lambda *g_: (batch_of(*g_), iq_of(*g_), 0)))
            specs.append(pl.BlockSpec(
                (1, 1, bk), lambda *g_: (batch_of(*g_), 0, ik_of(*g_))))
            arr += [q_seg.reshape(*q_seg.shape, 1),
                    k_seg.reshape(k_seg.shape[0], 1, k_seg.shape[1])]
        if rate > 0.0:
            specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
            arr.append(jnp.asarray(seed, jnp.uint32).reshape(1))
        if glse is not None:
            specs.append(pl.BlockSpec((1, bq, 1), qi))
            arr.append(glse.astype(jnp.float32).reshape(b * h, sq, 1))
        if q_pos is not None:
            specs.append(pl.BlockSpec(
                (bq, 1), lambda *g_: (iq_of(*g_), 0)))
            specs.append(pl.BlockSpec(
                (1, bk), lambda *g_: (0, ik_of(*g_))))
            arr += [jnp.asarray(q_pos, jnp.int32).reshape(sq, 1),
                    jnp.asarray(k_pos, jnp.int32).reshape(1, skp)]
        return specs, arr

    # banded sliding window (see _flash_fwd_pallas): inner dims walk only
    # the band's blocks, clamped + masked at the edges
    dq_banded, nk_inner = _band(window, bq, bk, nk,
                                dynamic=q_pos is not None)

    def dq_ik_of(iq, j):
        if not dq_banded:
            return j
        return _band_pos(_band_k_lo(iq, bq, bk, sk - sq, window), j, nk)[0]

    # dq pass: grid (b*h, iq, j); kv heads shared via the index map
    specs, arr = build(
        iq_of=lambda bh, a, b_: a,
        ik_of=lambda bh, a, b_: dq_ik_of(a, b_),
        qh_of=lambda bh, a, b_: bh,
        kvh_of=lambda bh, a, b_: bh // group,
        batch_of=lambda bh, a, b_: bh // h,
    )

    def dq_kernel(*refs):
        n = len(specs)
        it = iter(refs[:n])
        base = [next(it) for _ in range(6)]
        bias_ref = next(it) if bias is not None else None
        qs_ref = next(it) if q_seg is not None else None
        ks_ref = next(it) if q_seg is not None else None
        seed_ref = next(it) if rate > 0.0 else None
        glse_ref = next(it) if glse is not None else None
        qp_ref = next(it) if q_pos is not None else None
        kp_ref = next(it) if q_pos is not None else None
        dq_ref, dq_sc = refs[n:]
        _bwd_dq_kernel(*base, bias_ref, qs_ref, ks_ref, seed_ref, glse_ref,
                       qp_ref, kp_ref, dq_ref, dq_sc,
                       scale=scale, causal=causal, window=window,
                       rate=rate, nk=nk, n_inner=nk_inner,
                       banded=dq_banded, bq=bq, bk=bk, sq=sq, sk=sk,
                       k_len=k_len)

    dq = pl.pallas_call(
        dq_kernel,
        grid=(b * h, nq, nk_inner),
        in_specs=specs,
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, iq, j: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*arr)

    # dk/dv pass: grid (b*hk, ik, group*nq_inner) — the kv block stays
    # put while the inner dim walks every (q head of the group, q block
    # in the band); dk/dv accumulate in VMEM so GQA needs no
    # materialized repeat and backward peak memory is independent of
    # h/hk.
    dkv_banded, nq_inner = _band(window, bk, bq, nq,
                                 dynamic=q_pos is not None)

    def dkv_iq_of(ik, j):
        if not dkv_banded:
            return j
        return _band_pos(_band_q_lo(ik, bq, bk, sk - sq), j, nq)[0]
    n_inner = group * nq_inner
    qhead = lambda bhk, a, t: (                      # noqa: E731
        (bhk // hk) * h + (bhk % hk) * group + t // nq_inner)
    specs, arr = build(
        iq_of=lambda bhk, a, t: dkv_iq_of(a, t % nq_inner),
        ik_of=lambda bhk, a, t: a,
        qh_of=qhead,
        kvh_of=lambda bhk, a, t: bhk,
        batch_of=lambda bhk, a, t: bhk // hk,
    )

    def dkv_kernel(*refs):
        n = len(specs)
        it = iter(refs[:n])
        base = [next(it) for _ in range(6)]
        bias_ref = next(it) if bias is not None else None
        qs_ref = next(it) if q_seg is not None else None
        ks_ref = next(it) if q_seg is not None else None
        seed_ref = next(it) if rate > 0.0 else None
        glse_ref = next(it) if glse is not None else None
        qp_ref = next(it) if q_pos is not None else None
        kp_ref = next(it) if q_pos is not None else None
        dk_ref, dv_ref, dk_sc, dv_sc = refs[n:]
        _bwd_dkv_kernel(*base, bias_ref, qs_ref, ks_ref, seed_ref, glse_ref,
                        qp_ref, kp_ref, dk_ref, dv_ref, dk_sc, dv_sc,
                        scale=scale, causal=causal, window=window,
                        rate=rate, nq=nq, nq_inner=nq_inner,
                        banded=dkv_banded, h=h, hk=hk,
                        bq=bq, bk=bk, sq=sq, sk=sk, k_len=k_len)

    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(b * hk, nk, n_inner),
        in_specs=specs,
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda bhk, ik, t: (bhk, ik, 0)),
            pl.BlockSpec((1, bk, d), lambda bhk, ik, t: (bhk, ik, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * hk, skp, d), k.dtype),
            jax.ShapeDtypeStruct((b * hk, skp, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*arr)

    return (dq.reshape(b, h, sq, d),
            dk.reshape(b, hk, skp, d),
            dv.reshape(b, hk, skp, d))


# --------------------------------------------------------------------------
# XLA reference path
# --------------------------------------------------------------------------


def _attention_xla(q, k, v, bias, q_seg, k_seg, scale, causal,
                   window=None, dropout_rate=0.0, dropout_seed=None,
                   return_lse=False, q_pos=None, k_pos=None):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    hk = k.shape[1]
    if hk != h:
        # GQA: einsum over a kv-head-group axis — never materializes
        # repeated K/V (jnp.repeat here is an h/hk x KV HBM spike at
        # long sk, and this path serves every CPU test and any
        # Mosaic-fallback production run)
        group = h // hk
        s = jnp.einsum(
            "bkgqd,bkcd->bkgqc",
            (q.astype(jnp.float32) * scale).reshape(b, hk, group, sq, d),
            k.astype(jnp.float32)).reshape(b, h, sq, sk)
    else:
        s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32) * scale,
                       k.astype(jnp.float32))
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if causal or window is not None:
        # one (sq, sk) block = the full matrix; same mask code as the kernel
        if q_pos is not None and jnp.ndim(q_pos) == 2:
            # per-lane positions: the mask broadcasts to (b, sq, sk)
            s = s + _mask_block(
                0, 0, sq, sk, sq, sk, causal, window, None, None,
                q_pos=jnp.asarray(q_pos, jnp.int32).reshape(b, sq, 1),
                k_pos=jnp.asarray(k_pos, jnp.int32).reshape(b, 1, sk),
            )[:, None]
        else:
            s = s + _mask_block(
                0, 0, sq, sk, sq, sk, causal, window, None, None,
                q_pos=(jnp.asarray(q_pos, jnp.int32).reshape(sq, 1)
                       if q_pos is not None else None),
                k_pos=(jnp.asarray(k_pos, jnp.int32).reshape(1, sk)
                       if k_pos is not None else None))[None, None]
    if q_seg is not None:
        seg = q_seg[:, None, :, None] != k_seg[:, None, None, :]
        s = jnp.where(seg, NEG_INF, s)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    l = jnp.sum(e, axis=-1, keepdims=True)
    p = e / jnp.where(l > 0.0, l, 1.0)
    # fully-masked rows emit 0 (matches the Pallas kernel's guard)
    p = jnp.where(m > NEG_INF * 0.5, p, 0.0)
    if dropout_rate > 0.0:
        # same counter-based mask as the Pallas kernels — bit-identical
        # dropout across impls for a given seed
        bh = jnp.arange(b * h, dtype=jnp.uint32).reshape(b, h, 1, 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (1, 1, sq, sk), 2)
        col = jax.lax.broadcasted_iota(jnp.int32, (1, 1, sq, sk), 3)
        keep = _dropout_keep(dropout_seed, bh, row, col, dropout_rate)
        p = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
    if hk != h:
        out = jnp.einsum(
            "bkgqc,bkcd->bkgqd",
            p.reshape(b, hk, h // hk, sq, sk),
            v.astype(jnp.float32)).reshape(b, h, sq, d)
    else:
        out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    if return_lse:
        valid = m[..., 0] > NEG_INF * 0.5
        lse = jnp.where(valid, m[..., 0] + jnp.log(
            jnp.where(l[..., 0] > 0.0, l[..., 0], 1.0)), NEG_INF)
        return out.astype(q.dtype), lse
    return out.astype(q.dtype)


# --------------------------------------------------------------------------
# public API
# --------------------------------------------------------------------------


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(7, 8, 9, 10, 11, 12, 13, 14, 15, 16))
def _flash(q, k, v, bias, q_seg, k_seg, seed, scale, causal, window, rate,
           bq, bk, bbq, bbk, interpret, kv_len):
    out, _ = _flash_fwd_pallas(q, k, v, bias, q_seg, k_seg, seed, scale,
                               causal, window, rate, bq, bk, interpret,
                               kv_len=kv_len)
    return out


def _flash_fwd_rule(q, k, v, bias, q_seg, k_seg, seed, scale, causal,
                    window, rate, bq, bk, bbq, bbk, interpret, kv_len):
    out, lse = _flash_fwd_pallas(q, k, v, bias, q_seg, k_seg, seed, scale,
                                 causal, window, rate, bq, bk, interpret,
                                 kv_len=kv_len)
    return out, (q, k, v, bias, q_seg, k_seg, seed, out, lse)


def _flash_bwd_rule(scale, causal, window, rate, bq, bk, bbq, bbk,
                    interpret, kv_len, res, g):
    q, k, v, bias, q_seg, k_seg, seed, out, lse = res
    core = (q, k, v, bias, q_seg, k_seg, out, lse)
    delta = jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32), axis=-1)
    dq, dk, dv = _flash_bwd_pallas(core, g, delta, seed, scale, causal,
                                   window, rate, bbq, bbk, interpret,
                                   kv_len=kv_len)
    return _finish_bwd(core, g, delta, dq, dk, dv, seed, scale, causal,
                       window, rate, kv_len=kv_len)


def _finish_bwd(res, g, delta, dq, dk, dv, seed, scale, causal, window,
                rate, glse=None, q_pos=None, k_pos=None, with_pos=False,
                kv_len=None):
    """Shared tail of the backward rule: bias cotangent by recompute
    plus the integer (segment-id / seed) cotangents."""
    q, k, v, bias, q_seg, k_seg, out, lse = res
    dbias = None
    if bias is not None:
        # bias grad by recompute, one (batch, head) slice at a time —
        # O(sq*sk) live memory, scatter-added into the (possibly
        # broadcast-shaped) bias cotangent.
        b, h, sq, _ = q.shape
        skp = k.shape[2]                # see _flash_fwd_pallas
        sk = skp if kv_len is None else kv_len
        k_len = None if sk == skp else sk
        group = h // k.shape[1]         # GQA: kv head shared per group
        b_b, h_b, sq_b, sk_b = bias.shape
        bmap = _bias_index_map(b_b, h_b, h)

        def body(bh, acc):
            ib, ih = bh // h, bh % h
            s = jax.lax.dot_general(
                q[ib, ih].astype(jnp.float32) * scale,
                k[ib, ih // group].astype(jnp.float32),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            s = s + bias[ib % b_b, ih % h_b].astype(jnp.float32)
            if causal or window is not None or k_len is not None:
                s = s + _mask_block(
                    0, 0, sq, skp, sq, sk, causal, window, None, None,
                    q_pos=(q_pos.reshape(sq, 1)
                           if q_pos is not None else None),
                    k_pos=(k_pos.reshape(1, skp)
                           if k_pos is not None else None),
                    k_len=k_len)
            if q_seg is not None:
                seg = q_seg[ib][:, None] != k_seg[ib][None, :]
                s = jnp.where(seg, NEG_INF, s)
            p = jnp.exp(
                s - jnp.maximum(lse[ib, ih][:, None], NEG_INF * 0.5))
            dp = jax.lax.dot_general(
                g[ib, ih].astype(jnp.float32),
                v[ib, ih // group].astype(jnp.float32),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            if rate > 0.0:
                row = jax.lax.broadcasted_iota(jnp.int32, (sq, skp), 0)
                col = jax.lax.broadcasted_iota(jnp.int32, (sq, skp), 1)
                keep = _dropout_keep(seed, bh, row, col, rate)
                dp = jnp.where(keep, dp / (1.0 - rate), 0.0)
            ds = p * (dp - delta[ib, ih][:, None])
            if glse is not None:
                ds = ds + p * glse[ib, ih][:, None]
            if sq_b == 1:
                ds = jnp.sum(ds, axis=0, keepdims=True)
            if sk_b == 1:
                ds = jnp.sum(ds, axis=1, keepdims=True)
            return acc.at[bmap(bh)].add(ds)

        acc = jax.lax.fori_loop(
            0, b * h, body, jnp.zeros((b_b * h_b, sq_b, sk_b), jnp.float32))
        dbias = acc.reshape(bias.shape).astype(bias.dtype)

    def int_ct(a):
        import numpy as np
        return (None if a is None
                else np.zeros(a.shape, dtype=jax.dtypes.float0))

    cts = (dq, dk, dv, dbias, int_ct(q_seg), int_ct(k_seg), int_ct(seed))
    if with_pos or q_pos is not None or k_pos is not None:
        cts = cts + (int_ct(q_pos), int_ct(k_pos))
    return cts


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(9, 10, 11, 12, 13, 14, 15, 16, 17, 18))
def _flash_with_lse(q, k, v, bias, q_seg, k_seg, seed, q_pos, k_pos,
                    scale, causal, window, rate, bq, bk, bbq, bbk,
                    interpret, kv_len):
    """Like ``_flash`` but also returns the per-row logsumexp (fp32,
    (b, h, sq); NEG_INF on fully-masked rows) as a differentiable
    output — the merge signal for ring/blockwise attention. Accepts
    dynamic global positions for chunked causal masking."""
    return _flash_fwd_pallas(q, k, v, bias, q_seg, k_seg, seed, scale,
                             causal, window, rate, bq, bk, interpret,
                             q_pos=q_pos, k_pos=k_pos, kv_len=kv_len)


def _flash_lse_fwd_rule(q, k, v, bias, q_seg, k_seg, seed, q_pos, k_pos,
                        scale, causal, window, rate, bq, bk, bbq, bbk,
                        interpret, kv_len):
    out, lse = _flash_fwd_pallas(q, k, v, bias, q_seg, k_seg, seed, scale,
                                 causal, window, rate, bq, bk, interpret,
                                 q_pos=q_pos, k_pos=k_pos, kv_len=kv_len)
    return (out, lse), (q, k, v, bias, q_seg, k_seg, seed, q_pos, k_pos,
                        out, lse)


def _flash_lse_bwd_rule(scale, causal, window, rate, bq, bk, bbq, bbk,
                        interpret, kv_len, res, gs):
    g, glse = gs
    q, k, v, bias, q_seg, k_seg, seed, q_pos, k_pos, out, lse = res
    core = (q, k, v, bias, q_seg, k_seg, out, lse)
    delta = jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32), axis=-1)
    dq, dk, dv = _flash_bwd_pallas(core, g, delta, seed, scale, causal,
                                   window, rate, bbq, bbk, interpret,
                                   glse=glse, q_pos=q_pos, k_pos=k_pos,
                                   kv_len=kv_len)
    return _finish_bwd(core, g, delta, dq, dk, dv, seed, scale, causal,
                       window, rate, glse=glse, q_pos=q_pos, k_pos=k_pos,
                       with_pos=True, kv_len=kv_len)


_flash_with_lse.defvjp(_flash_lse_fwd_rule, _flash_lse_bwd_rule)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    bias: Optional[jax.Array] = None,
    segment_ids: Optional[jax.Array] = None,
    kv_segment_ids: Optional[jax.Array] = None,
    causal: bool = False,
    window_size: Optional[int] = None,
    softmax_scale: Optional[float] = None,
    dropout_rate: float = 0.0,
    dropout_rng: Optional[jax.Array] = None,
    block_q: int = 1024,
    block_k: int = 1024,
    bwd_block_q: Optional[int] = None,
    bwd_block_k: Optional[int] = None,
    impl: Optional[str] = None,
    return_lse: bool = False,
    q_positions: Optional[jax.Array] = None,
    kv_positions: Optional[jax.Array] = None,
):
    """Memory-efficient attention over (batch, heads, seq, head_dim).

    ``segment_ids`` (batch, seq_q) int32 enables packed-varlen batches —
    tokens only attend within their own segment (the TPU equivalent of the
    reference's cu_seqlens packed layout, ref apex/contrib/fmha/fmha.py:33-74).
    ``bias`` is an additive fp32 logit bias broadcastable to
    (batch, heads, seq_q, seq_k) — covers the reference's additive-mask
    multihead_attn variants. ``window_size=w`` (sliding-window / local
    attention, beyond the reference) restricts each query to its last
    ``w`` keys up to the diagonal. The kernel grids are banded: the
    inner dimension walks only the k (resp. q) blocks each band
    touches, so both FLOPs and DMA traffic scale O(S·w), not O(S²).

    ``q_positions`` / ``kv_positions`` replace the static causal and
    window geometry by token positions: (seq,) arrays shared by the
    batch (ring / blockwise chunks), or (batch, seq) arrays a lane
    (the serving paths, where each lane's keys start at a position of
    its own; forward only).

    ``return_lse=True`` additionally returns the per-row logsumexp
    (fp32, (batch, heads, seq_q); NEG_INF on fully-masked rows) as a
    differentiable output — chunk results merge exactly via
    ``logaddexp`` (the ring/blockwise-attention combine).

    ``dropout_rate`` applies dropout to the attention probabilities
    inside the kernel (the reference's fused softmax+dropout, ref
    apex/contrib/csrc/multihead_attn/): the mask comes from a
    counter-based hash seeded by ``dropout_rng``, so the forward and
    backward kernels — and the XLA path — regenerate the identical mask.
    """
    impl = resolve_impl(impl)
    if bias is not None:
        b, h, sq, sk = (q.shape[0], q.shape[1], q.shape[2], k.shape[2])
        ok = (bias.ndim == 4
              and bias.shape[0] in (1, b) and bias.shape[1] in (1, h)
              and bias.shape[2] in (1, sq) and bias.shape[3] in (1, sk))
        if not ok:
            raise ValueError(
                f"bias must be 4-D with each dim 1 or full "
                f"({(b, h, sq, sk)}); got shape {bias.shape}")
    if q.shape[1] % k.shape[1] or k.shape[1] != v.shape[1]:
        raise ValueError(
            f"kv heads ({k.shape[1]}/{v.shape[1]}) must be equal and "
            f"divide q heads ({q.shape[1]})")
    if (q_positions is None) != (kv_positions is None):
        raise ValueError("q_positions and kv_positions must be given together")
    if q_positions is not None and not causal:
        raise ValueError("positions only affect causal/window masking; "
                         "pass causal=True")
    per_lane = q_positions is not None and jnp.ndim(q_positions) == 2
    if per_lane and jnp.ndim(kv_positions) != 2:
        raise ValueError("per-lane q_positions (batch, seq_q) need "
                         "per-lane kv_positions (batch, seq_k)")
    if q_positions is not None and dropout_rate > 0.0:
        # the dropout counter hashes block-LOCAL row/col indices, so a
        # chunked (ring/blockwise) call would sample a different mask
        # than the equivalent unchunked call — silently breaking the
        # chunk-merge == full identity that positions exist to provide
        raise ValueError(
            "dropout_rate > 0 with q_positions/kv_positions is not "
            "supported: the dropout mask is keyed on local indices and "
            "would not match across chunked and unchunked calls")
    if window_size is not None:
        if not causal:
            raise ValueError("window_size requires causal=True")
        if window_size < 1:
            raise ValueError(f"window_size must be >= 1, got {window_size}")
    if softmax_scale is None:
        softmax_scale = q.shape[-1] ** -0.5
    if segment_ids is not None and kv_segment_ids is None:
        kv_segment_ids = segment_ids
    elif kv_segment_ids is not None and segment_ids is None:
        # key-side-only masking (e.g. padded keys in cross attention):
        # queries are all segment 0 and attend only to segment-0 keys.
        segment_ids = jnp.zeros(
            (q.shape[0], q.shape[2]), kv_segment_ids.dtype)
    seed = None
    if not (0.0 <= dropout_rate < 1.0):
        raise ValueError(f"dropout_rate must be in [0, 1), got {dropout_rate}")
    if dropout_rate > 0.0:
        if dropout_rng is None:
            raise ValueError("dropout_rate > 0 requires dropout_rng")
        # fold the key into one uint32 seed for the counter-based mask
        # (accepts typed PRNG keys and legacy raw uint32 key arrays)
        if jnp.issubdtype(jnp.asarray(dropout_rng).dtype, jax.dtypes.prng_key):
            kd = jax.random.key_data(dropout_rng)
        else:
            kd = jnp.asarray(dropout_rng)
        kd = kd.astype(jnp.uint32).ravel()
        seed = kd[0] if kd.size == 1 else kd[0] ^ kd[1]
    # backward blocks default to the forward's; tuned separately on-chip
    # (the dq/dkv kernels have different reuse patterns than the fwd)
    bbq = bwd_block_q if bwd_block_q is not None else block_q
    bbk = bwd_block_k if bwd_block_k is not None else block_k
    if impl == "xla":
        return _attention_xla(q, k, v, bias, segment_ids, kv_segment_ids,
                              softmax_scale, causal, window_size,
                              dropout_rate, seed, return_lse=return_lse,
                              q_pos=q_positions, k_pos=kv_positions)
    # a key count no lane-aligned block divides: pad the k axis, mask
    # the tail in the kernels (_kv_pad). jnp.pad's transpose slices the
    # padded rows off dk/dv/dbias again.
    sk = k.shape[2]
    pad = max(_kv_pad(sk, block_k), _kv_pad(sk, bbk))
    kv_len = None
    if pad:
        kv_len = sk

        def pad_keys(x, axis):
            widths = [(0, 0)] * x.ndim
            widths[axis] = (0, pad)
            return jnp.pad(x, widths)

        k, v = pad_keys(k, 2), pad_keys(v, 2)
        if kv_segment_ids is not None:
            kv_segment_ids = pad_keys(kv_segment_ids, 1)
        if kv_positions is not None:
            kv_positions = pad_keys(jnp.asarray(kv_positions),
                                    jnp.ndim(kv_positions) - 1)
        if bias is not None and bias.shape[3] == sk:
            bias = pad_keys(bias, 3)
    if per_lane:
        # the serving paths' masks: forward only, no gradient rule
        out = _flash_fwd_pallas(
            q, k, v, bias, segment_ids, kv_segment_ids, seed,
            softmax_scale, causal, window_size, float(dropout_rate),
            block_q, block_k, interpret_flag(impl),
            q_pos=q_positions, k_pos=kv_positions, kv_len=kv_len)
        return out if return_lse else out[0]
    if return_lse or q_positions is not None:
        out = _flash_with_lse(
            q, k, v, bias, segment_ids, kv_segment_ids, seed,
            q_positions, kv_positions,
            softmax_scale, causal, window_size, float(dropout_rate),
            block_q, block_k, bbq, bbk, interpret_flag(impl), kv_len)
        return out if return_lse else out[0]
    return _flash(q, k, v, bias, segment_ids, kv_segment_ids, seed,
                  softmax_scale, causal, window_size, float(dropout_rate),
                  block_q, block_k, bbq, bbk, interpret_flag(impl), kv_len)


__all__ = ["flash_attention"]
