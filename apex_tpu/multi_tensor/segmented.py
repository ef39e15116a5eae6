"""Segment-resident single-pass LAMB kernel.

The two-stage flat LAMB (ops.fused_lamb_update) pays ~10 HBM accesses
per element: stage 1 materializes the update term ``u`` so the
per-tensor trust ratios can be reduced before stage 2 re-reads ``p``
and ``u``. XLA gives optax a better deal on VMEM-sized leaves by
fusing each leaf's two kernels with the leaf resident on-chip. This
kernel takes that trick further, TPU-native:

- the flat buffer is laid out in *segments* (flat_buffer.
  segmented_space): every small leaf lives inside one segment, so its
  norm is a segment-local reduction;
- the grid runs (segment, phase, chunk). Phase 0 streams p/m/v/g
  chunks, writes m'/v' straight out, stashes ``u`` and ``p`` in VMEM
  scratch, and accumulates per-slot ‖p‖²/‖u‖² through a slot one-hot
  (slot ids are streamed per subtile — NO dynamic gathers, the
  construct Mosaic's compiler crashes on — and every intermediate is
  rank >= 2: a reshape to or from a rank-1 vector aborts its layout
  inference, which is what kept this kernel off the chip);
- phase 1 turns the accumulators into trust ratios once, then writes
  p' chunk-by-chunk from scratch. Phase-1 input blocks map to the
  phase-0 resident index (no refetch; pallas skips the DMA when the
  mapped block is unchanged) and the m'/v' output blocks stay mapped
  at their last phase-0 index (no extra writeback), so total traffic
  is r(p,m,v,g) + w(p',m',v') = **7 accesses per element** — below
  optax's per-leaf fusion, with one kernel launch for the whole model
  instead of per-leaf kernel pairs.

Leaves bigger than a segment (the embedding class) fall back to the
two-stage path over their contiguous slices — a few percent of the
params at BERT/GPT scale.

Ref parity: the math is csrc/multi_tensor_lamb.cu stage1 (:41-230) /
stage2 (:234-330) exactly as ops.fused_lamb_update implements it; this
module only changes the schedule. The interpret/xla impl resolves to
ops.fused_lamb_update (identical math), so CPU tests pin the pallas
schedule against the two-stage reference on the SAME segmented layout.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu._backend import resolve_impl
from apex_tpu.multi_tensor.engine import LANES, PER_TENSOR_TILE_ROWS
from apex_tpu.multi_tensor.flat_buffer import FlatSpace, SegmentMeta

CHUNK_ROWS = 512                      # rows per streamed block
CHUNK = CHUNK_ROWS * LANES            # elements per chunk


def _stage1_math(p_, m_, v_, g_, b1, b2, beta3, eps, wd, bc1, bc2,
                 mode, inv_scale):
    """Stage-1 update-term math, identical to ops.fused_lamb_update's
    (ref csrc/multi_tensor_lamb.cu:41-230)."""
    g_ = g_ / inv_scale
    g_eff = jnp.where(mode > 0.5, g_, g_ + wd * p_)
    m2 = b1 * m_ + beta3 * g_eff
    v2 = b2 * v_ + (1.0 - b2) * g_eff * g_eff
    u = (m2 / bc1) / (jnp.sqrt(v2 / bc2) + eps)
    u = u + jnp.where(mode > 0.5, wd * p_, 0.0)
    return u, m2, v2


def _small_segment_pass(
    p, m, v, g, *,
    meta: SegmentMeta,
    scalars: jax.Array,               # (10,) f32: b1,b2,beta3,eps,wd,
                                      # bc1,bc2,mode,inv_scale,lr
    use_nvlamb: bool,
    wd_is_zero: bool,
    out_dtype,
    sr_seed: Optional[jax.Array],
    interpret: bool = False,
    stash_p: bool = True,
    u_dtype=jnp.float32,
    with_grad_norm: bool = False,
):
    """The one-pass pallas kernel over the small segments. Regions not
    in meta.small_segments flow through untouched via input/output
    aliasing. Returns (p2, m2, v2, found[, gg_per_slot]).

    ``with_grad_norm=True`` additionally accumulates per-slot sums of
    squares of the RAW streamed gradient through the same phase-0
    one-hot reductions that build the ‖p‖²/‖u‖² accumulators (acc row 3),
    and dumps them per segment — per-tensor grad norms at zero extra
    HBM passes. Off by default so the flag cannot perturb the
    chip-validated default schedule.

    VMEM scratch knobs (the per-core budget is ~16 MB, flat_buffer.
    DEFAULT_SEG_VMEM_BUDGET):

    - ``stash_p=True`` keeps the phase-0 ``p`` chunks resident
      (seg_elems fp32 scratch) so phase 1 never touches HBM for them:
      7 accesses/element. ``False`` drops that buffer and re-streams
      ``p`` from HBM in phase 1 (the aliased output hasn't been
      written yet, so the read sees the original values): 8
      accesses/element, half the scratch — the right trade when it
      buys segments big enough to keep multi-MB leaves one-pass.
    - ``u_dtype=bfloat16`` halves the update-term stash. The stashed
      ``u`` is O(1) by construction (m̂/(√v̂+eps)), so bf16's ~2^-9
      relative error perturbs ``p2`` by lr*ratio*2^-9*|u| — far below
      optimizer noise, but outside the two-stage path's bitwise
      envelope, so it is opt-in, never a silent default.
    """
    n = p.shape[0]
    C = meta.seg_elems // CHUNK
    if C < 1 or meta.seg_elems % CHUNK:
        raise ValueError(f"seg_elems {meta.seg_elems} must be a "
                         f"multiple of the chunk {CHUNK}")
    n_small = len(meta.small_segments)
    sub_chunk = CHUNK_ROWS // PER_TENSOR_TILE_ROWS
    ms = meta.max_slots
    sr = sr_seed is not None

    seg_ids = jnp.asarray(np.asarray(meta.small_segments, np.int32))
    # (n_small, C*sub_chunk) -> one (sub_chunk, 1) column per chunk
    ids_col = jnp.asarray(
        np.asarray(meta.slot_ids, np.int32).reshape(-1, 1))

    def kernel(*args):
        if sr:
            (scal_ref, segid_ref, sr_ref, p_ref, m_ref, v_ref, g_ref,
             ids_ref, p2_ref, m2_ref, v2_ref, found_ref,
             *rest) = args
        else:
            (scal_ref, segid_ref, p_ref, m_ref, v_ref, g_ref,
             ids_ref, p2_ref, m2_ref, v2_ref, found_ref,
             *rest) = args
            sr_ref = None
        if with_grad_norm:
            gg_ref, *scratch = rest
        else:
            gg_ref, scratch = None, rest
        if stash_p:
            u_buf, p_buf, acc_ref = scratch
        else:
            (u_buf, acc_ref), p_buf = scratch, None
        s = pl.program_id(0)
        ph = pl.program_id(1)
        c = pl.program_id(2)

        b1, b2, beta3, eps, wd, bc1, bc2, mode, inv_scale, lr = (
            scal_ref[j] for j in range(10))

        def slot_one_hot():
            ids = ids_ref[...]                       # (sub_chunk, 1)
            slots = jax.lax.broadcasted_iota(
                jnp.int32, (sub_chunk, ms), 1)
            return (ids == slots).astype(jnp.float32)

        def slot_sums(sq, oh):
            """(1, ms) per-slot sums of the (CHUNK_ROWS, LANES) block
            ``sq``: row-group then lane reduction per subtile, routed
            to slots through the one-hot. Every intermediate stays
            rank >= 2 — Mosaic's layout inference aborts the compiler
            on a reshape to or from a rank-1 vector."""
            per_sub = jnp.sum(
                jnp.sum(sq.reshape(sub_chunk, PER_TENSOR_TILE_ROWS,
                                   LANES), axis=1),
                axis=1, keepdims=True)               # (sub_chunk, 1)
            return jnp.sum(per_sub * oh, axis=0, keepdims=True)

        @pl.when((s == 0) & (ph == 0) & (c == 0))
        def _():
            found_ref[0, 0] = jnp.float32(0.0)

        @pl.when((ph == 0) & (c == 0))
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        @pl.when(ph == 0)
        def _():
            p_ = p_ref[...].astype(jnp.float32)
            m_ = m_ref[...].astype(jnp.float32)
            v_ = v_ref[...].astype(jnp.float32)
            g_ = g_ref[...].astype(jnp.float32)
            ok = jnp.all(jnp.isfinite(g_))
            found_ref[0, 0] = jnp.maximum(
                found_ref[0, 0],
                jnp.where(ok, 0.0, 1.0).astype(jnp.float32))
            u, m2, v2 = _stage1_math(
                p_, m_, v_, g_, b1, b2, beta3, eps, wd, bc1, bc2,
                mode, inv_scale)
            m2_ref[...] = m2
            v2_ref[...] = v2
            row0 = c * CHUNK_ROWS
            u_buf[pl.ds(row0, CHUNK_ROWS), :] = u.astype(u_buf.dtype)
            if stash_p:
                p_buf[pl.ds(row0, CHUNK_ROWS), :] = p_
            oh = slot_one_hot()                      # (sub_chunk, ms)
            acc_ref[0:1, :] = acc_ref[0:1, :] + slot_sums(p_ * p_, oh)
            acc_ref[1:2, :] = acc_ref[1:2, :] + slot_sums(u * u, oh)
            if with_grad_norm:
                # raw-grad sumsq rides the same per-slot reduction; row
                # 3 keeps clear of the ratio slot (row 2, phase 1)
                acc_ref[3:4, :] = acc_ref[3:4, :] + slot_sums(g_ * g_, oh)

        @pl.when((ph == 1) & (c == 0))
        def _():
            wn = jnp.sqrt(acc_ref[0:1, :])
            un = jnp.sqrt(acc_ref[1:2, :])
            ratio = jnp.where((wn > 0.0) & (un > 0.0), wn / un, 1.0)
            if not use_nvlamb and wd_is_zero:
                # ref: trust ratio only applies to decayed groups
                # unless NVLAMB (csrc/multi_tensor_lamb.cu:270-283)
                ratio = jnp.ones_like(ratio)
            acc_ref[2:3, :] = ratio
            if with_grad_norm:
                gg_ref[0] = acc_ref[3:4, :]

        @pl.when(ph == 1)
        def _():
            oh = slot_one_hot()                      # (sub_chunk, ms)
            # each subtile's ratio: its slot's entry of the ratio row
            rr = jnp.sum(oh * acc_ref[2:3, :], axis=1,
                         keepdims=True)              # (sub_chunk, 1)
            rr_rows = jnp.broadcast_to(
                jnp.broadcast_to(rr, (sub_chunk, LANES))[:, None, :],
                (sub_chunk, PER_TENSOR_TILE_ROWS, LANES),
            ).reshape(CHUNK_ROWS, LANES)
            row0 = c * CHUNK_ROWS
            u = u_buf[pl.ds(row0, CHUNK_ROWS), :].astype(jnp.float32)
            if stash_p:
                p_ = p_buf[pl.ds(row0, CHUNK_ROWS), :]
            else:
                # the aliased p2 region for this chunk is still unwritten
                # (phase 1 writes chunk c at step c), so the streamed
                # input block holds the original p
                p_ = p_ref[...].astype(jnp.float32)
            p2 = p_ - lr * rr_rows * u
            if sr:
                # Counter-based SR bits (murmur3 finalizer over the
                # global element index): plain uint32 ops lower through
                # BOTH Mosaic and interpret, so the interpret schedule
                # runs the exact chip stream — unlike pltpu.prng, whose
                # hardware stream has no interpret lowering and left
                # segmented+SR untestable off-chip. E[round] == p2 by
                # the same add-low-bits-and-truncate construction as
                # engine.stochastic_round_cast.
                chunk_row0 = (segid_ref[s] * C + c) * CHUNK_ROWS
                ridx = jax.lax.broadcasted_iota(
                    jnp.uint32, p2.shape, 0)
                cidx = jax.lax.broadcasted_iota(
                    jnp.uint32, p2.shape, 1)
                idx = ((chunk_row0.astype(jnp.uint32) + ridx)
                       * jnp.uint32(LANES) + cidx)
                h = idx ^ (sr_ref[0].astype(jnp.uint32)
                           * jnp.uint32(0x9E3779B9))
                h = (h ^ (h >> 16)) * jnp.uint32(0x85EBCA6B)
                h = (h ^ (h >> 13)) * jnp.uint32(0xC2B2AE35)
                bits = h ^ (h >> 16)
                xi = jax.lax.bitcast_convert_type(p2, jnp.uint32)
                trunc = jax.lax.bitcast_convert_type(
                    (xi + (bits & jnp.uint32(0xFFFF)))
                    & jnp.uint32(0xFFFF0000), jnp.float32)
                p2_sr = jnp.where(jnp.isfinite(p2), trunc, p2)
                p2_ref[...] = p2_sr.astype(p2_ref.dtype)
            else:
                p2_ref[...] = p2.astype(p2_ref.dtype)

    # index maps. prefetch refs trail the grid indices; `seg` below is
    # the segid prefetch ref. Phase-1 data blocks pin to the LAST
    # phase-0 index: unchanged in-blocks skip the refetch DMA, and the
    # m'/v' out blocks stay resident (flushed, correct, at the next
    # index change).
    def data_in(s, ph, c, scal, seg, *_):
        return (seg[s] * C + jnp.where(ph == 0, c, C - 1), 0)

    def p_in(s, ph, c, scal, seg, *_):
        # without the p stash, phase 1 re-streams each p chunk
        return (seg[s] * C + c, 0)

    def ids_in(s, ph, c, *_):
        return (s * C + c, 0)

    def p2_out(s, ph, c, scal, seg, *_):
        return (seg[s] * C + jnp.where(ph == 0, 0, c), 0)

    def mv_out(s, ph, c, scal, seg, *_):
        return (seg[s] * C + jnp.where(ph == 0, c, C - 1), 0)

    rows2 = (n // LANES, LANES)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3 if sr else 2,
        grid=(n_small, 2, C),
        in_specs=[
            pl.BlockSpec((CHUNK_ROWS, LANES),
                         data_in if (i or stash_p) else p_in,
                         memory_space=pltpu.VMEM)
            for i in range(4)
        ] + [
            pl.BlockSpec((sub_chunk, 1), ids_in,
                         memory_space=pltpu.VMEM)
        ],
        out_specs=[
            pl.BlockSpec((CHUNK_ROWS, LANES), p2_out,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((CHUNK_ROWS, LANES), mv_out,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((CHUNK_ROWS, LANES), mv_out,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1), lambda *_: (0, 0),
                         memory_space=pltpu.SMEM),
        ] + ([
            pl.BlockSpec((1, 1, ms), lambda s, ph, c, *_: (s, 0, 0),
                         memory_space=pltpu.VMEM)
        ] if with_grad_norm else []),
        scratch_shapes=(
            [pltpu.VMEM((C * CHUNK_ROWS, LANES), jnp.dtype(u_dtype))]
            + ([pltpu.VMEM((C * CHUNK_ROWS, LANES), jnp.float32)]
               if stash_p else [])
            + [pltpu.VMEM((8, ms), jnp.float32)]                # acc
        ),
    )

    prefetch = [scalars, seg_ids]
    if sr:
        prefetch.append(jnp.asarray(sr_seed, jnp.int32).reshape(1))
    n_prefetch = len(prefetch)

    outs = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(rows2, out_dtype),
            jax.ShapeDtypeStruct(rows2, jnp.float32),
            jax.ShapeDtypeStruct(rows2, jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ] + ([jax.ShapeDtypeStruct((n_small, 1, ms), jnp.float32)]
             if with_grad_norm else []),
        input_output_aliases=(
            {n_prefetch + 0: 0, n_prefetch + 1: 1, n_prefetch + 2: 2}
            if jnp.dtype(p.dtype) == jnp.dtype(out_dtype) else
            {n_prefetch + 1: 1, n_prefetch + 2: 2}
        ),
        interpret=interpret,
    )(*prefetch, p.reshape(rows2), m.reshape(rows2), v.reshape(rows2),
      g.reshape(rows2), ids_col)
    p2, m2, v2, found = outs[:4]
    ret = (p2.reshape(n), m2.reshape(n), v2.reshape(n), found[0, 0])
    if with_grad_norm:
        ret = ret + (outs[4][:, 0, :],)        # (n_small, ms) gg sums
    return ret


def fused_lamb_segmented_update(
    p, m, v, g, space: FlatSpace, meta: SegmentMeta, *,
    lr, beta1=0.9, beta2=0.999, eps=1e-6, step=1,
    weight_decay=0.0, bias_correction=True, grad_averaging=True,
    max_grad_norm=0.0, adam_w_mode=True, use_nvlamb=False,
    global_grad_norm=None, grad_scale=1.0, impl=None, sr_seed=None,
    stash_p=None, u_dtype=None, with_grad_norm=False,
):
    """LAMB step over a segment-aligned flat space: one-pass kernel for
    the small segments + the two-stage path for each large leaf.

    Drop-in for ops.fused_lamb_update on a (space, meta) pair from
    flat_buffer.segmented_space; on non-pallas impls it IS
    ops.fused_lamb_update (identical math, two-stage schedule), which
    is what CPU tests compare the kernel against.

    ``with_grad_norm=True`` appends per-tensor L2 norms of the RAW
    gradient, accumulated through the phase-0 one-hot reductions (small
    segments) and the stage-1 sumsq ride-along (large leaves) — no
    standalone norm pass over the buffer.

    Returns (p', m', v', found_inf[, grad_norm_per_tensor]).
    """
    from apex_tpu.multi_tensor.ops import (
        fused_lamb_compute_update_term,
        fused_lamb_update,
        lamb_trust_ratio,
        multi_tensor_l2norm,
    )
    from apex_tpu.multi_tensor.engine import fused_elementwise

    if meta.n_segments * meta.seg_elems != space.total:
        raise ValueError(
            f"SegmentMeta (n_segments={meta.n_segments}, "
            f"seg_elems={meta.seg_elems}) does not cover the space "
            f"(total={space.total}) — the meta was built against a "
            "different layout (e.g. a stale optimizer re-init)")
    if stash_p is None:
        stash_p = meta.stash_p
    if u_dtype is None:
        u_dtype = jnp.dtype(meta.u_dtype_name)
    impl = resolve_impl(impl)
    if sr_seed is not None and jnp.dtype(p.dtype) != jnp.dtype(jnp.bfloat16):
        # the in-kernel truncation targets the bf16 mantissa boundary;
        # any other param dtype would quantize silently (the engine's
        # two-stage path validates the same way, engine.py sr_outputs)
        raise ValueError(
            "stochastic rounding targets bfloat16 params; got "
            f"{jnp.dtype(p.dtype).name}")
    # interpret mode runs the REAL kernel schedule (CPU tests pin it
    # against the two-stage reference) — including SR, whose
    # counter-hash bits are impl-independent by construction
    kernel_capable = impl in ("pallas", "interpret")
    if not kernel_capable:
        return fused_lamb_update(
            p, m, v, g, space, lr=lr, beta1=beta1, beta2=beta2, eps=eps,
            step=step, weight_decay=weight_decay,
            bias_correction=bias_correction, grad_averaging=grad_averaging,
            max_grad_norm=max_grad_norm, adam_w_mode=adam_w_mode,
            use_nvlamb=use_nvlamb, global_grad_norm=global_grad_norm,
            grad_scale=grad_scale, impl=impl, sr_seed=sr_seed,
            with_grad_norm=with_grad_norm)

    step = jnp.asarray(step, jnp.float32)
    b1 = jnp.asarray(beta1, jnp.float32)
    b2 = jnp.asarray(beta2, jnp.float32)
    beta3 = jnp.asarray(1.0 - beta1 if grad_averaging else 1.0,
                        jnp.float32)
    bc1 = jnp.where(bias_correction, 1.0 - jnp.power(b1, step), 1.0)
    bc2 = jnp.where(bias_correction, 1.0 - jnp.power(b2, step), 1.0)
    if max_grad_norm and max_grad_norm > 0:
        if global_grad_norm is None:
            global_grad_norm, _ = multi_tensor_l2norm(g, impl=impl)
        global_grad_norm = (global_grad_norm
                            / jnp.asarray(grad_scale, jnp.float32))
        clip = jnp.maximum(global_grad_norm / max_grad_norm, 1.0)
    else:
        clip = jnp.float32(1.0)
    inv_scale = clip * jnp.asarray(grad_scale, jnp.float32)
    mode = jnp.float32(1.0 if adam_w_mode else 0.0)
    lr_f = jnp.asarray(lr, jnp.float32)
    scalars = jnp.stack([
        b1, b2, beta3, jnp.asarray(eps, jnp.float32),
        jnp.asarray(weight_decay, jnp.float32), bc1, bc2, mode,
        inv_scale, lr_f,
    ])

    leaf_gg = (jnp.zeros((space.num_leaves,), jnp.float32)
               if with_grad_norm else None)
    if len(meta.small_segments):
        outs = _small_segment_pass(
            p, m, v, g, meta=meta, scalars=scalars,
            use_nvlamb=use_nvlamb,
            wd_is_zero=not (weight_decay > 0.0), out_dtype=p.dtype,
            sr_seed=sr_seed, interpret=impl == "interpret",
            stash_p=stash_p, u_dtype=u_dtype,
            with_grad_norm=with_grad_norm)
        p2, m2, v2, found = outs[:4]
        if with_grad_norm:
            # (n_small, ms) per-slot gg -> per-leaf via the static
            # slot->leaf map (padding slots carry -1 and zero value)
            sl = jnp.asarray(np.asarray(meta.slot_leaf, np.int32))
            gg = outs[4]
            leaf_gg = jax.ops.segment_sum(
                jnp.where(sl >= 0, gg, 0.0).reshape(-1),
                jnp.maximum(sl, 0).reshape(-1),
                num_segments=space.num_leaves)
    else:
        p2, m2, v2 = p, m, v
        found = jnp.float32(0.0)

    # large leaves: two-stage over each contiguous slice. The aliased
    # kernel left their regions holding the ORIGINAL p/m/v values.
    for leaf_idx, start, plen in meta.large:
        size = space.sizes[leaf_idx]
        sl = lambda b: jax.lax.slice(b, (start,), (start + plen,))
        stage1_outs, found_l = \
            fused_lamb_compute_update_term(
                sl(p2).astype(jnp.float32), sl(m2), sl(v2), sl(g),
                beta1=b1, beta2=b2, beta3=beta3, eps=eps,
                weight_decay=weight_decay, bias_correction1=bc1,
                bias_correction2=bc2, adam_w_mode=adam_w_mode,
                inv_scale=inv_scale, impl=impl, with_norm_partials=True,
                with_grad_partials=with_grad_norm)
        if with_grad_norm:
            u_l, m2_l, v2_l, pp_l, uu_l, gg_l = stage1_outs
            leaf_gg = leaf_gg.at[leaf_idx].add(jnp.sum(gg_l))
        else:
            u_l, m2_l, v2_l, pp_l, uu_l = stage1_outs
        w_norm = jnp.sqrt(jnp.sum(pp_l))
        u_norm = jnp.sqrt(jnp.sum(uu_l))
        ratio = lamb_trust_ratio(w_norm, u_norm,
                                 weight_decay=weight_decay,
                                 use_nvlamb=use_nvlamb)

        def stage2(ins, s_, t_):
            pl_, ul_ = [x.astype(jnp.float32) for x in ins]
            (lr_,) = s_
            (r_,) = t_
            return [pl_ - lr_ * r_ * ul_]

        (p2_l,), _ = fused_elementwise(
            stage2, [sl(p2), u_l], scalars=[lr_f],
            per_tensor=[jnp.reshape(ratio, (1,))],
            num_outputs=1, out_dtypes=[p.dtype], impl=impl,
            aliases={0: 0},
            sr_outputs=(0,) if sr_seed is not None else (),
            sr_seed=(None if sr_seed is None
                     else jnp.asarray(sr_seed, jnp.int32) + leaf_idx + 1),
        )
        del size
        p2 = jax.lax.dynamic_update_slice(p2, p2_l, (start,))
        m2 = jax.lax.dynamic_update_slice(m2, m2_l, (start,))
        v2 = jax.lax.dynamic_update_slice(v2, v2_l, (start,))
        found = jnp.maximum(found, found_l)

    if with_grad_norm:
        return p2, m2, v2, found, jnp.sqrt(leaf_gg)
    return p2, m2, v2, found


def segmented_per_leaf_sumsq(buf, space: FlatSpace,
                             meta: SegmentMeta) -> jax.Array:
    """(num_leaves,) per-leaf sums of squares of a flat buffer, reduced
    through the segmented layout's per-segment slot machinery — the
    same ``slot_ids``/``slot_leaf`` maps the one-pass kernel's phase-0
    accumulators ride (``with_grad_norm``), expressed in XLA so it runs
    on any backend.

    This is the resilience watchdog's localization primitive
    (apex_tpu/resilience/watchdog.py): a NaN/Inf gradient makes exactly
    its own leaf's sum nonfinite. The reduction is therefore routed
    per-slot via ``segment_sum`` (not the kernel's one-hot product,
    whose ``0 * NaN`` contributions would bleed a NaN across every slot
    in the segment) so localization stays leaf-exact.
    """
    if meta.n_segments * meta.seg_elems != space.total:
        raise ValueError(
            f"SegmentMeta (n_segments={meta.n_segments}, "
            f"seg_elems={meta.seg_elems}) does not cover the space "
            f"(total={space.total})")
    x = buf.astype(jnp.float32)
    nl = space.num_leaves
    leaf_sumsq = jnp.zeros((nl,), jnp.float32)

    n_small = len(meta.small_segments)
    if n_small:
        align = space.align
        sub_per_seg = meta.seg_elems // align
        ms = meta.max_slots
        segs = x.reshape(meta.n_segments, meta.seg_elems)[
            np.asarray(meta.small_segments, np.int64)]
        # per-subtile partial sums — the accumulators' input granularity
        sub = jnp.sum(
            segs.reshape(n_small, sub_per_seg, align) ** 2, axis=-1)
        # subtile -> (segment-local) slot: a static global-slot id per
        # subtile (padding subtiles carry slot -1 and zero value; they
        # route to a dump bucket that is dropped)
        ids = np.asarray(meta.slot_ids, np.int64)
        rows = np.arange(n_small, dtype=np.int64)[:, None]
        gslot = np.where(ids >= 0, rows * ms + ids, n_small * ms)
        per_slot = jax.ops.segment_sum(
            sub.reshape(-1), jnp.asarray(gslot.reshape(-1)),
            num_segments=n_small * ms + 1)[:-1]
        # slot -> global leaf via the static slot_leaf map
        sl = np.asarray(meta.slot_leaf, np.int64).reshape(-1)
        gleaf = np.where(sl >= 0, sl, nl)
        leaf_sumsq = jax.ops.segment_sum(
            per_slot, jnp.asarray(gleaf), num_segments=nl + 1)[:-1]

    for leaf_idx, start, plen in meta.large:
        sl_ = jax.lax.slice(x, (start,), (start + plen,))
        leaf_sumsq = leaf_sumsq.at[leaf_idx].add(jnp.sum(sl_ * sl_))
    return leaf_sumsq


def segmented_per_leaf_checksum(buf, space: FlatSpace,
                                meta: Optional[SegmentMeta] = None
                                ) -> jax.Array:
    """(num_leaves,) BITWISE checksums of a flat buffer: the buffer is
    reinterpreted as uint32 words (``lax.bitcast_convert_type`` — no
    value semantics, so two buffers checksum equal iff they are
    bit-identical up to word order) and each leaf's words are summed
    mod 2^32. Integer addition is exactly associative, so the result is
    reduction-order independent: every replica of a data-parallel run
    computes the identical fingerprint for identical state, and any
    single bit flip changes its leaf's sum.

    With ``meta`` the reduction rides the segmented layout's per-slot
    machinery — the same ``slot_ids``/``slot_leaf`` maps as
    :func:`segmented_per_leaf_sumsq` (per-subtile partial sums routed
    subtile -> slot -> leaf) — so fingerprinting shares the static maps
    the one-pass kernel already carries. Without ``meta`` the words are
    routed straight through the space's per-leaf padded extents. Both
    paths include each leaf's padding words (zero on any buffer built
    by ``FlatSpace.pack``/``zeros``, and deterministic either way).

    This is the resilience consistency guard's divergence primitive
    (apex_tpu/resilience/guard.py): fingerprints are all-gathered over
    the data axis and a mismatch localizes to (leaf, replica).
    """
    words = jax.lax.bitcast_convert_type(
        buf.astype(jnp.float32), jnp.uint32)
    nl = space.num_leaves
    if meta is None:
        # leaf-id per element via the padded extents (static map)
        reps = np.asarray(space.padded_sizes, np.int64)
        owner = jnp.asarray(np.repeat(np.arange(nl, dtype=np.int32), reps))
        return jax.ops.segment_sum(words, owner, num_segments=nl)
    if meta.n_segments * meta.seg_elems != space.total:
        raise ValueError(
            f"SegmentMeta (n_segments={meta.n_segments}, "
            f"seg_elems={meta.seg_elems}) does not cover the space "
            f"(total={space.total})")
    leaf_sum = jnp.zeros((nl,), jnp.uint32)

    n_small = len(meta.small_segments)
    if n_small:
        align = space.align
        sub_per_seg = meta.seg_elems // align
        ms = meta.max_slots
        segs = words.reshape(meta.n_segments, meta.seg_elems)[
            np.asarray(meta.small_segments, np.int64)]
        # per-subtile partial word-sums (mod 2^32 all the way down)
        sub = jnp.sum(segs.reshape(n_small, sub_per_seg, align), axis=-1)
        ids = np.asarray(meta.slot_ids, np.int64)
        rows = np.arange(n_small, dtype=np.int64)[:, None]
        gslot = np.where(ids >= 0, rows * ms + ids, n_small * ms)
        per_slot = jax.ops.segment_sum(
            sub.reshape(-1), jnp.asarray(gslot.reshape(-1)),
            num_segments=n_small * ms + 1)[:-1]
        sl = np.asarray(meta.slot_leaf, np.int64).reshape(-1)
        gleaf = np.where(sl >= 0, sl, nl)
        leaf_sum = jax.ops.segment_sum(
            per_slot, jnp.asarray(gleaf), num_segments=nl + 1)[:-1]

    for leaf_idx, start, plen in meta.large:
        sl_ = jax.lax.slice(words, (start,), (start + plen,))
        leaf_sum = leaf_sum.at[leaf_idx].add(jnp.sum(sl_))
    return leaf_sum


__all__ = ["fused_lamb_segmented_update", "segmented_per_leaf_sumsq",
           "segmented_per_leaf_checksum", "CHUNK", "CHUNK_ROWS"]
