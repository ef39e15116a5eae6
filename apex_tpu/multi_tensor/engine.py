"""Generic fused element-wise engine over flat buffers.

TPU re-design of the reference's multi-tensor-apply machinery
(ref: csrc/multi_tensor_apply.cuh:44-147 launcher, csrc/amp_C_frontend.cpp
op table). One Pallas kernel sweeps lane-aligned tiles of a flat buffer;
the per-op functor is a Python callable traced into the kernel, so every
fused optimizer/scaler op is a few lines. Per-tensor scalars (LAMB trust
ratios, LARS coefficients, per-tensor norms) ride in via scalar prefetch
plus a static tile->leaf map, replacing the reference's device-side
pointer/chunk tables.

The `found_inf` output replaces the reference's ``noop_flag`` convention
(ref: csrc/multi_tensor_scale_kernel.cu:47-70): kernels *report* non-finite
values; skip-step gating happens functionally in the loss scaler
(`apex_tpu.amp.scaler`) via `lax.cond`/`jnp.where`, never by patching.
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu._backend import interpret_flag, resolve_impl

LANES = 128
# 512 rows x 128 lanes = 65536 elements per tile, matching the reference's
# large multi-tensor chunk size (ref: apex/multi_tensor_apply/__init__.py:4).
DEFAULT_TILE_ROWS = 512
# The per-tensor SUBTILE quantum: tile_ids carry one leaf id per
# (PER_TENSOR_TILE_ROWS * LANES) elements — the FlatSpace alignment —
# so ids never straddle a leaf regardless of the sweep tile size
# (see FlatSpace.tile_leaf_ids; ids resolve to per-row values in XLA
# outside the kernel).
PER_TENSOR_TILE_ROWS = 16


def _pad_to(buf: jax.Array, n: int) -> jax.Array:
    if buf.shape[0] == n:
        return buf
    return jnp.pad(buf, (0, n - buf.shape[0]))


def stochastic_round_cast(x: jax.Array, seed, salt: int = 0) -> jax.Array:
    """fp32 -> bf16 stochastic round in plain XLA ops.

    Equivalent in distribution to ``pltpu.stochastic_round`` (which only
    lowers through Mosaic): add uniform random low bits below the bf16
    mantissa boundary, then truncate. E[result] == x exactly; non-finite
    values pass through a nearest cast (adding bits to an inf/nan
    pattern could change its class). Used by the engine's xla/interpret
    paths and by sharded optimizers whose update tail is plain XLA;
    compiled Pallas kernels use the in-kernel primitive instead.
    """
    xf = x.astype(jnp.float32)
    key = jax.random.fold_in(
        jax.random.PRNGKey(jnp.asarray(seed, jnp.uint32)), salt)
    bits = jax.random.bits(key, xf.shape, jnp.uint32)
    xi = jax.lax.bitcast_convert_type(xf, jnp.uint32)
    trunc = jax.lax.bitcast_convert_type(
        (xi + (bits & jnp.uint32(0xFFFF))) & jnp.uint32(0xFFFF0000),
        jnp.float32)
    return jnp.where(jnp.isfinite(xf), trunc, xf).astype(jnp.bfloat16)


def fused_elementwise(
    fn: Callable,
    inputs: Sequence[jax.Array],
    *,
    scalars: Sequence = (),
    num_outputs: int = 1,
    out_dtypes: Optional[Sequence] = None,
    check_finite: Sequence[int] = (),
    tile_ids: Optional[np.ndarray] = None,
    per_tensor: Sequence[jax.Array] = (),
    impl: Optional[str] = None,
    tile_rows: Optional[int] = None,
    aliases: Optional[dict] = None,
    sumsq_subtiles: Sequence = (),
    sr_outputs: Sequence[int] = (),
    sr_seed=None,
):
    """Run ``fn`` element-wise over 1-D buffers in one fused kernel.

    fn(ins, scalars, tensor_scalars) -> list of output arrays, where
    ``ins`` are same-shape blocks, ``scalars`` are 0-d values and
    ``tensor_scalars`` are values broadcastable against the blocks
    (per-tensor values resolved through ``tile_ids``).

    ``tile_ids`` is SUBTILE-granular: one leaf id per
    ``PER_TENSOR_TILE_ROWS * LANES`` elements (the FlatSpace alignment
    quantum) — i.e. exactly ``FlatSpace.tile_leaf_ids(2048)``. Sweeps
    still run at ``tile_rows`` (default DEFAULT_TILE_ROWS): the
    id->value resolution happens OUTSIDE the kernel (a tiny XLA gather
    producing one fp32 per buffer row, ~n/128 elements), and the kernel
    reads the per-row values as a (tile_rows, 1) VMEM block alongside
    the data tile. Per-tensor ops thus keep big-tile grids (32x fewer
    steps than one-id-per-tile tiling) without the kernel ever doing a
    dynamic SMEM gather — stacked dynamic scalar reads are exactly the
    construct Mosaic's compiler rejects at sub>1.

    ``aliases`` maps input position (into ``inputs``) -> output position:
    the output may reuse the input's buffer (the TPU analog of the
    reference's in-place multi-tensor updates, ref
    csrc/multi_tensor_apply.cuh:44-147 — kernels write through the same
    tensor pointers). XLA inserts a copy when the input is still live,
    so this is always safe; in a jitted train step whose optimizer state
    flows through, it eliminates the fresh allocation per updated buffer.

    ``sumsq_subtiles`` — entries ``("in", i)`` or ``("out", j)`` — emits,
    for each named buffer, per-(PER_TENSOR_TILE_ROWS*LANES)-subtile
    per-lane partial sums of squares from INSIDE the same kernel pass
    (shape (num_tiles, tile_rows//PER_TENSOR_TILE_ROWS, LANES), fp32),
    appended to the returned outputs. The tail pad beyond ``n`` is
    masked out of the partials (``fn``'s image of the zero padding
    never contaminates them), so summing all partials gives the exact
    global sum-of-squares on every impl. Since FlatSpace aligns every
    leaf to the subtile size, a segment-sum of these partials yields
    exact per-tensor norms without re-reading the buffer — the fusion
    LAMB uses to fold its ||p||/||update|| passes into stage 1.

    ``sr_outputs`` lists output indices to write with **stochastic
    rounding** to bfloat16 (their ``out_dtypes`` entry must be bf16,
    and ``sr_seed`` — an int32 scalar, traced OK — must be given). This
    is the TPU-native replacement for the reference's fp32 master-copy
    discipline (ref: csrc/multi_tensor_lamb_mp.cu mixed param/state
    dtypes): E[rounded] equals the fp32 value, so sub-ulp updates
    accumulate in expectation instead of being lost to nearest
    rounding, letting params (and optimizer state) live in bf16 with
    no master at half the HBM traffic. On compiled TPU the rounding
    runs in-kernel via ``pltpu.stochastic_round`` seeded per
    (sr_seed, tile); the xla/interpret paths emulate it with
    ``jax.random`` bits (statistically identical, different stream).

    Returns ``(outputs, found_inf)`` where ``found_inf`` is a float32
    scalar in {0, 1} covering the ``check_finite`` input indices.
    """
    impl = resolve_impl(impl)
    n = inputs[0].shape[0]
    for b in inputs:
        assert b.ndim == 1 and b.shape[0] == n, "flat buffers must be same-length 1-D"
    if out_dtypes is None:
        out_dtypes = [inputs[0].dtype] * num_outputs

    if tile_rows is None:
        tile_rows = DEFAULT_TILE_ROWS

    # compile-plane: publish this sweep's abstract signature so shape/
    # impl churn across engine calls shows up as recompile events (one
    # module-global read when no tracker is armed — the common case)
    from apex_tpu.telemetry import compiled as _compiled

    if _compiled.get_tracker() is not None:
        _compiled.observe("fused_elementwise", {
            "n": int(n), "inputs": len(inputs),
            "dtypes": [str(b.dtype) for b in inputs],
            "outputs": num_outputs, "impl": impl,
            "tile_rows": int(tile_rows),
            "per_tensor": len(per_tensor), "sr": bool(sr_outputs)})
    if impl in ("pallas", "interpret"):
        # a tile of 4 MiB or more is refused by the TPU compiler
        # (ops/mosaic_limits.py); say so before the shape reaches it
        from apex_tpu.ops.mosaic_limits import check_block

        check_block(tile_rows, LANES, 4, what="engine tile")
    tile = tile_rows * LANES
    for kind, idx in sumsq_subtiles:
        if kind not in ("in", "out") or not (
                0 <= idx < (len(inputs) if kind == "in" else num_outputs)):
            raise ValueError(f"bad sumsq_subtiles entry {(kind, idx)}")
    if (sumsq_subtiles or tile_ids is not None) \
            and tile_rows % PER_TENSOR_TILE_ROWS:
        raise ValueError(
            f"sumsq_subtiles/tile_ids need tile_rows divisible by "
            f"{PER_TENSOR_TILE_ROWS}, got {tile_rows}")
    sub = tile_rows // PER_TENSOR_TILE_ROWS

    sr_outputs = tuple(sr_outputs)
    if sr_outputs:
        if sr_seed is None:
            raise ValueError("sr_outputs requires sr_seed")
        for j in sr_outputs:
            if not 0 <= j < num_outputs:
                raise ValueError(f"sr output {j} out of range")
            if jnp.dtype(out_dtypes[j]) != jnp.bfloat16:
                raise ValueError(
                    f"stochastic rounding targets bfloat16 outputs; "
                    f"output {j} is {out_dtypes[j]}")

    scalars = [jnp.asarray(s, jnp.float32) for s in scalars]

    if impl == "xla":
        return _fused_elementwise_xla(
            fn, inputs, scalars, num_outputs, out_dtypes, check_finite,
            tile_ids, per_tensor, tile, sumsq_subtiles,
            sr_outputs, sr_seed,
        )

    padded_n = ((n + tile - 1) // tile) * tile
    bufs = [_pad_to(b, padded_n) for b in inputs]
    num_tiles = padded_n // tile
    pt_rows = []
    if tile_ids is not None:
        # SUBTILE-granular leaf map: one id per PER_TENSOR_TILE_ROWS*LANES
        # elements (the FlatSpace alignment quantum). Resolve ids to
        # values OUTSIDE the kernel: a (num_rows, 1) fp32 array of each
        # row's per-tensor value (rows never straddle a leaf because
        # FlatSpace aligns leaves to the subtile quantum). The kernel
        # then reads a (tile_rows, 1) VMEM block per tile — no dynamic
        # SMEM gather, which Mosaic's compiler crashes on at sub>1.
        # Cost: one extra fp32 per 128 data elements of read traffic.
        tile_ids = np.asarray(tile_ids, np.int32)
        want = num_tiles * sub
        if tile_ids.shape[0] != want:
            # pad map for the trailing partial tile (maps to last leaf)
            extra = want - tile_ids.shape[0]
            tile_ids = np.concatenate([tile_ids, np.full(extra, tile_ids[-1] if len(tile_ids) else 0, np.int32)])
        ids = jnp.asarray(tile_ids)
        pt_rows = [
            jnp.repeat(jnp.asarray(p, jnp.float32)[ids],
                       PER_TENSOR_TILE_ROWS).reshape(-1, 1)
            for p in per_tensor
        ]

    n_in = len(bufs)
    n_pt = len(per_tensor)
    has_ids = tile_ids is not None
    is_interp = bool(interpret_flag(impl))
    # in-kernel SR lowers only through Mosaic (prng_seed has no CPU
    # rule); interpret mode writes fp32 and SR-casts after the call
    sr_in_kernel = bool(sr_outputs) and not is_interp
    sr_post = set(sr_outputs) if (sr_outputs and is_interp) else set()
    kernel_out_dtypes = [
        jnp.float32 if j in sr_post else dt
        for j, dt in enumerate(out_dtypes)
    ]

    def kernel(*refs):
        # ref order: scalars prefetch, [pt prefetch when no ids],
        # [sr seed prefetch], data inputs, [per-row pt values when
        # ids], outputs...
        k = 0
        scalar_ref = refs[k]; k += 1
        pt_sc_refs = ()
        if not has_ids:
            pt_sc_refs = refs[k : k + n_pt]; k += n_pt
        sr_ref = None
        if sr_in_kernel:
            sr_ref = refs[k]; k += 1
        in_refs = refs[k : k + n_in]; k += n_in
        ptv_refs = ()
        if has_ids:
            ptv_refs = refs[k : k + n_pt]; k += n_pt
        out_refs = refs[k : k + num_outputs]; k += num_outputs
        found_ref = refs[k]; k += 1
        sq_refs = refs[k : k + len(sumsq_subtiles)]

        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            found_ref[0, 0] = jnp.float32(0.0)

        svals = [scalar_ref[j] for j in range(len(scalars))]
        if has_ids:
            # (tile_rows, 1) per-row values, pre-resolved outside the
            # kernel; broadcasts against the (tile_rows, LANES) blocks
            tvals = [r[...] for r in ptv_refs]
        else:
            tvals = [r[0] for r in pt_sc_refs]

        ins = [r[...] for r in in_refs]
        if check_finite:
            ok = jnp.bool_(True)
            for idx in check_finite:
                ok = jnp.logical_and(ok, jnp.all(jnp.isfinite(ins[idx])))
            found_ref[0, 0] = jnp.maximum(
                found_ref[0, 0], jnp.where(ok, 0.0, 1.0).astype(jnp.float32)
            )
        outs = fn(ins, svals, tvals)
        if sr_in_kernel:
            # one per-tile stream: (sr_seed, tile index); successive
            # random_bits calls for multiple SR outputs continue it
            pltpu.prng_seed(sr_ref[0], i)
        for j, (r, o) in enumerate(zip(out_refs, outs)):
            if sr_in_kernel and j in sr_outputs:
                bits = jax.lax.bitcast_convert_type(
                    pltpu.prng_random_bits(o.shape), jnp.uint32)
                r[...] = pltpu.stochastic_round(
                    o.astype(jnp.float32), bits, target_dtype=r.dtype)
            else:
                r[...] = o.astype(r.dtype)
        if sumsq_subtiles:
            # mask the tail pad so partials never include fn's image of
            # the zero padding (fn(0) may be nonzero) — keeps pallas and
            # XLA paths bit-consistent for any buffer length
            ridx = jax.lax.broadcasted_iota(
                jnp.int32, (tile_rows, LANES), 0)
            lidx = jax.lax.broadcasted_iota(
                jnp.int32, (tile_rows, LANES), 1)
            valid = (i * tile + ridx * LANES + lidx) < n
        for r, (kind, idx) in zip(sq_refs, sumsq_subtiles):
            src = (ins[idx] if kind == "in" else outs[idx]).astype(
                jnp.float32)
            src = jnp.where(valid, src, 0.0)
            # per-(PER_TENSOR_TILE_ROWS-row) subtile, per-lane partial
            # sums: the row-group reduction runs in-kernel; lane sums
            # and the per-leaf segment-sum are tiny XLA finishing work
            r[0] = jnp.sum(
                (src * src).reshape(sub, PER_TENSOR_TILE_ROWS, LANES),
                axis=1)

    # index maps receive (grid idx, *prefetch refs) under PrefetchScalarGridSpec
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=(1 + (0 if has_ids else n_pt)
                             + (1 if sr_in_kernel else 0)),
        grid=(num_tiles,),
        in_specs=[
            pl.BlockSpec(
                (tile_rows, LANES), lambda i, *_: (i, 0), memory_space=pltpu.VMEM
            )
            for _ in range(n_in)
        ] + [
            pl.BlockSpec(
                (tile_rows, 1), lambda i, *_: (i, 0), memory_space=pltpu.VMEM
            )
            for _ in pt_rows
        ],
        out_specs=(
            [
                pl.BlockSpec(
                    (tile_rows, LANES), lambda i, *_: (i, 0), memory_space=pltpu.VMEM
                )
                for _ in range(num_outputs)
            ]
            + [pl.BlockSpec((1, 1), lambda i, *_: (0, 0), memory_space=pltpu.SMEM)]
            + [
                pl.BlockSpec((1, sub, LANES), lambda i, *_: (i, 0, 0),
                             memory_space=pltpu.VMEM)
                for _ in sumsq_subtiles
            ]
        ),
    )

    scalar_arg = (
        jnp.stack(scalars) if scalars else jnp.zeros((1,), jnp.float32)
    )
    prefetch = [scalar_arg]
    if not has_ids:
        prefetch.extend(jnp.asarray(p, jnp.float32) for p in per_tensor)
    if sr_in_kernel:
        prefetch.append(jnp.asarray(sr_seed, jnp.int32).reshape(1))

    out_shapes = (
        [jax.ShapeDtypeStruct((padded_n // LANES, LANES), dt)
         for dt in kernel_out_dtypes]
        + [jax.ShapeDtypeStruct((1, 1), jnp.float32)]
        + [jax.ShapeDtypeStruct((num_tiles, sub, LANES), jnp.float32)
           for _ in sumsq_subtiles]
    )

    io_aliases = {}
    if aliases:
        # alias indices count ALL pallas inputs, scalar-prefetch args first
        n_prefetch = len(prefetch)
        for in_idx, out_idx in aliases.items():
            if not (0 <= in_idx < len(inputs)
                    and 0 <= out_idx < num_outputs):
                raise ValueError(
                    f"alias {in_idx}->{out_idx} out of range: "
                    f"{len(inputs)} inputs, {num_outputs} outputs")
            if out_idx in sr_post:
                # interpret-mode SR writes fp32 storage then casts
                # outside; the in-place reuse intentionally doesn't
                # apply (CPU-only path, no warning needed)
                continue
            if jnp.dtype(inputs[in_idx].dtype) == jnp.dtype(out_dtypes[out_idx]):
                io_aliases[n_prefetch + in_idx] = out_idx
            else:
                # in-place donation silently NOT applying would double
                # the op's HBM traffic with no signal — warn once
                import warnings

                warnings.warn(
                    f"requested alias input {in_idx} "
                    f"({inputs[in_idx].dtype}) -> output {out_idx} "
                    f"({out_dtypes[out_idx]}) skipped: dtype mismatch "
                    f"prevents in-place buffer reuse", stacklevel=3)

    # label the dispatch so an eager call's Mosaic/XLA compile is
    # attributed to the engine (inside an outer jit the enclosing entry
    # point's label — e.g. "train_step" — wins, which is the right
    # attribution for the program that actually compiles)
    with _compiled.label("fused_elementwise"):
        results = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=out_shapes,
            input_output_aliases=io_aliases,
            interpret=interpret_flag(impl),
        )(*prefetch, *[b.reshape(padded_n // LANES, LANES) for b in bufs],
          *pt_rows)

    outs = [r.reshape(padded_n)[:n] for r in results[:num_outputs]]
    if sr_post:
        outs = [
            stochastic_round_cast(o, sr_seed, j) if j in sr_post else o
            for j, o in enumerate(outs)
        ]
    found = results[num_outputs][0, 0]
    outs.extend(results[num_outputs + 1:])      # sumsq partials, if any
    return outs, found


def _fused_elementwise_xla(
    fn, inputs, scalars, num_outputs, out_dtypes, check_finite,
    tile_ids, per_tensor, tile, sumsq_subtiles=(),
    sr_outputs=(), sr_seed=None,
):
    """Pure-XLA reference path (CPU tests, simulated meshes)."""
    n = inputs[0].shape[0]
    sub_elems = PER_TENSOR_TILE_ROWS * LANES
    if tile_ids is not None:
        # tile_ids are SUBTILE-granular (one per alignment quantum);
        # XLA has no grid to amortize, so blocks reshape at subtile
        # granularity and values broadcast as (n_subtiles, 1) — never
        # materialized per element
        padded_n = tile_ids.shape[0] * sub_elems
        bufs = [_pad_to(b, padded_n).reshape(-1, sub_elems)
                for b in inputs]
        ids = jnp.asarray(tile_ids)
        tvals = [jnp.asarray(p, jnp.float32)[ids][:, None]
                 for p in per_tensor]
    else:
        bufs = list(inputs)
        tvals = [jnp.asarray(p, jnp.float32) for p in per_tensor]
    found = jnp.float32(0.0)
    for idx in check_finite:
        found = jnp.maximum(
            found, jnp.where(jnp.all(jnp.isfinite(bufs[idx])), 0.0, 1.0)
        )
    raw_outs = fn(bufs, scalars, tvals)
    sr = set(sr_outputs)

    def final_cast(j, o, dt):
        if tile_ids is not None:
            o = o.reshape(-1)[:n]
        return stochastic_round_cast(o, sr_seed, j) if j in sr else o.astype(dt)

    outs = [final_cast(j, o, dt)
            for j, (o, dt) in enumerate(zip(raw_outs, out_dtypes))]
    if sumsq_subtiles:
        # mirror the kernel's (num_tiles, sub, LANES) partial layout
        num_tiles = -(-n // tile)
        padded_n = num_tiles * tile
        sub = tile // (PER_TENSOR_TILE_ROWS * LANES)
        for kind, idx in sumsq_subtiles:
            src = inputs[idx] if kind == "in" else raw_outs[idx].reshape(-1)[:n]
            x = _pad_to(src.astype(jnp.float32), padded_n)
            outs.append(jnp.sum(
                x.reshape(num_tiles, sub, PER_TENSOR_TILE_ROWS, LANES) ** 2,
                axis=2))
    return outs, found


# ---------------------------------------------------------------------------
# Fused L2-norm (per-buffer and per-tensor partials)
# ---------------------------------------------------------------------------


def fused_sumsq_partials(
    buf: jax.Array,
    *,
    impl: Optional[str] = None,
    tile_rows: Optional[int] = None,
    scale=None,
) -> jax.Array:
    """Per-tile partial sums of squares over a flat buffer.

    TPU analog of the two-phase reduction in
    ref: csrc/multi_tensor_l2norm_kernel.cu (per-chunk partials + cleanup):
    the kernel emits one fp32 partial per tile; the tiny finishing
    reduction (global sum or per-tensor segment-sum) runs in XLA.

    Default tile is the big (512-row) sweep — right for GLOBAL norms
    (no alignment constraint; a 2048-element tile would cost a 32x
    larger grid). Per-tensor callers pass PER_TENSOR_TILE_ROWS so tiles
    never straddle a leaf.

    ``scale`` (a traced f32 scalar is fine) multiplies every element
    BEFORE squaring, in the same read — the fused train-step's
    unscale+norm reduction: ``sumsq((1/loss_scale) * g)`` in one pass
    over ``g`` with no unscaled buffer ever materialized. The multiply
    happens first (then the square), so the partials bit-match
    squaring an explicitly unscaled copy of the buffer.
    """
    impl = resolve_impl(impl)
    if tile_rows is None:
        # read at call time so runtime tuning of DEFAULT_TILE_ROWS
        # (tools/tpu_tune.py monkeypatch pattern) applies here too
        tile_rows = DEFAULT_TILE_ROWS
    tile = tile_rows * LANES
    n = buf.shape[0]
    padded_n = ((n + tile - 1) // tile) * tile
    num_tiles = padded_n // tile
    if impl == "xla":
        x = _pad_to(buf, padded_n).astype(jnp.float32)
        if scale is not None:
            x = x * jnp.asarray(scale, jnp.float32)
        x = x.reshape(num_tiles, tile)
        return jnp.sum(x * x, axis=1)

    if scale is None:
        def kernel(in_ref, out_ref):
            x = in_ref[...].astype(jnp.float32)
            # reduce the sublane (row) dim in-kernel; the cross-lane sum
            # is a tiny XLA reduction. The (num_tiles, 1, LANES) output
            # layout keeps the last-two block dims (1, LANES) legal under
            # Mosaic's tiling rule (a (1, 1) SMEM block per grid step is
            # not).
            out_ref[0] = jnp.sum(x * x, axis=0, keepdims=True)

        out = pl.pallas_call(
            kernel,
            grid=(num_tiles,),
            in_specs=[
                pl.BlockSpec((tile_rows, LANES), lambda i: (i, 0),
                             memory_space=pltpu.VMEM)
            ],
            out_specs=pl.BlockSpec((1, 1, LANES), lambda i: (i, 0, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((num_tiles, 1, LANES),
                                           jnp.float32),
            interpret=interpret_flag(impl),
        )(_pad_to(buf, padded_n).reshape(padded_n // LANES, LANES))
        return jnp.sum(out, axis=(1, 2))

    def scaled_kernel(scal_ref, in_ref, out_ref):
        x = in_ref[...].astype(jnp.float32) * scal_ref[0]
        out_ref[0] = jnp.sum(x * x, axis=0, keepdims=True)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(num_tiles,),
        in_specs=[
            pl.BlockSpec((tile_rows, LANES), lambda i, *_: (i, 0),
                         memory_space=pltpu.VMEM)
        ],
        out_specs=pl.BlockSpec((1, 1, LANES), lambda i, *_: (i, 0, 0),
                               memory_space=pltpu.VMEM),
    )
    out = pl.pallas_call(
        scaled_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_tiles, 1, LANES), jnp.float32),
        interpret=interpret_flag(impl),
    )(jnp.asarray(scale, jnp.float32).reshape(1),
      _pad_to(buf, padded_n).reshape(padded_n // LANES, LANES))
    return jnp.sum(out, axis=(1, 2))
