"""Fused LayerNorm / RMSNorm — Pallas kernels with custom VJP.

TPU re-design of the reference's fused layer-norm stack
(ref: apex/normalization/fused_layer_norm.py:32-165 autograd Functions,
csrc/layer_norm_cuda_kernel.cu Welford/block reductions). On TPU a row
fits in VMEM, so per-row mean/variance are single-pass VPU reductions
over the lane dimension — no Welford merge tree needed; the grid sweeps
row tiles. Backward emits per-tile partial dweight/dbias which are
summed in XLA (the analog of the reference's two-stage part-grad
reduction, layer_norm_cuda_kernel.cu cuComputePartGradGammaBeta).

Covers the reference surface: affine/no-affine, RMS variant, and
mixed-dtype inputs (bf16 x with fp32 weights — the `Mixed*` module
family, fused_layer_norm.py:204-433): compute is always fp32, output
takes x.dtype.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu._backend import interpret_flag, resolve_impl
from apex_tpu.ops._tiling import row_tile

_DEF_ROWS = 256   # row-tile cap; tools/tpu_tune.py sweeps this


def _row_tile(n_rows: int, hidden: int):
    # keep ~ <=4MB fp32 per input tile in VMEM; None -> XLA fallback
    return row_tile(n_rows, hidden, cap=_DEF_ROWS,
                    budget=4 * 1024 * 1024)


# ---------------------------------------------------------------------------
# forward/backward kernels (shared by LN and RMS via `rms` flag)
# ---------------------------------------------------------------------------


def _fwd_kernel(x_ref, w_ref, b_ref, o_ref, mean_ref, rstd_ref, *, eps, rms,
                affine, has_bias):
    x = x_ref[...].astype(jnp.float32)
    if rms:
        mean = jnp.zeros((x.shape[0], 1), jnp.float32)
        var = jnp.mean(x * x, axis=-1, keepdims=True)
    else:
        mean = jnp.mean(x, axis=-1, keepdims=True)
        xc = x - mean
        var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = (x - mean) * rstd
    y = xhat
    if affine:
        y = y * w_ref[...].astype(jnp.float32)
        if has_bias:
            y = y + b_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)
    mean_ref[...] = mean
    rstd_ref[...] = rstd


def _bwd_kernel(x_ref, w_ref, mean_ref, rstd_ref, g_ref,
                dx_ref, dw_ref, db_ref, *, rms, affine):
    x = x_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    mean = mean_ref[...]
    rstd = rstd_ref[...]
    xhat = (x - mean) * rstd
    if affine:
        wg = g * w_ref[...].astype(jnp.float32)
    else:
        wg = g
    c2 = jnp.mean(wg * xhat, axis=-1, keepdims=True)
    if rms:
        dx = rstd * (wg - xhat * c2)
    else:
        c1 = jnp.mean(wg, axis=-1, keepdims=True)
        dx = rstd * (wg - c1 - xhat * c2)
    dx_ref[...] = dx.astype(dx_ref.dtype)
    # per-tile partial param grads (summed over tiles in XLA); the
    # (num_tiles, 1, hidden) layout keeps a size-1 middle dim so the
    # (1, 1, hidden) block satisfies Mosaic's last-two-dims tiling rule
    dw_ref[0] = jnp.sum(g * xhat, axis=0, keepdims=True)
    db_ref[0] = jnp.sum(g, axis=0, keepdims=True)


def _fwd_pallas(x2, w, b, eps, rms, affine, has_bias, impl):
    rows, hidden = x2.shape
    tile = _row_tile(rows, hidden)
    grid = (rows // tile,)
    kernel = functools.partial(
        _fwd_kernel, eps=eps, rms=rms, affine=affine, has_bias=has_bias
    )
    wa = w if affine else jnp.zeros((1, hidden), x2.dtype)
    ba = b if (affine and has_bias) else jnp.zeros((1, hidden), x2.dtype)
    y, mean, rstd = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile, hidden), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, hidden), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, hidden), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((tile, hidden), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((tile, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((tile, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, hidden), x2.dtype),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        ],
        interpret=interpret_flag(impl),
    )(x2, wa.reshape(1, hidden), ba.reshape(1, hidden))
    return y, mean, rstd


def _bwd_pallas(x2, w, mean, rstd, g2, rms, affine, impl):
    rows, hidden = x2.shape
    tile = _row_tile(rows, hidden)
    grid = (rows // tile,)
    kernel = functools.partial(_bwd_kernel, rms=rms, affine=affine)
    wa = w if affine else jnp.zeros((1, hidden), x2.dtype)
    dx, dw_p, db_p = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile, hidden), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, hidden), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((tile, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((tile, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((tile, hidden), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((tile, hidden), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, hidden), lambda i: (i, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, hidden), lambda i: (i, 0, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, hidden), x2.dtype),
            jax.ShapeDtypeStruct((grid[0], 1, hidden), jnp.float32),
            jax.ShapeDtypeStruct((grid[0], 1, hidden), jnp.float32),
        ],
        interpret=interpret_flag(impl),
    )(x2, wa.reshape(1, hidden), mean, rstd, g2)
    return dx, jnp.sum(dw_p, axis=(0, 1)), jnp.sum(db_p, axis=(0, 1))


# ---------------------------------------------------------------------------
# XLA reference path
# ---------------------------------------------------------------------------


def _fwd_xla(x2, w, b, eps, rms, affine, has_bias):
    x = x2.astype(jnp.float32)
    if rms:
        mean = jnp.zeros((x.shape[0], 1), jnp.float32)
        var = jnp.mean(x * x, axis=-1, keepdims=True)
    else:
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    y = (x - mean) * rstd
    if affine:
        y = y * w.astype(jnp.float32).reshape(1, -1)
        if has_bias:
            y = y + b.astype(jnp.float32).reshape(1, -1)
    return y.astype(x2.dtype), mean, rstd


def _bwd_xla(x2, w, mean, rstd, g2, rms, affine):
    x = x2.astype(jnp.float32)
    g = g2.astype(jnp.float32)
    xhat = (x - mean) * rstd
    wg = g * w.astype(jnp.float32).reshape(1, -1) if affine else g
    c2 = jnp.mean(wg * xhat, axis=-1, keepdims=True)
    if rms:
        dx = rstd * (wg - xhat * c2)
    else:
        c1 = jnp.mean(wg, axis=-1, keepdims=True)
        dx = rstd * (wg - c1 - xhat * c2)
    return (
        dx.astype(x2.dtype),
        jnp.sum(g * xhat, axis=0),
        jnp.sum(g, axis=0),
    )


# ---------------------------------------------------------------------------
# public functional API with custom VJP
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _norm(x2, w, b, eps, rms, impl):
    y, _, _ = _norm_fwd_impl(x2, w, b, eps, rms, impl)
    return y


def _tileable(x2):
    # shared Mosaic-legality rule: a None tile (ragged/empty rows, huge
    # hidden) routes to the XLA path
    return _row_tile(x2.shape[0], x2.shape[1]) is not None


def _norm_fwd_impl(x2, w, b, eps, rms, impl):
    affine = w is not None
    has_bias = b is not None
    if impl == "xla" or not _tileable(x2):
        return _fwd_xla(x2, w, b, eps, rms, affine, has_bias)
    return _fwd_pallas(x2, w, b, eps, rms, affine, has_bias, impl)


def _norm_fwd(x2, w, b, eps, rms, impl):
    y, mean, rstd = _norm_fwd_impl(x2, w, b, eps, rms, impl)
    return y, (x2, w, b, mean, rstd)


def _norm_bwd(eps, rms, impl, res, g):
    x2, w, b, mean, rstd = res
    affine = w is not None
    if impl == "xla" or not _tileable(x2):
        dx, dw, db = _bwd_xla(x2, w, mean, rstd, g, rms, affine)
    else:
        dx, dw, db = _bwd_pallas(x2, w, mean, rstd, g, rms, affine, impl)
    dwo = dw.reshape(w.shape).astype(w.dtype) if affine else None
    dbo = db.reshape(b.shape).astype(b.dtype) if b is not None else None
    return dx, dwo, dbo


_norm.defvjp(_norm_fwd, _norm_bwd)


def _normalize_args(x, normalized_ndim):
    shape = x.shape
    hidden = 1
    for d in shape[len(shape) - normalized_ndim:]:
        hidden *= d
    return x.reshape(-1, hidden), shape


def fused_layer_norm(
    x: jax.Array,
    weight: Optional[jax.Array] = None,
    bias: Optional[jax.Array] = None,
    *,
    eps: float = 1e-5,
    impl: Optional[str] = None,
) -> jax.Array:
    """Fused layer norm over the trailing dims covered by ``weight``
    (ref: apex.normalization.fused_layer_norm affine/no-affine forms).

    Mixed dtypes are allowed (bf16 ``x`` with fp32 ``weight``/``bias``):
    compute is fp32, output dtype follows ``x`` — the reference's
    ``MixedFusedLayerNorm`` semantics (fused_layer_norm.py:204-433).
    """
    impl = resolve_impl(impl)
    ndim = weight.ndim if weight is not None else 1
    x2, shape = _normalize_args(x, ndim)
    w = weight.reshape(1, -1) if weight is not None else None
    b = bias.reshape(1, -1) if bias is not None else None
    y = _norm(x2, w, b, eps, False, impl)
    return y.reshape(shape)


def fused_rms_norm(
    x: jax.Array,
    weight: Optional[jax.Array] = None,
    *,
    eps: float = 1e-5,
    impl: Optional[str] = None,
) -> jax.Array:
    """Fused RMS norm (ref: apex.normalization.FusedRMSNorm,
    fused_layer_norm.py rms_forward_* bindings)."""
    impl = resolve_impl(impl)
    ndim = weight.ndim if weight is not None else 1
    x2, shape = _normalize_args(x, ndim)
    w = weight.reshape(1, -1) if weight is not None else None
    y = _norm(x2, w, None, eps, True, impl)
    return y.reshape(shape)
