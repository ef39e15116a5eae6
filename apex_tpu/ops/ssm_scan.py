"""Mamba-1's selective scan over a call's rows, as one Pallas kernel.

``models/ssm.py``'s :class:`SelectiveMixer` has a decay for every
(channel, state) pair, ``exp(Delta_t[e] A[e, n])``, so a chunk cannot
be factored into products the way Mamba-2's SSD form factors it: the
state has to walk the rows in order. In XLA that is a loop of one trip
a row (1024 a chunk, a layer), or an associative scan that writes the
whole ``(rows, N, E)`` float32 tensor of decays and inputs, some 335 MB
a lane and layer at Jamba's width, many times over. Here a grid step
holds one lane's state of a block of channels in registers and walks
its rows: ``exp(Delta A)`` is computed where it is used and never
stored, and what goes to HBM is the rows' ``Delta``, ``x``, ``z``,
``B`` and ``C`` in and ``y`` out.

The grid is (lanes, blocks of ``CHANNELS`` channels, blocks of
``ROWS`` rows), the last in order: the state block is the output's
and stays in VMEM across a lane's row blocks, written back once. The
state comes in and goes out as the lanes' copies
(``ops/ssm_step.py`` ``read_lanes`` / ``write_lanes`` around the
call), channels minor-most, ``(E / 128, N, 128)``.

``B`` and ``C`` are handed in turned, sixteen rows at a time ``(N,
16)``: a row's ``B`` is then a column that broadcasts along the
channels, which is the direction the update needs it in.

The kernel is named ``ssm_scan`` in the compiled program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu._backend import interpret_flag, resolve_impl
from apex_tpu.models.ssm import LANES, decay_table, selective_scan

#: channels a grid step: two groups of 128, whose state (2 x 16 x 128
#: float32) stays in registers across the rows
CHANNELS = 256

#: rows a grid step; the kernel walks them sixteen at a time (a tile
#: of bf16 rows)
ROWS = 256

_TURN = 16


def _kernel(s0_ref, d_ref, x_ref, z_ref, bt_ref, ct_ref, a_ref, skip_ref,
            y_ref, s_ref):
    """One lane's block of channels over one block of rows. ``s_ref``
    (groups, N, 128) is the state, carried from the lane's previous row
    block; ``d_ref`` / ``x_ref`` / ``z_ref`` / ``y_ref`` (rows,
    channels); ``bt_ref`` / ``ct_ref`` (rows / 16, N, 16)."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = s0_ref[...]

    groups = s_ref.shape[0]
    A = [a_ref[g] for g in range(groups)]                  # (N, 128)
    skip = skip_ref[...]                                   # (16, channels)
    row = lax.broadcasted_iota(jnp.int32, (_TURN, LANES), 0)

    def turn(i, S):
        at = pl.multiple_of(i * _TURN, _TURN)
        d = d_ref[pl.ds(at, _TURN), :]
        x = x_ref[pl.ds(at, _TURN), :]
        bt, ct = bt_ref[i], ct_ref[i]                      # (N, 16)
        S = list(S)
        y = [jnp.zeros((_TURN, LANES), jnp.float32) for _ in range(groups)]
        for j in range(_TURN):
            B, C = bt[:, j:j + 1], ct[:, j:j + 1]          # (N, 1)
            for g in range(groups):
                lanes = slice(g * LANES, (g + 1) * LANES)
                dj = d[j:j + 1, lanes]                     # (1, 128)
                S[g] = (jnp.exp(dj * A[g]) * S[g]
                        + B * (dj * x[j:j + 1, lanes]))
                y[g] = jnp.where(row == j, jnp.sum(S[g] * C, axis=0,
                                                   keepdims=True), y[g])
        for g in range(groups):
            lanes = slice(g * LANES, (g + 1) * LANES)
            zg = z_ref[pl.ds(at, _TURN), lanes].astype(jnp.float32)
            out = ((y[g] + skip[:, lanes] * x[:, lanes])
                   * (zg / (1.0 + jnp.exp(-zg))))
            y_ref[pl.ds(at, _TURN), lanes] = out.astype(y_ref.dtype)
        return tuple(S)

    S = lax.fori_loop(0, d_ref.shape[0] // _TURN, turn,
                      tuple(s_ref[g] for g in range(groups)))
    for g in range(groups):
        s_ref[g] = S[g]


def ssm_scan(S, x, delta, A, B, C, D, z, *, impl=None):
    """``models/ssm.py`` ``selective_scan``'s contract: ``S`` (b,
    E/128, N, 128) float32; ``x``, ``delta`` (b, s, E) float32,
    ``delta`` 0 on pad rows; ``A`` (E, N); ``B`` / ``C`` (b, s, N)
    float32; ``D`` (E,); ``z`` (b, s, E). Returns (``y`` (b, s, E) in
    ``z``'s type, the state after the last row). A kernel on a TPU,
    the scan of ``models/ssm.py`` where ``impl`` is ``xla``."""
    impl = resolve_impl(impl)
    if impl == "xla":
        y, S = selective_scan(S, x, delta, A, B, C, D, z)
        return y.astype(z.dtype), S
    b, rows, E = x.shape
    N = A.shape[1]
    s = -(-rows // _TURN) * _TURN
    if s != rows:                      # pad rows: Delta 0 stands still
        pad = lambda t: jnp.pad(t, ((0, 0), (0, s - rows), (0, 0)))  # noqa
        x, delta, z, B, C = (pad(t) for t in (x, delta, z, B, C))
    rows_a_step = math.gcd(s, ROWS)
    channels = math.gcd(E, CHANNELS)

    def turned(t):                     # (b, s, N) -> (b, s / 16, N, 16)
        return t.reshape(b, s // _TURN, _TURN, N).transpose(0, 1, 3, 2)

    state = pl.BlockSpec((None, channels // LANES, N, LANES),
                         lambda i, c, r: (i, c, 0, 0))
    by_row = pl.BlockSpec((None, rows_a_step, channels),
                          lambda i, c, r: (i, r, c))
    cols = pl.BlockSpec((None, rows_a_step // _TURN, N, _TURN),
                        lambda i, c, r: (i, r, 0, 0))
    y, S = pl.pallas_call(
        _kernel,
        grid=(b, E // channels, s // rows_a_step),
        in_specs=[state, by_row, by_row, by_row, cols, cols,
                  pl.BlockSpec((channels // LANES, N, LANES),
                               lambda i, c, r: (c, 0, 0)),
                  pl.BlockSpec((_TURN, channels), lambda i, c, r: (0, c))],
        out_specs=[by_row, state],
        out_shape=[jax.ShapeDtypeStruct((b, s, E), z.dtype),
                   jax.ShapeDtypeStruct(S.shape, jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret_flag(impl),
        name="ssm_scan",
    )(S.astype(jnp.float32), delta.astype(jnp.float32),
      x.astype(jnp.float32), z, turned(B.astype(jnp.float32)),
      turned(C.astype(jnp.float32)), decay_table(A.astype(jnp.float32)),
      # D as sixteen equal rows: a row of it would have to be broadcast
      # along the sublanes, which Mosaic does not lay out from a load
      jnp.broadcast_to(D.astype(jnp.float32), (_TURN, E)))
    return y[:, :rows], S


__all__ = ["CHANNELS", "ROWS", "ssm_scan"]
