"""The Mamba-2 mixer (state-space duality, Dao & Gu 2024) in two forms
over one parameter tree: the *chunked* form for ``s > 1`` tokens a lane
and the *one-step* recurrence for decode. Both take a state in and give
a state out, so a sequence can be prefilled in chunks, decoded a token
at a time, and moved between lanes between calls.

One head's recurrence, ``H`` heads of ``P`` channels, a state of ``N``
a channel, one group (``B`` and ``C`` shared by all heads)::

    [z | c | dt] = u W_in
    c_t <- silu(b_conv + sum_j w_conv[:, j] c_{t-3+j})   (depthwise, causal)
    [x | B | C] = c_t
    d_t = softplus(dt_t + dt_bias);  a_t = exp(d_t A),  A = -exp(A_log)
    S_t = a_t S_{t-1} + d_t x_t (x) B_t                   (H, P, N) float32
    y_t = S_t C_t + D x_t
    o = norm(y_t * silu(z_t)) W_out        (the gate BEFORE the norm)

A lane's state is ``(S, rows)``: ``S`` (H, P, N) in float32 and the
last ``d_conv - 1`` rows of ``c`` before the convolution, in the
activations' type. What a lane holds does not grow with its context
(docs/serving.md "Recurrent state"). The states live in pools of
slots, one a sequence (``serving/kv_cache.py``); the mixer reads its
lanes' out of them and writes them back by one pair of accessors
(``ops/ssm_step.py`` ``read_lanes`` / ``write_lanes``), and the
one-step form updates ``S`` where it lies (``ssm_step_by_slot``).

The chunked form cuts the ``s`` rows into blocks of ``chunk`` (256):
inside a block ``y`` is two products masked by the decays between the
rows (``exp`` of differences of the running sum of ``d A``, all of
them <= 0); between blocks the state is carried, a block at a time.
Rows past ``lengths[b]`` are pads: their ``d_t`` is 0, so ``a_t = 1``
and nothing is added, the state after the block is the state after the
lane's last real row, and the rows the convolution carries on are the
last three REAL rows.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax


@dataclasses.dataclass(frozen=True)
class Mamba2Config:
    """The mixer's own sizes. The model's width, its norms' epsilon,
    its types and its kernels' impl are the model's, given to
    :class:`Mamba2Mixer` where it is built."""

    num_heads: int                   # H
    head_dim: int                    # P; the inner width is H P
    state_size: int                  # N
    conv_width: int = 4
    chunk: int = 256

    @property
    def inner(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        """The channels the convolution runs over: ``[x | B | C]``."""
        return self.inner + 2 * self.state_size

    def state_shapes(self, dtype):
        """A lane's state: ``((shape, dtype), (shape, dtype))`` of
        ``S`` and of the convolution's carried rows in the
        activations' ``dtype`` (held flat: three rows are no tile of
        the device's memory, and a pool of them is converted whole
        around every update)."""
        return (((self.num_heads, self.head_dim, self.state_size),
                 jnp.float32),
                (((self.conv_width - 1) * self.conv_dim,), dtype))


def a_log_init(key, shape, dtype=jnp.float32):
    """``A = -U(1, 16)``: Mamba-2's published initialisation."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
                   ).astype(dtype)


def dt_bias_init(key, shape, dtype=jnp.float32, lo=1e-3, hi=1e-1):
    """The softplus-inverse of a step drawn log-uniformly in
    ``[lo, hi]``: Mamba-2's published initialisation."""
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                 * (math.log(hi) - math.log(lo)) + math.log(lo))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def conv_step(rows, c, w, b):
    """One token: ``rows`` (b, k-1, C) the carried rows, ``c`` (b, C)
    the new one -> (convolved (b, C) float32, the rows carried on)."""
    full = jnp.concatenate([rows, c[:, None].astype(rows.dtype)], axis=1)
    out = jnp.einsum("bkc,ck->bc", full.astype(jnp.float32),
                     w.astype(jnp.float32)) + b.astype(jnp.float32)
    return jax.nn.silu(out), full[:, 1:]


def conv_rows(rows, c, w, b, lengths):
    """``s`` tokens a lane: ``rows`` (b, k-1, C), ``c`` (b, s, C) ->
    (convolved (b, s, C) float32, the rows carried on: the last
    ``k - 1`` rows before position ``lengths[b]``)."""
    k = w.shape[1]
    s = c.shape[1]
    full = jnp.concatenate([rows, c.astype(rows.dtype)], axis=1)
    f32 = full.astype(jnp.float32)
    out = b.astype(jnp.float32) + sum(
        f32[:, j:j + s] * w[:, j].astype(jnp.float32) for j in range(k))
    carried = jax.vmap(
        lambda f, n: lax.dynamic_slice_in_dim(f, n, k - 1, axis=0))(
        full, jnp.clip(lengths, 0, s))
    return jax.nn.silu(out), carried


def ssm_step(S, x, dt, A, B, C, D):
    """The one-step recurrence. ``S`` (b, H, P, N); ``x`` (b, H, P),
    ``dt`` (b, H) after the softplus, ``B`` / ``C`` (b, N), all
    float32 -> (y (b, H, P) float32, the new state in ``S``'s type)."""
    a = jnp.exp(dt * A)                                     # (b, H)
    new = (a[:, :, None, None] * S.astype(jnp.float32)
           + (dt[:, :, None] * x)[..., None] * B[:, None, None, :])
    # a sum of products, not a dot: a TPU's default float32 dot rounds
    # its operands to bf16, and this fuses with the update above
    y = (new * C[:, None, None, :]).sum(-1) + D[None, :, None] * x
    return y, new.astype(S.dtype)


def ssm_scan(S, x, dt, A, B, C, D, *, chunk: int, dtype):
    """The chunked form. ``S`` (b, H, P, N); ``x`` (b, s, H, P), ``dt``
    (b, s, H) after the softplus and 0 on pad rows, ``B`` / ``C``
    (b, s, N), float32 (rows past a multiple of ``chunk`` are made up
    with pads, which stand still). The products run on ``dtype``
    operands into float32; decays and the carried state stay float32.
    Returns (y (b, s, H, P) float32, the state after the last row in
    ``S``'s type)."""
    rows = x.shape[1]
    q = min(chunk, rows)
    if rows % q:
        pad = lambda t: jnp.pad(  # noqa: E731
            t, ((0, 0), (0, -rows % q)) + ((0, 0),) * (t.ndim - 2))
        x, dt, B, C = pad(x), pad(dt), pad(B), pad(C)
    b, s, H, P = x.shape
    nc = s // q
    f32 = jnp.float32
    xs = (x * dt[..., None]).reshape(b, nc, q, H, P)       # d_t x_t
    a = (dt * A).reshape(b, nc, q, H)                      # <= 0
    Bc, Cc = B.reshape(b, nc, q, -1), C.reshape(b, nc, q, -1)
    cum = jnp.cumsum(a, axis=2)                            # to row i, incl.
    # inside a block: y_i += sum_{j <= i} (C_i . B_j) exp(cum_i - cum_j)
    #                                     d_j x_j
    cb = jnp.einsum("bcin,bcjn->bcij", Cc.astype(dtype), Bc.astype(dtype),
                    preferred_element_type=f32)
    tri = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(tri[None, None, :, :, None],
                              cum[:, :, :, None] - cum[:, :, None, :],
                              -jnp.inf))                   # (b, nc, i, j, H)
    y = jnp.einsum("bcijh,bcjhp->bcihp",
                   (cb[..., None] * decay).astype(dtype), xs.astype(dtype),
                   preferred_element_type=f32)
    # what a block adds to the state at its end. The state goes into
    # its products as (H P, N) planes: with H and P apart the TPU
    # compiler wants P outside H in the operand, hands that wish back
    # through the slice that read the lane's state, and lays the whole
    # pool out anew around a one-lane call (2.45 GB there and back)
    to_end = jnp.exp(cum[:, :, -1:] - cum)                 # (b, nc, q, H)
    added = jnp.einsum("bcjm,bcjn->bcmn",
                       (xs * to_end[..., None]).astype(dtype).reshape(
                           b, nc, q, H * P),
                       Bc.astype(dtype), preferred_element_type=f32)
    whole = jnp.repeat(jnp.exp(cum[:, :, -1]), P, axis=-1)  # (b, nc, H P)
    from_start = jnp.exp(cum)                              # (b, nc, q, H)
    state = S.astype(f32).reshape(b, H * P, -1)
    outs = []
    for c in range(nc):                  # between blocks: the state
        outs.append(jnp.einsum(
            "bin,bmn->bim", Cc[:, c].astype(dtype), state.astype(dtype),
            preferred_element_type=f32).reshape(b, q, H, P)
            * from_start[:, c, :, :, None])
        state = whole[:, c, :, None] * state + added[:, c]
    state = state.reshape(S.shape)
    y = y + jnp.stack(outs, axis=1)
    y = y.reshape(b, s, H, P) + D[None, None, :, None] * x
    return y[:, :rows], state.astype(S.dtype)


class Mamba2Mixer(nn.Module):
    """``u`` (b, s, hidden) -> (b, s, hidden) and the state pools.

    ``state_ctx = (pools, layer, slots, fresh)``: the pools of state
    slots whole, ``(S (slots + 1, layers, H, P, N), rows (slots + 1,
    layers, (k-1) C))``, this layer's place in them, each lane's slot
    (b,) and whether it starts its sequence here (b,) bool: from zeros
    then, whatever its slot held. The lanes' new state goes back into
    their slots and the pools are returned. None: every lane from
    zeros, its state dropped (None returned). ``lengths`` (b,) are the
    lanes' real rows where ``s > 1`` (None: all of them). ``s == 1``
    runs the one-step form, on the states where they lie
    (``ops/ssm_step.py``), anything longer the chunked one."""

    config: Mamba2Config
    hidden_size: int
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    impl: Optional[str] = None       # the one-step kernel's (ops/ssm_step.py)

    @nn.compact
    def __call__(self, u, *, state_ctx=None, lengths=None):
        from apex_tpu.ops.ssm_step import (read_lanes, ssm_step_by_slot,
                                           write_lanes)

        cfg = self.config
        b, s, _ = u.shape
        H, P, N = cfg.num_heads, cfg.head_dim, cfg.state_size
        D_in, C_dim = cfg.inner, cfg.conv_dim
        init = nn.initializers.normal(stddev=0.02)
        w_in = self.param("in_proj", init,
                          (self.hidden_size, D_in + C_dim + H),
                          self.param_dtype)
        conv_w = self.param("conv_w", nn.initializers.normal(stddev=0.2),
                            (C_dim, cfg.conv_width), self.param_dtype)
        conv_b = self.param("conv_b", nn.initializers.zeros, (C_dim,),
                            self.param_dtype)
        dt_bias = self.param("dt_bias", dt_bias_init, (H,), jnp.float32)
        a_log = self.param("A_log", a_log_init, (H,), jnp.float32)
        d_skip = self.param("D", nn.initializers.ones, (H,), jnp.float32)
        gain = self.param("norm", nn.initializers.ones, (D_in,), jnp.float32)
        w_out = self.param("out_proj", init, (D_in, self.hidden_size),
                           self.param_dtype)
        if state_ctx is None:          # one slot a lane, made here
            layer, slots = 0, jnp.arange(b)
            fresh = jnp.ones((b,), bool)
            S_pool, rows_pool = (
                jnp.zeros((b, 1, *shape), dtype)
                for shape, dtype in cfg.state_shapes(self.dtype))
        else:
            (S_pool, rows_pool), layer, slots, fresh = state_ctx
        rows = read_lanes(rows_pool, layer, slots, fresh).reshape(
            b, cfg.conv_width - 1, C_dim)
        z, c, dt = jnp.split(jnp.dot(u, w_in.astype(self.dtype)),
                             [D_in, D_in + C_dim], axis=-1)
        A = -jnp.exp(a_log.astype(jnp.float32))
        dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
        if s == 1:
            c, rows = conv_step(rows, c[:, 0], conv_w, conv_b)
            x, B, C = jnp.split(c, [D_in, D_in + N], axis=-1)
            with jax.named_scope("ssm_step"):
                y, S_pool = ssm_step_by_slot(
                    S_pool, slots, fresh, layer, x.reshape(b, H, P),
                    dt[:, 0], A, B, C, d_skip, impl=self.impl)
            y = y[:, None]
        else:
            if lengths is None:
                lengths = jnp.full((b,), s, jnp.int32)
            c, rows = conv_rows(rows, c, conv_w, conv_b, lengths)
            x, B, C = jnp.split(c, [D_in, D_in + N], axis=-1)
            real = jnp.arange(s)[None, :] < lengths[:, None]
            dt = jnp.where(real[..., None], dt, 0.0)     # pads stand still
            with jax.named_scope("ssm_scan"):
                y, S = ssm_scan(read_lanes(S_pool, layer, slots, fresh),
                                x.reshape(b, s, H, P), dt, A, B, C,
                                d_skip, chunk=cfg.chunk, dtype=self.dtype)
            S_pool = write_lanes(S_pool, layer, slots, S)
        g = y.reshape(b, s, D_in) * jax.nn.silu(z.astype(jnp.float32))
        g = g * lax.rsqrt((g * g).mean(-1, keepdims=True)
                          + self.rms_eps) * gain
        out = jnp.dot(g.astype(self.dtype), w_out.astype(self.dtype))
        if state_ctx is None:
            return out, None
        return out, (S_pool, write_lanes(rows_pool, layer, slots,
                                         rows.reshape(b, -1)))


__all__ = ["Mamba2Config", "Mamba2Mixer", "a_log_init", "conv_rows",
           "conv_step", "dt_bias_init", "ssm_scan",
           "ssm_step"]
