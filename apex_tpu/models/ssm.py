"""The state-space mixers, each in two forms over one parameter tree:
Mamba-2 (state-space duality, Dao & Gu 2024) and, below it, Mamba-1's
selective scan (Gu & Dao 2023, :class:`SelectiveMixer`). Mamba-2's: the *chunked* form for ``s > 1`` tokens a lane
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


#: the lanes of a vector register: a selective mixer's channels are
#: held in groups of this many, minor-most (``SelectiveSSMConfig``)
LANES = 128


@dataclasses.dataclass(frozen=True)
class SelectiveSSMConfig:
    """Mamba-1's selective scan (Gu & Dao 2023) as Jamba runs it, one
    decay for every (channel, state) pair::

        [x | z] = u W_in                                 (E each)
        x_t <- silu(b_conv + sum_j w_conv[:, j] x_{t-3+j})  (depthwise)
        [d | B | C] = x_t W_x                            (R, N, N)
        d, B, C <- RMSNorm each (Jamba's inner norms)
        Delta_t = softplus(d W_dt + b_dt)                (E,)
        S_t[e, n] = exp(Delta_t[e] A[e, n]) S_{t-1}[e, n]
                    + Delta_t[e] B_t[n] x_t[e]           float32
        y_t[e] = sum_n S_t[e, n] C_t[n] + D[e] x_t[e]
        o = (y_t * silu(z_t)) W_out                      (no norm)

    With a decay for every channel and state there is no scalar decay
    a head to factor out of the products, so the SSD chunk form of
    Mamba-2 does not apply: a chunk is a scan over its rows
    (``ops/ssm_scan.py``), a decode step one turn of it
    (``ops/ssm_step.py``). A lane's state is ``S`` held with the
    channels minor-most, ``(E / 128, N, 128)``: ``N`` (16) as the minor
    dimension would pad every tile to 128 lanes, eight times the bytes.
    """

    inner: int                       # E
    state_size: int                  # N
    dt_rank: int                     # R
    conv_width: int = 4

    def __post_init__(self):
        if self.inner % LANES:
            raise ValueError(f"the inner width ({self.inner}) is held in "
                             f"groups of {LANES} channels: a multiple of it")

    def state_shapes(self, dtype):
        """A lane's state: ``S`` as ``(E / 128, N, 128)`` float32, and
        the convolution's carried rows of ``x`` flat in ``dtype``."""
        return (((self.inner // LANES, self.state_size, LANES), jnp.float32),
                (((self.conv_width - 1) * self.inner,), dtype))


def a_log_range_init(key, shape, dtype=jnp.float32):
    """``A[e] = -(1, 2, ..., N)`` for every channel: Mamba-1's published
    initialisation (S4D-real). ``shape`` is ``(E, N)``."""
    del key
    return jnp.log(jnp.broadcast_to(
        jnp.arange(1, shape[1] + 1, dtype=jnp.float32), shape)).astype(dtype)


def symmetric_uniform(bound: float):
    """``U(-bound, bound)``: Mamba-1's convolution (torch's default at
    fan-in 4) and ``W_dt`` (``R^-0.5``)."""
    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, jnp.float32, -bound,
                                  bound).astype(dtype)
    return init


def channel_groups(t):
    """``(..., E)`` -> ``(..., E / 128, 1, 128)``: a channel's value
    where it lies in the state, broadcast along ``N``."""
    return t.reshape(*t.shape[:-1], t.shape[-1] // LANES, 1, LANES)


def decay_table(A):
    """``A`` (E, N) -> ``(E / 128, N, 128)``, the state's layout."""
    E, N = A.shape
    return A.reshape(E // LANES, LANES, N).transpose(0, 2, 1)


def selective_step(S, x, delta, A, B, C):
    """One token of the selective scan. ``S`` (b, E/128, N, 128);
    ``x``, ``delta`` (b, E), ``A`` (E, N), ``B`` / ``C`` (b, N), all
    float32 -> (``sum_n S' C`` (b, E) float32, ``S'`` in ``S``'s type).
    ``D x`` and the gate are the caller's."""
    d = channel_groups(delta)
    new = (jnp.exp(d * decay_table(A)) * S.astype(jnp.float32)
           + (d * channel_groups(x)) * B[:, None, :, None])
    # a sum of products, not a dot (ssm_step's reason)
    y = (new * C[:, None, :, None]).sum(-2)
    return y.reshape(x.shape), new.astype(S.dtype)


def selective_scan(S, x, delta, A, B, C, D, z):
    """``s`` tokens a lane, a scan over them. ``S`` (b, E/128, N, 128);
    ``x``, ``delta`` (b, s, E) float32, ``delta`` 0 on pad rows (they
    stand still); ``z`` (b, s, E); ``B`` / ``C`` (b, s, N) float32.
    Returns (``(sum_n S_t C_t + D x_t) silu(z_t)`` (b, s, E) float32,
    the state after the last row in ``S``'s type)."""
    def token(S, t):
        x_t, d_t, B_t, C_t = t
        y, S = selective_step(S, x_t, d_t, A, B_t, C_t)
        return S, y

    S, y = lax.scan(token, S, tuple(jnp.moveaxis(t, 1, 0)
                                    for t in (x, delta, B, C)))
    y = jnp.moveaxis(y, 0, 1) + D * x
    return y * jax.nn.silu(z.astype(jnp.float32)), S


def _rms(t, gain, eps):
    return t * lax.rsqrt((t * t).mean(-1, keepdims=True) + eps) * gain


class SelectiveMixer(nn.Module):
    """Mamba-1's mixer (:class:`SelectiveSSMConfig`): ``u`` (b, s,
    hidden) -> (b, s, hidden) and the state pools, over the same
    ``state_ctx`` and ``lengths`` as :class:`Mamba2Mixer`. ``s == 1``
    runs one turn on the lanes' states where they lie
    (``ops/ssm_step.py`` ``selective_step_by_slot``), anything longer
    the scan (``ops/ssm_scan.py``) between :func:`read_lanes` and
    :func:`write_lanes` of ``ops/ssm_step.py``. A pad row's ``Delta``
    is 0: its decay is 1 and it adds nothing, and the convolution
    carries the last real rows on."""

    config: SelectiveSSMConfig
    hidden_size: int
    rms_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    impl: Optional[str] = None       # the kernels' (ops/ssm_*.py)

    @nn.compact
    def __call__(self, u, *, state_ctx=None, lengths=None):
        from apex_tpu.ops.ssm_scan import ssm_scan as scan_kernel
        from apex_tpu.ops.ssm_step import (read_lanes,
                                           selective_step_by_slot,
                                           write_lanes)

        cfg, f32, dt = self.config, jnp.float32, self.dtype
        b, s, _ = u.shape
        E, N, R, k = cfg.inner, cfg.state_size, cfg.dt_rank, cfg.conv_width
        init = nn.initializers.normal(stddev=0.02)
        conv = symmetric_uniform(0.5)

        def param(name, shape, how=init, dtype=self.param_dtype):
            return self.param(name, how, shape, dtype)

        w_in = param("in_proj", (self.hidden_size, 2 * E))
        conv_w = param("conv_w", (E, k), conv)
        conv_b = param("conv_b", (E,), conv)
        w_x = param("x_proj", (E, R + 2 * N))
        gains = [param(name, (n,), nn.initializers.ones, f32)
                 for name, n in (("dt_norm", R), ("B_norm", N),
                                 ("C_norm", N))]
        w_dt = param("dt_proj", (R, E), symmetric_uniform(R ** -0.5))
        dt_bias = param("dt_bias", (E,), dt_bias_init, f32)
        a_log = param("A_log", (E, N), a_log_range_init, f32)
        d_skip = param("D", (E,), nn.initializers.ones, f32)
        w_out = param("out_proj", (E, self.hidden_size))
        if state_ctx is None:          # one slot a lane, made here
            layer, slots = 0, jnp.arange(b)
            fresh = jnp.ones((b,), bool)
            S_pool, rows_pool = (
                jnp.zeros((b, 1, *shape), dtype)
                for shape, dtype in cfg.state_shapes(dt))
        else:
            (S_pool, rows_pool), layer, slots, fresh = state_ctx
        rows = read_lanes(rows_pool, layer, slots, fresh).reshape(b, k - 1, E)
        x, z = jnp.split(jnp.dot(u, w_in.astype(dt)), 2, axis=-1)
        if s == 1:
            x, rows = conv_step(rows, x[:, 0], conv_w, conv_b)
            x = x[:, None]
        else:
            if lengths is None:
                lengths = jnp.full((b,), s, jnp.int32)
            x, rows = conv_rows(rows, x, conv_w, conv_b, lengths)
        d, B, C = jnp.split(
            jnp.dot(x.astype(dt), w_x.astype(dt), preferred_element_type=f32),
            [R, R + N], axis=-1)
        d, B, C = (_rms(t, g, self.rms_eps) for t, g in zip((d, B, C), gains))
        delta = jax.nn.softplus(
            jnp.dot(d.astype(dt), w_dt.astype(dt), preferred_element_type=f32)
            + dt_bias)
        A = -jnp.exp(a_log)
        if s == 1:
            with jax.named_scope("ssm_step"):
                y, S_pool = selective_step_by_slot(
                    S_pool, slots, fresh, layer, x[:, 0], delta[:, 0], A,
                    B[:, 0], C[:, 0], impl=self.impl)
            y = ((y + d_skip * x[:, 0])
                 * jax.nn.silu(z[:, 0].astype(f32)))[:, None]
        else:
            real = jnp.arange(s)[None, :] < lengths[:, None]
            delta = jnp.where(real[..., None], delta, 0.0)  # pads stand still
            with jax.named_scope("ssm_scan"):
                y, S = scan_kernel(read_lanes(S_pool, layer, slots, fresh),
                                   x, delta, A, B, C, d_skip, z,
                                   impl=self.impl)
            S_pool = write_lanes(S_pool, layer, slots, S)
        out = jnp.dot(y.astype(dt), w_out.astype(dt))
        if state_ctx is None:
            return out, None
        return out, (S_pool, write_lanes(rows_pool, layer, slots,
                                         rows.reshape(b, -1)))


__all__ = ["LANES", "Mamba2Config", "Mamba2Mixer", "SelectiveMixer",
           "SelectiveSSMConfig", "a_log_init", "a_log_range_init",
           "channel_groups", "conv_rows", "conv_step", "decay_table",
           "dt_bias_init", "selective_scan", "selective_step", "ssm_scan",
           "ssm_step", "symmetric_uniform"]
