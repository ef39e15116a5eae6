"""The plain reference of the ``jamba`` block as ``models/decoder.py``
runs it (AI21 Jamba2-3B): the forward pass in straightforward float32
``jax.numpy``, with no kernel, cache or batching. The recurrence is a
plain ``lax.scan`` over tokens.

    x0 = E[tokens];   logits = n_f(x_L) E^T                    (tied)
    layer:  h = x + Mixer(n_1(x));  x' = h + MLP(n_2(h))       (pre-norm)
    n(x) = x * rsqrt(mean(x^2) + eps) * g                      (float32)
    MLP(u) = (silu(u W_g) * (u W_u)) W_d
    Mixer of a ``mamba`` layer (Mamba-1: E channels, a state of N a
    channel, dt of rank R):
      [x | z] = u W_in                              (no bias)
      x_t <- silu(b_conv + sum_{j<4} w_conv[:, j] x_{t-3+j})
                                  depthwise, causal, zeros before 0
      [d | B | C] = x_t W_x                         (R, N, N; no bias)
      d, B, C <- n_dt(d), n_B(B), n_C(C)            (Jamba's inner norms)
      Delta_t = softplus(d W_dt + b_dt);  A = -exp(A_log)      (E, N)
      S_t[e, n] = exp(Delta_t[e] A[e, n]) S_{t-1}[e, n]
                  + Delta_t[e] B_t[n] x_t[e]        (E, N), S_{-1} = 0
      y_t = sum_n S_t[:, n] C_t[n] + D x_t
      Mixer = (y_t * silu(z_t)) W_out               (no norm)
    Mixer of an ``attention`` layer: q, k, v = u W (no bias, no
      QK-norm, NO positional encoding); scores q.k / sqrt(head_dim),
      causal, every key, softmax, one KV head for every query head;
      Mixer = Attn W_o

It reads the program's parameter tree (the weights are the thing
compared) and nothing else of the program. Departures from the
published layout, none of them mathematics: the three attention
projections are stored as one matrix ``[q | k | v]``, kernels are
stored ``(in, out)``, the convolution's kernel ``(channels, 4)``, the
inner norms' gains under the mixer as ``dt_norm`` / ``B_norm`` /
``C_norm``.

The parts that are no model's own (a norm, the gated MLP, rotary, the
judgement of served tokens) are ``decoder_reference``'s, imported.
Every product runs under ``jax.default_matmul_precision("highest")``;
one layer's weights are upcast at a time, attention runs in blocks of
query rows and the head in blocks of vocabulary rows, so the reference
fits beside a server that holds the bf16 model.

``round_to`` and ``faults`` are the controls (``tests/
test_selective_ssm.py``, and on the chip ``benchmark/
controls_jamba.py``): ``round_to`` rounds every weight and every
product through a lower type, and a fault computes a *wrong* model
(the state carried in bfloat16, no inner norms, one decay a channel
(``A`` averaged over ``N``), no ``dt`` bias, no convolution bias, no
``D x``, rotary on the attention layers), which the comparison has to
refuse.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.models.decoder_reference import (
    _cast, _gated, _rms, _rotary, judge, teacher_forced)

F32 = jnp.float32
FAULTS = ("bf16_state", "no_inner_norms", "scalar_decay", "no_dt_bias",
          "no_conv_bias", "no_D", "rope_on_attention")


class Arch(NamedTuple):
    """What the reference needs beside the weights."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    layers: Tuple[str, ...]                  # "mamba" | "attention" a layer
    inner: int                               # E
    state: int                               # N
    dt_rank: int                             # R
    eps: float = 1e-6
    rope_theta: float = 10000.0              # the rope_on_attention control's


@functools.partial(jax.jit, static_argnames=("arch", "round_to", "faults"))
def _mamba(x, p, *, arch: Arch, round_to, faults):
    """``Mixer(n_1(x))`` of a ``mamba`` layer over ONE sequence ``x``
    (n, hidden), from zeros: the recurrence a scan over tokens."""
    with jax.default_matmul_precision("highest"):
        r = functools.partial(_cast, round_to=round_to)
        E, N, R = arch.inner, arch.state, arch.dt_rank
        mix = p["mixer"]
        u = r(_rms(x, r(p["input_norm"]["scale"]), arch.eps))
        xs, z = jnp.split(r(u @ r(mix["in_proj"])), [E], axis=-1)
        w = r(mix["conv_w"])                               # (E, k)
        k = w.shape[1]
        n = x.shape[0]
        padded = jnp.concatenate([jnp.zeros((k - 1, E), F32), xs])
        conv = sum(padded[j:j + n] * w[:, j] for j in range(k))
        if "no_conv_bias" not in faults:
            conv = conv + r(mix["conv_b"])
        xs = r(jax.nn.silu(conv))
        d, B, C = jnp.split(r(xs @ r(mix["x_proj"])), [R, R + N], axis=-1)
        if "no_inner_norms" not in faults:
            d = r(_rms(d, jnp.asarray(mix["dt_norm"], F32), arch.eps))
            B = r(_rms(B, jnp.asarray(mix["B_norm"], F32), arch.eps))
            C = r(_rms(C, jnp.asarray(mix["C_norm"], F32), arch.eps))
        dt = r(d @ r(mix["dt_proj"]))
        if "no_dt_bias" not in faults:
            dt = dt + jnp.asarray(mix["dt_bias"], F32)
        delta = jax.nn.softplus(dt)                        # (n, E)
        A = -jnp.exp(jnp.asarray(mix["A_log"], F32))       # (E, N)
        if "scalar_decay" in faults:     # one decay a channel
            A = jnp.broadcast_to(A.mean(-1, keepdims=True), A.shape)
        skip = jnp.asarray(mix["D"], F32)
        if "no_D" in faults:
            skip = jnp.zeros_like(skip)

        def token(S, t):
            x_t, d_t, B_t, C_t = t
            S = r(jnp.exp(d_t[:, None] * A) * S
                  + (d_t * x_t)[:, None] * B_t[None, :])
            if "bf16_state" in faults:
                # the control: the state carried in bfloat16. By
                # reduce_precision: a convert there and back is one the
                # TPU compiler may drop (it allows excess precision)
                S = jax.lax.reduce_precision(S, exponent_bits=8,
                                             mantissa_bits=7)
            return S, (S * C_t[None, :]).sum(-1) + skip * x_t

        _, y = jax.lax.scan(token, jnp.zeros((E, N), F32), (xs, delta, B, C))
        o = r(r(y) * jax.nn.silu(z))
        return r(o @ r(mix["out_proj"]))


@functools.partial(jax.jit, static_argnames=("arch", "round_to", "rope"))
def _qkv(x, p, positions, *, arch: Arch, round_to, rope: bool):
    with jax.default_matmul_precision("highest"):
        r = functools.partial(_cast, round_to=round_to)
        nh, nkv, d = arch.num_heads, arch.num_kv_heads, arch.head_dim
        u = r(_rms(x, r(p["input_norm"]["scale"]), arch.eps))
        q, k, v = jnp.split(r(u @ r(p["attention"]["qkv"])),
                            [nh * d, (nh + nkv) * d], axis=-1)
        n = x.shape[0]
        q, k = q.reshape(n, nh, d), k.reshape(n, nkv, d)
        if rope:                         # the control: Jamba has none
            q = r(_rotary(q, positions, arch.rope_theta))
            k = r(_rotary(k, positions, arch.rope_theta))
        return q, k, v.reshape(n, nkv, d)


@functools.partial(jax.jit, static_argnames=("round_to",))
def _attend(q, k, v, q_pos, k_pos, *, round_to):
    """``q`` (rows, heads, d) against every key (n, kv_heads, d) at or
    before its own position, scores scaled by ``d^-0.5``."""
    with jax.default_matmul_precision("highest"):
        rows, nh, d = q.shape
        nkv = k.shape[1]
        qg = q.reshape(rows, nkv, nh // nkv, d)
        s = jnp.einsum("rkgd,nkd->kgrn", qg, k) * d ** -0.5
        see = k_pos[None, :] <= q_pos[:, None]
        p = jax.nn.softmax(jnp.where(see, s, -jnp.inf), axis=-1)
        o = jnp.einsum("kgrn,nkd->rkgd", p, v).reshape(rows, nh * d)
        return _cast(o, round_to)


@functools.partial(jax.jit, static_argnames=("round_to",))
def _project(o, proj, *, round_to):
    with jax.default_matmul_precision("highest"):
        return _cast(o @ _cast(proj, round_to), round_to)


@functools.partial(jax.jit, static_argnames=("arch", "round_to"))
def _mlp_half(x, a, p, *, arch: Arch, round_to):
    """``h = x + a``, then ``h + MLP(n_2(h))``."""
    r = functools.partial(_cast, round_to=round_to)
    h = r(x + a)
    m = r(_rms(h, r(p["pre_mlp_norm"]["scale"]), arch.eps))
    mlp = p["mlp"]
    return r(h + _gated(m, mlp["gate"], mlp["up"], mlp["down"],
                        round_to=round_to))


@functools.partial(jax.jit, static_argnames=("arch", "round_to", "block"))
def _head(x, norm, table, *, arch: Arch, round_to, block: int = 16384):
    """The tied head a block of vocabulary rows at a time."""
    with jax.default_matmul_precision("highest"):
        r = functools.partial(_cast, round_to=round_to)
        x = r(_rms(x, r(norm["scale"]), arch.eps))
        return jnp.concatenate(
            [x @ r(table[a:a + block]).T
             for a in range(0, table.shape[0], block)], axis=-1)


def forward(params, tokens, rows, arch: Arch, *, round_to=None,
            faults: Sequence[str] = (), row_block: int = 128):
    """Float32 logits (len(rows), vocab) of ONE sequence ``tokens``
    (1-D) at the positions ``rows``."""
    faults = tuple(sorted(faults))
    if set(faults) - set(FAULTS):
        raise ValueError(f"unknown faults {faults}; known: {FAULTS}")
    p = params["params"]
    tokens = jnp.asarray(tokens, jnp.int32)
    n = tokens.shape[0]
    positions = jnp.arange(n, dtype=jnp.int32)
    x = _cast(p["embedding"][tokens], round_to)
    for i, kind in enumerate(arch.layers):
        layer = p[f"layer_{i}"]
        if kind == "mamba":
            a = _mamba(x, layer, arch=arch, round_to=round_to, faults=faults)
        else:
            q, k, v = _qkv(x, layer, positions, arch=arch, round_to=round_to,
                           rope="rope_on_attention" in faults)
            o = jnp.concatenate([
                _attend(q[a:a + row_block], k, v, positions[a:a + row_block],
                        positions, round_to=round_to)
                for a in range(0, n, row_block)])
            a = _project(o, layer["attention"]["proj"], round_to=round_to)
        x = _mlp_half(x, a, layer, arch=arch, round_to=round_to)
    return _head(x[jnp.asarray(rows)], p["final_norm"], p["embedding"],
                 arch=arch, round_to=round_to)


def check_served(params, arch: Arch, prompt, served, *, ulps: float,
                 dtype_eps: float, pad_to: int = 128, round_to=None,
                 faults: Sequence[str] = (), program_logits=None,
                 logit_limit: float = float("inf"),
                 rms_limit: float = float("inf")) -> Dict[str, Any]:
    """:func:`judge` of a served sequence, teacher-forced through the
    reference: every served token must be the reference's argmax or
    trail it by at most ``ulps`` ulps (no row is excused: the model
    routes nothing, so no choice of the program's can differ from the
    reference's). ``program_logits`` (len(served), vocab) are the
    program's own logits of the rows that decided the served tokens
    (kept from the dispatches that served them); then the sequence is
    ok only if at every row they leave the reference's by no more
    than ``logit_limit`` of the reference's largest logit in that row
    (``logit_error`` the worst row's) and by no more than
    ``rms_limit`` in the root mean square over the rows
    (``logit_rms``): a token's argmax need not show an error that the
    state carries, and a fault that moves every row a little stands
    out of the rounding of a bf16 program in the mean where no single
    row shows it."""
    toks, rows = teacher_forced(prompt, served, pad_to)
    logits = forward(params, toks, rows, arch, round_to=round_to,
                     faults=faults)
    out = judge(logits, served, np.zeros(len(rows), bool), ulps=ulps,
                dtype_eps=dtype_eps)
    out.update(logit_error=0.0, logit_worst_row=0, logit_rms=0.0)
    if program_logits is not None:
        off = np.asarray(
            jnp.abs(jnp.asarray(program_logits, F32) - logits).max(-1)
            / jnp.abs(logits).max(-1))
        rms = float(np.sqrt((off ** 2).mean()))
        out.update(logit_error=float(off.max()),
                   logit_worst_row=int(off.argmax()), logit_rms=rms,
                   logit_error_by_row=off,
                   ok=(out["ok"] and bool(off.max() <= logit_limit)
                       and rms <= rms_limit))
    return out
