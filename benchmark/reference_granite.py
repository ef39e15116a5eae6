"""The plain reference of the ``granitemoehybrid`` block as
``models/decoder.py`` runs it (IBM Granite 4.0-H): the forward pass in
straightforward float32 ``jax.numpy``, with no kernel, cache, chunked
form or batching. The recurrence is a plain ``lax.scan`` over tokens.

    x0 = e_mult E[tokens];   logits = n_f(x_L) E^T / l_div      (tied)
    layer:  y = x + r Mixer(n_1(x));  m = n_2(y);
            x' = y + r (Experts(m) + Shared(m))         (pre-norm)
    n(x) = x * rsqrt(mean(x^2) + eps) * g               (float32)
    Mixer of a ``mamba`` layer (Mamba-2: H heads of P, a state of N,
    one group, inner width D = H P):
      [z | c | dt] = u W_in                      (no bias)
      c_t <- silu(b_conv + sum_{j<4} w_conv[:, j] c_{t-3+j})
                                  depthwise, causal, zeros before 0
      [x | B | C] = c_t                x: (H, P);  B, C: (N,)
      d_t = softplus(dt_t + dt_bias);  A = -exp(A_log);  a_t = exp(d_t A)
      S_t = a_t S_{t-1} + d_t x_t (x) B_t        (H, P, N), S_{-1} = 0
      y_t = S_t C_t + D x_t
      g = y_t * silu(z_t);  o = g rsqrt(mean_D(g^2) + eps) w_norm
                                  the gate BEFORE the norm, one group
      Mixer = o W_out
    Mixer of an ``attention`` layer: q, k, v = u W (no bias, no
      QK-norm, no rotary); scores q.k * a_mult, causal, every key,
      softmax, GQA;  Mixer = Attn W_o
    Experts: p = softmax(m W_r) over ALL the experts; chosen = top-k;
      w = p[chosen] / sum p[chosen]  (= softmax over the chosen logits);
      sum_e w_e (silu(m W_gate,e) * (m W_up,e)) W_down,e
    Shared:  (silu(m V_gate) * (m V_up)) V_down

Given ``held = (first, count)`` it leaves out what the experts outside
that range would add, as the program does; the shared MLP is whole.

It reads the program's parameter tree (the weights are the thing
compared) and nothing else of the program. Departures from the
published layout, none of them mathematics: the three attention
projections are stored as one matrix ``[q | k | v]``, kernels are
stored ``(in, out)``, the convolution's kernel ``(channels, 4)``, a
layer's experts are stacked and only the held ones are stored.

The parts that are no model's own (a norm, a gated MLP, one expert's
rows, the judgement of served tokens) are ``decoder_reference``'s,
imported. Every product runs under
``jax.default_matmul_precision("highest")``; one layer's weights are
upcast at a time, one expert's at a time, attention runs in blocks of
query rows and the head in blocks of vocabulary rows, so the reference
fits beside a server that holds the bf16 model.

As ``decoder_reference``, it can be *given the program's choice* of
experts (``forward(choice=...)``): where that choice differs from its
own only among experts whose log probabilities, as the reference
computes them, lie within ``band`` of the cut, it follows the program;
anywhere else it keeps its own, and a program that chose wrongly shows.

``round_to`` and ``faults`` are the controls (``tests/test_decoder.py``,
and on the chip ``benchmark/controls_granite.py``): ``round_to`` rounds
every weight and every product through a lower type, and a fault
computes a *wrong* model (the gate after the norm, no ``dt_bias``, no
convolution bias, scores scaled by ``head_dim^-0.5``, residuals added
whole, the recurrent state carried in bfloat16), which the comparison
has to refuse.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference_trinity import (
    _add_expert, _cast, _gated, _rms, held_margin, judge, teacher_forced)

F32 = jnp.float32
FAULTS = ("gate_after_norm", "no_dt_bias", "no_conv_bias", "sqrt_scale",
          "residual_one", "bf16_state")


class Arch(NamedTuple):
    """What the reference needs beside the weights."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    layers: Tuple[str, ...]                  # "mamba" | "attention" a layer
    mamba_heads: int
    mamba_head_dim: int
    mamba_state: int
    top_k: int
    attention_multiplier: float
    embedding_multiplier: float
    residual_multiplier: float
    logits_scaling: float
    held: Optional[Tuple[int, int]] = None   # (first, count); None = all
    eps: float = 1e-5


@functools.partial(jax.jit, static_argnames=("arch", "round_to", "faults"))
def _mamba(x, p, *, arch: Arch, round_to, faults):
    """``r Mixer(n_1(x))`` of a ``mamba`` layer over ONE sequence
    ``x`` (n, hidden), from zeros: the recurrence a scan over tokens."""
    with jax.default_matmul_precision("highest"):
        r = functools.partial(_cast, round_to=round_to)
        H, P, N = arch.mamba_heads, arch.mamba_head_dim, arch.mamba_state
        D = H * P
        mix = p["mixer"]
        u = r(_rms(x, r(p["input_norm"]["scale"]), arch.eps))
        z, c, dt = jnp.split(r(u @ r(mix["in_proj"])), [D, 2 * D + 2 * N],
                             axis=-1)
        w = r(mix["conv_w"])                               # (channels, k)
        k = w.shape[1]
        n = x.shape[0]
        padded = jnp.concatenate([jnp.zeros((k - 1, c.shape[1]), F32), c])
        conv = sum(padded[j:j + n] * w[:, j] for j in range(k))
        if "no_conv_bias" not in faults:
            conv = conv + r(mix["conv_b"])
        c = r(jax.nn.silu(conv))
        xs, B, C = jnp.split(c, [D, D + N], axis=-1)
        xs = xs.reshape(n, H, P)
        if "no_dt_bias" not in faults:
            dt = dt + jnp.asarray(mix["dt_bias"], F32)
        d = jax.nn.softplus(dt)                            # (n, H)
        A = -jnp.exp(jnp.asarray(mix["A_log"], F32))
        skip = jnp.asarray(mix["D"], F32)
        def token(S, t):
            x_t, d_t, B_t, C_t = t
            S = r(jnp.exp(d_t * A)[:, None, None] * S
                  + (d_t[:, None] * x_t)[:, :, None] * B_t[None, None, :])
            if "bf16_state" in faults:
                # the control: the state carried in bfloat16. By
                # reduce_precision: a convert there and back is one the
                # TPU compiler may drop (it allows excess precision),
                # and the control then reads what the true model reads
                S = jax.lax.reduce_precision(S, exponent_bits=8,
                                             mantissa_bits=7)
            return S, (S * C_t[None, None, :]).sum(-1) + skip[:, None] * x_t

        _, y = jax.lax.scan(token, jnp.zeros((H, P, N), F32), (xs, d, B, C))
        y = r(y.reshape(n, D))
        gate, gain = jax.nn.silu(z), r(mix["norm"])
        if "gate_after_norm" in faults:
            o = _rms(y, gain, arch.eps) * gate
        else:
            o = _rms(y * gate, gain, arch.eps)
        return r(r(o) @ r(mix["out_proj"]))


@functools.partial(jax.jit, static_argnames=("arch", "round_to"))
def _qkv(x, p, *, arch: Arch, round_to):
    with jax.default_matmul_precision("highest"):
        r = functools.partial(_cast, round_to=round_to)
        nh, nkv, d = arch.num_heads, arch.num_kv_heads, arch.head_dim
        u = r(_rms(x, r(p["input_norm"]["scale"]), arch.eps))
        q, k, v = jnp.split(r(u @ r(p["attention"]["qkv"])),
                            [nh * d, (nh + nkv) * d], axis=-1)
        n = x.shape[0]
        return (q.reshape(n, nh, d), k.reshape(n, nkv, d),
                v.reshape(n, nkv, d))


@functools.partial(jax.jit, static_argnames=("scale", "round_to"))
def _attend(q, k, v, q_pos, k_pos, *, scale: float, round_to):
    """``q`` (rows, heads, d) against every key (n, kv_heads, d) at or
    before its own position; no positional embedding."""
    with jax.default_matmul_precision("highest"):
        rows, nh, d = q.shape
        nkv = k.shape[1]
        qg = q.reshape(rows, nkv, nh // nkv, d)
        s = jnp.einsum("rkgd,nkd->kgrn", qg, k) * scale
        see = k_pos[None, :] <= q_pos[:, None]
        p = jax.nn.softmax(jnp.where(see, s, -jnp.inf), axis=-1)
        o = jnp.einsum("kgrn,nkd->rkgd", p, v).reshape(rows, nh * d)
        return _cast(o, round_to)


@functools.partial(jax.jit, static_argnames=("round_to",))
def _project(o, proj, *, round_to):
    with jax.default_matmul_precision("highest"):
        return _cast(o @ _cast(proj, round_to), round_to)


@functools.partial(jax.jit, static_argnames=("arch", "round_to", "mult"))
def _add_mixer(x, a, p, *, arch: Arch, round_to, mult: float):
    """``y = x + r a`` and ``m = n_2(y)``."""
    r = functools.partial(_cast, round_to=round_to)
    y = r(x + mult * a)
    return y, r(_rms(y, r(p["pre_mlp_norm"]["scale"]), arch.eps))


@functools.partial(jax.jit,
                   static_argnames=("arch", "band", "round_to"))
def _route(m, router, theirs, *, arch: Arch, band, round_to):
    """(weights (n, k), chosen (n, k), misfit (n,)).

    ``theirs`` (n, k) is the program's choice, or None. Its ``misfit``
    is how far outside the reference's own choice it lies, in LOG
    probability: the reference's k-th less the least one the program
    took, or the largest one the program left less the reference's
    (k+1)-th, whichever is more; 0 or less where the two choices are
    one. Up to ``band`` the reference takes the program's choice; past
    it the reference keeps its own."""
    with jax.default_matmul_precision("highest"):
        k = arch.top_k
        log_p = jax.nn.log_softmax(m @ _cast(router, round_to), axis=-1)
        top, ids = jax.lax.top_k(log_p, k + 1)
        chosen = ids[:, :k]
        misfit = jnp.zeros(log_p.shape[:1], F32)
        if theirs is not None:
            at = jnp.arange(log_p.shape[0])[:, None]
            taken = jnp.zeros(log_p.shape, bool).at[at, theirs].set(True)
            misfit = jnp.maximum(
                top[:, k - 1] - jnp.where(taken, log_p, jnp.inf).min(-1),
                jnp.where(taken, -jnp.inf, log_p).max(-1) - top[:, k])
            misfit = jnp.where(taken.sum(-1) == k, misfit, jnp.inf)
            chosen = jnp.where((misfit <= band)[:, None], theirs, chosen)
        w = jnp.exp(jnp.take_along_axis(log_p, chosen, axis=-1))
        return w / w.sum(-1, keepdims=True), chosen, misfit


@functools.partial(jax.jit, static_argnames=("arch", "round_to", "block"))
def _head(x, norm, table, *, arch: Arch, round_to, block: int = 16384):
    """The tied head a block of vocabulary rows at a time: one block's
    float32 copy is alive, not the whole matrix's."""
    with jax.default_matmul_precision("highest"):
        r = functools.partial(_cast, round_to=round_to)
        x = r(_rms(x, r(norm["scale"]), arch.eps))
        return jnp.concatenate(
            [x @ r(table[a:a + block]).T
             for a in range(0, table.shape[0], block)],
            axis=-1) / arch.logits_scaling


def _experts(m, mlp, arch: Arch, round_to, theirs=None, band: float = 0.0):
    """The shared MLP and the held experts' part, one expert at a time
    over the rows routed to it (chosen on the host: plain). Returns the
    layer's result and its routing facts."""
    count = mlp["w_gate"].shape[0]
    first = arch.held[0] if arch.held is not None else 0
    w, chosen, misfit = _route(
        m, mlp["router"],
        None if theirs is None else jnp.asarray(theirs, jnp.int32),
        arch=arch, band=band, round_to=round_to)
    chosen_h, w_h = np.asarray(chosen), np.asarray(w)
    out = _gated(m, mlp["shared_gate"], mlp["shared_up"],
                 mlp["shared_down"], round_to=round_to)
    experts = {k: mlp[k] for k in ("w_gate", "w_up", "w_down")}
    held_pairs = 0
    for e in range(count):
        rows, slot = np.nonzero(chosen_h == first + e)
        if not len(rows):
            continue
        held_pairs += len(rows)
        pad = -len(rows) % 256
        out = _add_expert(
            out, m, np.pad(rows, (0, pad)).astype(np.int32),
            np.pad(w_h[rows, slot], (0, pad)).astype(np.float32), experts,
            np.int32(e), round_to=round_to)
    facts = {"misfit": np.asarray(misfit), "held_pairs": held_pairs,
             "pairs": int(chosen_h.size), "chosen": chosen_h}
    return out, facts


def forward(params, tokens, rows, arch: Arch, *, choice=None,
            band: float = 0.0, round_to=None, faults: Sequence[str] = (),
            row_block: int = 128):
    """Float32 logits (len(rows), vocab) of ONE sequence ``tokens``
    (1-D) at the positions ``rows``, and the routing facts of every
    layer (``chosen``; ``misfit`` a row, ``_route``'s; ``held_pairs`` /
    ``pairs``). ``choice`` holds, for each layer in order, the
    program's chosen experts (len(tokens), k) over the same tokens."""
    faults = tuple(sorted(faults))
    if set(faults) - set(FAULTS):
        raise ValueError(f"unknown faults {faults}; known: {FAULTS}")
    p = params["params"]
    tokens = jnp.asarray(tokens, jnp.int32)
    n = tokens.shape[0]
    positions = jnp.arange(n, dtype=jnp.int32)
    x = _cast(_cast(p["embedding"][tokens], round_to)
              * arch.embedding_multiplier, round_to)
    mult = 1.0 if "residual_one" in faults else arch.residual_multiplier
    scale = (arch.head_dim ** -0.5 if "sqrt_scale" in faults
             else arch.attention_multiplier)
    routing = []
    for i, kind in enumerate(arch.layers):
        layer = p[f"layer_{i}"]
        if kind == "mamba":
            a = _mamba(x, layer, arch=arch, round_to=round_to, faults=faults)
        else:
            q, k, v = _qkv(x, layer, arch=arch, round_to=round_to)
            o = jnp.concatenate([
                _attend(q[a:a + row_block], k, v, positions[a:a + row_block],
                        positions, scale=scale, round_to=round_to)
                for a in range(0, n, row_block)])
            a = _project(o, layer["attention"]["proj"], round_to=round_to)
        y, m = _add_mixer(x, a, layer, arch=arch, round_to=round_to,
                          mult=mult)
        theirs = None if choice is None else choice[i]
        out, facts = _experts(m, layer["mlp"], arch, round_to, theirs,
                              float(band))
        routing.append(facts)
        x = _cast(y + mult * out, round_to)
    logits = _head(x[jnp.asarray(rows)], p["final_norm"], p["embedding"],
                   arch=arch, round_to=round_to)
    return logits, routing


def check_served(params, arch: Arch, prompt, served, *, ulps: float,
                 dtype_eps: float, choice=None, band: float = 0.0,
                 slack: float = 0.0, pad_to: int = 128, round_to=None,
                 faults: Sequence[str] = (), program_logits=None,
                 logit_limit: float = float("inf")) -> Dict[str, Any]:
    """:func:`judge` of a served sequence, teacher-forced through the
    reference: ``decoder_reference.check_served``'s rule over this
    block's forward pass.

    ``choice`` is what the program's own router gave in a pass of its
    own over :func:`teacher_forced`'s tokens: for each layer ``(ids
    (n, k), probabilities (n, experts))``. The reference follows the
    ids within ``band``. That pass is not the served one (no cache, no
    slots, one chunk): where a row's :func:`held_margin` by the
    *program's* log probabilities is under ``slack`` at some layer,
    the served step may have chosen otherwise than the pass did, and
    such a row is excused if it trails by more than ``ulps`` (no other
    row is). A row of the sequence, prompt or served, where the
    program's choice lies further than ``band`` outside the
    reference's is ``refused``, and the sequence is not ok. ``band``
    and ``slack`` are differences of log probabilities.

    ``program_logits`` (len(served), vocab) are the program's own
    logits of the rows that decided the served tokens (kept from the
    dispatches that served them). Then the sequence
    is ok only if, at every row held to the reference (not marked as
    one that may differ), they leave the reference's by no more than
    ``logit_limit`` of the reference's largest logit in that row
    (``logit_error`` the worst such row's, ``logit_rms`` the root mean
    square over those rows): a token's argmax need not show an error
    that the state carries."""
    toks, rows = teacher_forced(prompt, served, pad_to)
    logits, routing = forward(
        params, toks, rows, arch, band=band, round_to=round_to,
        faults=faults,
        choice=None if choice is None else [ids for ids, _ in choice])
    n, last = len(rows), rows[-1] + 1           # padding rows left out
    misfit = np.max([f["misfit"][:last] for f in routing], axis=0)
    refused = misfit > band
    margin = np.full(n, np.inf)
    for _, probs in choice or ():
        log_p = np.log(np.asarray(probs)[rows])
        margin = np.minimum(margin, held_margin(
            log_p, arch.top_k, arch.held or (0, log_p.shape[1])))
    out = judge(logits, served, margin < slack, ulps=ulps,
                dtype_eps=dtype_eps)
    out.update(logit_error=0.0, logit_worst_row=0, logit_rms=0.0)
    if program_logits is not None:
        off = np.asarray(
            jnp.abs(jnp.asarray(program_logits, F32) - logits).max(-1)
            / jnp.abs(logits).max(-1))
        held_to = margin >= slack
        off = np.where(held_to, off, 0.0)
        out.update(logit_error=float(off.max()),
                   logit_worst_row=int(off.argmax()),
                   logit_rms=float(np.sqrt((off ** 2).sum()
                                           / max(held_to.sum(), 1))),
                   logit_error_by_row=off,
                   ok=out["ok"] and bool(off.max() <= logit_limit))
    out.update(ok=out["ok"] and not refused.any(),
               followed=int(((misfit > 0) & ~refused).sum()),
               refused=int(refused.sum()), worst_misfit=float(misfit.max()),
               misfit_by_layer=[f["misfit"][:last] for f in routing],
               program_margin=margin,
               held_pairs=sum(f["held_pairs"] for f in routing),
               pairs=sum(f["pairs"] for f in routing))
    return out
