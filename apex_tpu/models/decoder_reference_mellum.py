"""The plain reference of the ``mellum`` block as ``models/decoder.py``
runs it (JetBrains Mellum2): the forward pass in straightforward
float32 ``jax.numpy``, with no kernel, cache, scan or batching.

    x0 = E[tokens]  (no scale);  logits = n_f(x_L) @ W_head^T  (untied)
    layer:  a = n_1(x);  y = x + Attn(a);  m = n_2(y);
            x' = y + MoE(m)                         (pre-norm)
    n(x) = x * rsqrt(mean(x^2) + eps) * g           (float32)
    Attn:   q, k, v = a W;  q <- n_q(q), k <- n_k(k) per head;
            rotary embedding on q and k by KIND of layer (whole head,
            rotate-half):
              window layers: inv_freq_i = theta^(-2i/d); query t sees
                keys t - window < j <= t;
              full layers (YaRN): ext_i = theta^(-2i/d), int_i = ext_i
                / factor; c(r) = d ln(original / (2 pi r)) / (2 ln
                theta); low = max(floor(c(beta_fast)), 0), high =
                min(ceil(c(beta_slow)), d - 1); ramp_i = clip((i - low)
                / (high - low), 0, 1); inv_freq_i = int_i ramp_i +
                ext_i (1 - ramp_i); cos and sin times attention_factor;
                every key j <= t;
            scores * d^-0.5, softmax, GQA;  o W_o   (no gate, no bias)
    MoE:    p = softmax(m W_r) over ALL the experts; chosen = top-k of
            p; w = p[chosen] / sum p[chosen];
            sum_e w_e (silu(m W_gate,e) * (m W_up,e)) W_down,e

Given ``held = (first, count)`` it leaves out what the experts outside
that range would add, as the program does. Not on the forward path and
left out: any load-balancing loss, the multi-token-prediction head the
model's card speaks of (``config.json`` has no key for one).

It reads the program's parameter tree (the weights are the thing
compared) and nothing else of the program. Departures from the
published layout, none of them mathematics: the three attention
projections are stored as one matrix ``[q | k | v]``, kernels are
stored ``(in, out)``, a layer's experts are stacked and only the held
ones are stored.

The parts that are no model's own (a norm, attention of a block of
query rows, a gated MLP, one expert's rows, the judgement of served
tokens) are ``decoder_reference``'s, imported. Every product runs
under ``jax.default_matmul_precision("highest")``; one layer's weights
are upcast at a time, one expert's at a time, attention runs in blocks
of query rows and the head in blocks of vocabulary rows, so the
reference fits beside a server that holds the bf16 model.

As ``decoder_reference``, it can be *given the program's choice* of
experts (``forward(choice=...)``): where that choice differs from its
own only among experts whose probabilities, as the reference computes
them, lie within ``band`` of the cut, it follows the program; anywhere
else it keeps its own, and a program that chose wrongly shows.

``round_to`` and ``faults`` are the controls (``tests/test_decoder.py``,
and on the chip ``benchmark/controls_mellum.py``): ``round_to`` rounds
every weight and every product through a lower type, and a fault
computes a *wrong* model (plain rotary on the full layers, YaRN on the
window layers, YaRN without its ``attention_factor``, a top-k that is
not renormalised, a window one key too wide, the router's product on
bf16 inputs), which the comparison has to refuse.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.models.decoder_reference import (
    _add_expert, _attend, _cast, _rms, held_margin, judge, teacher_forced)

F32 = jnp.float32
FAULTS = ("plain_rope_on_full", "yarn_on_window", "no_attention_factor",
          "no_renormalisation", "window_edge", "bf16_router")


class Yarn(NamedTuple):
    """``rope_parameters.full_attention``'s six numbers."""

    theta: float
    factor: float
    original: int                     # original_max_position_embeddings
    beta_fast: float
    beta_slow: float
    attention_factor: float


class Arch(NamedTuple):
    """What the reference needs beside the weights."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    layers: Tuple[Tuple[str, str], ...]      # (attention kind, "experts")
    window: int
    top_k: int
    theta: float                             # the window layers' rotary
    yarn: Yarn                               # the full layers'
    held: Optional[Tuple[int, int]] = None   # (first, count); None = all
    eps: float = 1e-6


def inv_freq(d: int, theta: float) -> np.ndarray:
    return theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)


def yarn_inv_freq(d: int, yarn: Yarn) -> np.ndarray:
    """YaRN's blend (module docstring), ``truncate`` true."""
    ext = inv_freq(d, yarn.theta)

    def c(r):
        return (d * math.log(yarn.original / (2 * math.pi * r))
                / (2 * math.log(yarn.theta)))

    low = max(math.floor(c(yarn.beta_fast)), 0)
    high = min(math.ceil(c(yarn.beta_slow)), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return ext / yarn.factor * ramp + ext * (1.0 - ramp)


def _rotary(t, positions, freq, factor):
    d = t.shape[-1]
    ang = positions.astype(F32)[:, None] * jnp.asarray(freq, F32)
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))[:, None, :] * factor
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))[:, None, :] * factor
    t1, t2 = t[..., :d // 2], t[..., d // 2:]
    return t * cos + jnp.concatenate([-t2, t1], -1) * sin


@functools.partial(jax.jit, static_argnames=("arch", "rope", "round_to"))
def _qkv(x, p, positions, *, arch: Arch, rope: str, round_to):
    """``rope``: "plain", "yarn", or "yarn_unscaled" (YaRN's
    frequencies without its factor on cos and sin: a fault)."""
    with jax.default_matmul_precision("highest"):
        r = functools.partial(_cast, round_to=round_to)
        nh, nkv, d = arch.num_heads, arch.num_kv_heads, arch.head_dim
        a = r(_rms(x, r(p["input_norm"]["scale"]), arch.eps))
        att = p["attention"]
        q, k, v = jnp.split(r(a @ r(att["qkv"])),
                            [nh * d, (nh + nkv) * d], axis=-1)
        n = x.shape[0]
        q = _rms(q.reshape(n, nh, d), r(att["q_norm"]["scale"]), arch.eps)
        k = _rms(k.reshape(n, nkv, d), r(att["k_norm"]["scale"]), arch.eps)
        if rope == "plain":
            freq, factor = inv_freq(d, arch.theta), 1.0
        else:
            freq = yarn_inv_freq(d, arch.yarn)
            factor = arch.yarn.attention_factor if rope == "yarn" else 1.0
        q = _rotary(q, positions, freq, factor)
        k = _rotary(k, positions, freq, factor)
        return r(q), r(k), r(v.reshape(n, nkv, d))


@functools.partial(jax.jit, static_argnames=("arch", "round_to"))
def _after_attention(x, o, p, *, arch: Arch, round_to):
    """``y = x + o W_o`` and ``m = n_2(y)``."""
    with jax.default_matmul_precision("highest"):
        r = functools.partial(_cast, round_to=round_to)
        y = r(x + r(o @ r(p["attention"]["proj"])))
        return y, r(_rms(y, r(p["pre_mlp_norm"]["scale"]), arch.eps))


@functools.partial(jax.jit,
                   static_argnames=("arch", "band", "round_to", "faults"))
def _route(m, router, theirs, *, arch: Arch, band, round_to, faults):
    """(weights (n, k), chosen (n, k), misfit (n,)).

    ``theirs`` (n, k) is the program's choice, or None. Its ``misfit``
    is how far outside the reference's own choice it lies, in LOG
    probability (what the served type's rounding of the router's input
    moves evenly, whatever the row's probabilities are): the
    reference's k-th less the least one the program took, or the
    largest one the program left less the reference's (k+1)-th,
    whichever is more; 0 or less where the two choices are one. Up to
    ``band`` the reference takes the program's choice; past it the
    reference keeps its own."""
    with jax.default_matmul_precision("highest"):
        k = arch.top_k
        router = _cast(router, round_to)
        if "bf16_router" in faults:      # the product's inputs in bf16
            m, router = _cast(m, jnp.bfloat16), _cast(router, jnp.bfloat16)
        log_p = jax.nn.log_softmax(m @ router, axis=-1)
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
        if "no_renormalisation" not in faults:
            w = w / w.sum(-1, keepdims=True)
        return w, chosen, misfit


@functools.partial(jax.jit, static_argnames=("arch", "round_to", "block"))
def _head(x, norm, head, *, arch: Arch, round_to, block: int = 16384):
    """The head a block of vocabulary rows at a time: one block's
    float32 copy is alive, not the whole matrix's."""
    with jax.default_matmul_precision("highest"):
        r = functools.partial(_cast, round_to=round_to)
        x = r(_rms(x, r(norm["scale"]), arch.eps))
        return jnp.concatenate(
            [x @ r(head[a:a + block]).T
             for a in range(0, head.shape[0], block)], axis=-1)


def _experts(m, mlp, arch: Arch, round_to, faults, theirs=None,
             band: float = 0.0):
    """The held experts' part, one expert at a time over the rows
    routed to it (chosen on the host: plain, and an expert sees one
    row in eight). Returns the layer's result and its routing facts."""
    count = mlp["w_gate"].shape[0]
    first = arch.held[0] if arch.held is not None else 0
    w, chosen, misfit = _route(
        m, mlp["router"],
        None if theirs is None else jnp.asarray(theirs, jnp.int32),
        arch=arch, band=band, round_to=round_to, faults=faults)
    chosen_h, w_h = np.asarray(chosen), np.asarray(w)
    out = jnp.zeros_like(m)
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
    x = _cast(p["embedding"][tokens], round_to)
    yarn = "yarn_unscaled" if "no_attention_factor" in faults else "yarn"
    rope = {"window": yarn if "yarn_on_window" in faults else "plain",
            "full": "plain" if "plain_rope_on_full" in faults else yarn}
    routing = []
    for i, (attention, _) in enumerate(arch.layers):
        layer = p[f"layer_{i}"]
        window = arch.window if attention == "window" else None
        if window is not None and "window_edge" in faults:
            window += 1
        q, k, v = _qkv(x, layer, positions, arch=arch, round_to=round_to,
                       rope=rope[attention])
        o = jnp.concatenate([
            _attend(q[a:a + row_block], k, v, positions[a:a + row_block],
                    positions, window=window, round_to=round_to)
            for a in range(0, n, row_block)])
        y, m = _after_attention(x, o, layer, arch=arch, round_to=round_to)
        theirs = None if choice is None else choice[len(routing)]
        out, facts = _experts(m, layer["mlp"], arch, round_to, faults,
                              theirs, float(band))
        routing.append(facts)
        x = _cast(y + out, round_to)
    logits = _head(x[jnp.asarray(rows)], p["final_norm"], p["head"],
                   arch=arch, round_to=round_to)
    return logits, routing


def check_served(params, arch: Arch, prompt, served, *, ulps: float,
                 dtype_eps: float, choice=None, band: float = 0.0,
                 slack: float = 0.0, pad_to: int = 128, round_to=None,
                 faults: Sequence[str] = ()) -> Dict[str, Any]:
    """:func:`judge` of a served sequence, teacher-forced through the
    reference: ``decoder_reference.check_served``'s rule over this
    block's forward pass.

    ``choice`` is what the program's own router gave in a pass of its
    own over :func:`teacher_forced`'s tokens: for each layer ``(ids
    (n, k), probabilities (n, experts))``. The reference follows the
    ids within ``band``. That pass is not the served one (no cache,
    other kernels' blocks): where a row's :func:`held_margin` by the
    *program's* log probabilities is under ``slack`` at some layer,
    the served step may have chosen otherwise than the pass did, and
    such a row is excused if it trails by more than ``ulps`` (no other
    row is). A row of the sequence, prompt or served, where the
    program's choice lies further than ``band`` outside the
    reference's is ``refused``, and the sequence is not ok. ``band``
    and ``slack`` are differences of log probabilities."""
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
    out.update(ok=out["ok"] and not refused.any(),
               followed=int(((misfit > 0) & ~refused).sum()),
               refused=int(refused.sum()), worst_misfit=float(misfit.max()),
               misfit_by_layer=[f["misfit"][:last] for f in routing],
               program_margin=margin,
               held_pairs=sum(f["held_pairs"] for f in routing),
               pairs=sum(f["pairs"] for f in routing))
    return out
