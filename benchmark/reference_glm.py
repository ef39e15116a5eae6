"""The plain reference of the ``glm4_moe_lite`` block as
``models/decoder.py`` runs it (GLM-4.7-Flash): the forward pass in
straightforward float32 ``jax.numpy``, with no kernel, cache, chunked
form or batching. Attention is the *expanded* form, as the published
description (DeepSeek-V2/V3's multi-head latent attention, which
``glm4_moe_lite`` takes over) writes it.

    x0 = E[tokens];   logits = n_f(x_L) W_head^T            (untied)
    layer:  y = x + Attn(n_1(x));  m = n_2(y);  x' = y + MLP(m)
    n(x) = x * rsqrt(mean(x^2) + eps) * g                   (float32)
    Attn (per head h; H heads):
      c_q = n_q(u W_DQ);  [q_nope_h | q_rope_h] = c_q W_UQ,h
      [c_kv | k_r] = u W_DKV;  c_kv <- n_kv(c_kv)
      [k_nope_h | v_h] = c_kv W_UKV,h
      q_rope_h, k_r rotated at the token's position, theta, pairs of
        ADJACENT dimensions (2i, 2i + 1) turned by theta^(-2i / d_rope);
        k_r is one key shared by the H heads
      scores_h = (q_nope_h . k_nope_h + q_rope_h . k_r)
                 (d_nope + d_rope)^-0.5, causal, every key
      Attn = [softmax(scores_h) v_h]_h W_O
    MLP of the leading dense layers: (silu(m W_gate) * (m W_up)) W_down
    MLP of the expert layers: s = sigmoid(m W_r) over ALL the experts;
      chosen = top-k of s + b;  w = s[chosen] / sum s[chosen] * scale;
      Shared(m) + sum_e w_e Expert_e(m)

Given ``held = (first, count)`` it leaves out what the experts outside
that range would add, as the program does; the shared expert is whole.
Not on the forward path and left out: the multi-token-prediction
module (a draft head for speculative decoding, which the engine does
not serve), the load-balancing loss and the bias's update rule.
Assumed, where ``config.json`` does not say: the rotary pairs
interleaved (DeepSeek-V3's layout), the router's ``noaux_tc`` scoring
sigmoid with one group.

It reads the program's parameter tree (the weights are the thing
compared) and nothing else of the program. Departures from the
published layout, none of them mathematics: kernels are stored ``(in,
out)``, a layer's experts are stacked and only the held ones are
stored.

The parts that are no model's own (a norm, a gated MLP, the sigmoid
router and one expert's rows, the judgement of served tokens) are
``decoder_reference``'s, imported. Every product runs under
``jax.default_matmul_precision("highest")``; one layer's weights are
upcast at a time, one expert's at a time, attention runs in blocks of
query rows and the head in blocks of vocabulary rows, so the reference
fits beside a server that holds the bf16 model.

As ``decoder_reference``, it can be *given the program's choice* of
experts (``forward(choice=...)``): where that choice differs from its
own only among experts whose biased scores, as the reference computes
them, lie within ``band`` of the cut, it follows the program; anywhere
else it keeps its own, and a program that chose wrongly shows.

``round_to`` and ``faults`` are the controls (``tests/test_decoder.py``,
and on the chip ``benchmark/controls_glm.py``): ``round_to`` rounds
every weight and every product through a lower type, and a fault
computes a *wrong* model (``c_kv`` without its norm, rotary over the
rotate-half layout, scores scaled by ``d_nope^-0.5``, the routing
weights without their scale, the latents carried in fp8), which the
comparison has to refuse.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference_trinity import (
    _add_expert, _cast, _gated, _rms, _route, held_margin, judge,
    teacher_forced)

F32 = jnp.float32
FAULTS = ("no_kv_norm", "rope_layout", "scale_nope", "no_route_scale",
          "fp8_latent")
#: the rows of one held expert a call: every call is the one program
ROWS = 256
#: the most rows of a request that ``check_served`` judges again under
#: another choice of experts; a request with more rows astray is refused
ASTRAY_TRIES = 4


class Arch(NamedTuple):
    """What the reference needs beside the weights."""

    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    layers: Tuple[str, ...]                  # "dense" | "experts" a layer
    top_k: int
    route_scale: float
    held: Optional[Tuple[int, int]] = None   # (first, count); None = all
    eps: float = 1e-5
    theta: float = 10000.0


def _rotary(t, positions, theta, *, interleaved: bool):
    """``t`` (n, ..., d) at ``positions`` (n,): pair ``i`` is dims
    ``(2i, 2i + 1)`` where ``interleaved``, else ``(i, i + d / 2)``,
    turned by ``positions * theta^(-2i / d)``."""
    d = t.shape[-1]
    ang = positions.astype(F32)[:, None] * theta ** (
        -jnp.arange(0, d, 2, dtype=F32) / d)
    ang = ang.reshape(ang.shape[:1] + (1,) * (t.ndim - 2) + ang.shape[1:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if interleaved:
        a, b = t[..., 0::2], t[..., 1::2]
        return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                         axis=-1).reshape(t.shape)
    a, b = t[..., :d // 2], t[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("arch", "round_to", "faults"))
def _latent(x, p, positions, *, arch: Arch, round_to, faults):
    """One layer's queries, keys and values over ONE sequence ``x``
    (n, hidden): ``q_nope`` (n, H, d_nope), ``q_rope`` (n, H, d_rope),
    ``k_nope`` (n, H, d_nope), ``k_r`` (n, d_rope), ``v`` (n, H, d_v)."""
    with jax.default_matmul_precision("highest"):
        r = functools.partial(_cast, round_to=round_to)
        nh, lat = arch.num_heads, arch.kv_lora_rank
        nope, rope = arch.qk_nope_head_dim, arch.qk_rope_head_dim
        att = p["attention"]
        n = x.shape[0]
        u = r(_rms(x, r(p["input_norm"]["scale"]), arch.eps))
        c_q = r(_rms(r(u @ r(att["q_down"])), r(att["q_norm"]["scale"]),
                     arch.eps))
        q = r(c_q @ r(att["q_up"])).reshape(n, nh, nope + rope)
        ckr = r(u @ r(att["kv_down"]))
        c_kv, k_r = ckr[:, :lat], ckr[:, lat:]
        if "no_kv_norm" not in faults:
            c_kv = r(_rms(c_kv, r(att["kv_norm"]["scale"]), arch.eps))
        interleaved = "rope_layout" not in faults
        q_rope = r(_rotary(q[..., nope:], positions, arch.theta,
                           interleaved=interleaved))
        k_r = r(_rotary(k_r, positions, arch.theta, interleaved=interleaved))
        if "fp8_latent" in faults:       # what the cache would hold
            c_kv = _cast(c_kv, jnp.float8_e4m3fn)
            k_r = _cast(k_r, jnp.float8_e4m3fn)
        kv = r(c_kv @ r(att["kv_up"])).reshape(n, nh, nope + arch.v_head_dim)
        return q[..., :nope], q_rope, kv[..., :nope], k_r, kv[..., nope:]


def _experts(m, mlp, arch: Arch, round_to, theirs=None,
             band: float = 0.0):
    """The held experts' part and the shared expert, as the shared
    ``_experts`` computes it (the rows routed to a held expert chosen
    on the host, their products added back in float32), but ``ROWS``
    rows a call, so that every call is one program whatever an
    expert's count: on a v5e that program at 512 rows (one expert of
    GLM's widths given 366 rows of a 1536-token sequence) never
    returned, where at 256 it returns for every expert. Returns the
    MLP's result and the layer's routing facts."""
    first = arch.held[0] if arch.held is not None else 0
    w, chosen, misfit = _route(
        m, mlp["router"], mlp["select_bias"],
        None if theirs is None else jnp.asarray(theirs, jnp.int32),
        arch=arch, band=band, round_to=round_to, faults=())
    chosen_h, w_h = np.asarray(chosen), np.asarray(w)
    out = _gated(m, mlp["shared_gate"], mlp["shared_up"],
                 mlp["shared_down"], round_to=round_to)
    experts = {k: mlp[k] for k in ("w_gate", "w_up", "w_down")}
    held_pairs = 0
    for e in range(mlp["w_gate"].shape[0]):
        rows, slot = np.nonzero(chosen_h == first + e)
        held_pairs += len(rows)
        for a in range(0, len(rows), ROWS):
            idx, at = rows[a:a + ROWS], slot[a:a + ROWS]
            pad = ROWS - len(idx)      # row 0 at weight 0
            out = _add_expert(
                out, m, np.pad(idx, (0, pad)).astype(np.int32),
                np.pad(w_h[idx, at], (0, pad)).astype(np.float32), experts,
                np.int32(e), round_to=round_to)
    return out, {"misfit": np.asarray(misfit), "held_pairs": held_pairs,
                 "pairs": int(chosen_h.size), "chosen": chosen_h}


@functools.partial(jax.jit, static_argnames=("scale", "round_to"))
def _attend(q_nope, q_rope, k_nope, k_r, v, q_pos, k_pos, *, scale: float,
            round_to):
    """Query rows (rows, H, ...) against every key (n, ...) at or
    before their own positions."""
    with jax.default_matmul_precision("highest"):
        rows, nh, _ = q_nope.shape
        s = (jnp.einsum("rhd,nhd->hrn", q_nope, k_nope)
             + jnp.einsum("rhd,nd->hrn", q_rope, k_r)) * scale
        see = k_pos[None, :] <= q_pos[:, None]
        p = jax.nn.softmax(jnp.where(see, s, -jnp.inf), axis=-1)
        o = jnp.einsum("hrn,nhd->rhd", p, v).reshape(rows, -1)
        return _cast(o, round_to)


@functools.partial(jax.jit, static_argnames=("arch", "round_to"))
def _after_attention(x, o, p, *, arch: Arch, round_to):
    """``y = x + o W_O`` and ``m = n_2(y)``."""
    with jax.default_matmul_precision("highest"):
        r = functools.partial(_cast, round_to=round_to)
        y = r(x + r(o @ r(p["attention"]["proj"])))
        return y, r(_rms(y, r(p["pre_mlp_norm"]["scale"]), arch.eps))


@functools.partial(jax.jit, static_argnames=("arch", "round_to", "block"))
def _head(x, norm, head, *, arch: Arch, round_to, block: int = 16384):
    """The untied head a block of vocabulary rows at a time: one
    block's float32 copy is alive, not the whole matrix's."""
    with jax.default_matmul_precision("highest"):
        r = functools.partial(_cast, round_to=round_to)
        x = r(_rms(x, r(norm["scale"]), arch.eps))
        return jnp.concatenate(
            [x @ r(head[a:a + block]).T
             for a in range(0, head.shape[0], block)], axis=-1)


def forward(params, tokens, rows, arch: Arch, *, choice=None,
            band: float = 0.0, round_to=None, faults: Sequence[str] = (),
            row_block: int = 128):
    """Float32 logits (len(rows), vocab) of ONE sequence ``tokens``
    (1-D) at the positions ``rows``, and the routing facts of every
    expert layer (``chosen``; ``misfit`` a row, ``decoder_reference``'s
    ``_route``; ``held_pairs`` / ``pairs``). ``choice`` holds, for
    each expert layer in order, the program's chosen experts
    (len(tokens), k) over the same tokens."""
    faults = tuple(sorted(faults))
    if set(faults) - set(FAULTS):
        raise ValueError(f"unknown faults {faults}; known: {FAULTS}")
    p = params["params"]
    tokens = jnp.asarray(tokens, jnp.int32)
    n = tokens.shape[0]
    positions = jnp.arange(n, dtype=jnp.int32)
    x = _cast(p["embedding"][tokens], round_to)
    d_qk = arch.qk_nope_head_dim + (
        0 if "scale_nope" in faults else arch.qk_rope_head_dim)
    routed = arch._replace(route_scale=1.0) if "no_route_scale" in faults \
        else arch
    routing = []
    for i, kind in enumerate(arch.layers):
        layer = p[f"layer_{i}"]
        q_nope, q_rope, k_nope, k_r, v = _latent(
            x, layer, positions, arch=arch, round_to=round_to, faults=faults)
        o = jnp.concatenate([
            _attend(q_nope[a:a + row_block], q_rope[a:a + row_block], k_nope,
                    k_r, v, positions[a:a + row_block], positions,
                    scale=d_qk ** -0.5, round_to=round_to)
            for a in range(0, n, row_block)])
        del q_nope, q_rope, k_nope, k_r, v
        y, m = _after_attention(x, o, layer, arch=arch, round_to=round_to)
        mlp = layer["mlp"]
        if kind == "experts":
            theirs = None if choice is None else choice[len(routing)]
            out, facts = _experts(m, mlp, routed, round_to, theirs,
                                  float(band))
            routing.append(facts)
        else:
            out = _gated(m, mlp["gate"], mlp["up"], mlp["down"],
                         round_to=round_to)
        x = _cast(y + out, round_to)
    logits = _head(x[jnp.asarray(rows)], p["final_norm"], p["head"],
                   arch=arch, round_to=round_to)
    return logits, routing


def nearest_flip(ids, biased, k: int, held: Tuple[int, int]):
    """The choice one step from ``ids`` (k,) that changes what the held
    experts add, by one row's biased scores (experts,): a chosen held
    expert dropped for the first one left out, or an unchosen held one
    taken for the last one chosen, whichever lies nearer the cut. Returns
    that choice and its distance from the cut (``held_margin``'s)."""
    b = np.asarray(biased, np.float32)
    ids = np.asarray(ids)
    order = np.argsort(-b)
    last, left_out = order[k - 1], order[k]
    best, flip = np.inf, ids
    for e in range(held[0], held[0] + held[1]):
        if e in ids:
            m, other = b[e] - b[left_out], np.where(ids == e, left_out, ids)
        else:
            m, other = b[last] - b[e], np.where(ids == last, e, ids)
        if m < best:
            best, flip = m, other
    return flip, float(best)


def check_served(params, arch: Arch, prompt, served, *, ulps: float,
                 dtype_eps: float, choice=None, band: float = 0.0,
                 slack: float = 0.0, pad_to: int = 128, round_to=None,
                 faults: Sequence[str] = (), program_logits=None,
                 logit_limit: float = float("inf")) -> Dict[str, Any]:
    """:func:`judge` of a served sequence, teacher-forced through the
    reference, and its logits held to the reference's.

    ``choice`` is what the program's own routers gave in a pass of its
    own over :func:`teacher_forced`'s tokens: for each expert layer
    ``(ids (n, k), biased scores (n, experts))``. The reference follows
    the ids within ``band``. That pass is not the served one (no cache,
    one chunk): where a row's :func:`held_margin` by the *program's*
    scores is under ``slack`` at some layer, the served step may have
    chosen otherwise than the pass did, and such a row is excused if it
    trails by more than ``ulps`` (no other row is). A row of the
    sequence, prompt or served, where the program's choice lies further
    than ``band`` outside the reference's is ``refused``, and the
    sequence is not ok.

    ``program_logits`` (len(served), vocab) are the program's own
    logits of the rows that decided the served tokens (kept from the
    dispatches that served them). Then the sequence is ok only if, at
    every row held to the reference, they leave the reference's by no
    more than ``logit_limit`` of the reference's largest logit in that
    row (``logit_error`` the worst such row's, ``logit_rms`` the root
    mean square over those rows).

    A row held to the reference that fails either limit is *astray*.
    The served step may have chosen otherwise than the pass at a margin
    over ``slack`` too: each astray row, where there are at most
    ``ASTRAY_TRIES``, is judged again with the program's choice at that
    row changed to :func:`nearest_flip`'s, one expert layer at a time
    where its distance from the cut is under ``band`` (so the reference
    follows it only where its own scores put it within ``band`` as
    well). A row that then holds to both limits is ``swapped`` (row,
    layer, distance) and counted among the ``excused``; any other
    astray row fails the sequence."""
    toks, rows = teacher_forced(prompt, served, pad_to)
    logits, routing = forward(
        params, toks, rows, arch, band=band, round_to=round_to,
        faults=faults,
        choice=None if choice is None else [ids for ids, _ in choice])
    n, last = len(rows), rows[-1] + 1           # padding rows left out
    misfit = np.max([f["misfit"][:last] for f in routing], axis=0)
    refused = misfit > band
    margin = np.full(n, np.inf)
    for _, biased in choice or ():
        biased = np.asarray(biased)[rows]
        margin = np.minimum(margin, held_margin(
            biased, arch.top_k, arch.held or (0, biased.shape[1])))
    out = judge(logits, served, margin < slack, ulps=ulps,
                dtype_eps=dtype_eps)
    out.update(logit_error=0.0, logit_worst_row=0, logit_rms=0.0, swapped=[])
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
        astray = held_to & ((off > logit_limit) | (out["ulps_by_row"] > ulps))
        swapped = []
        if choice is not None and 0 < astray.sum() <= ASTRAY_TRIES:
            ids_by_layer = [np.asarray(ids) for ids, _ in choice]
            for r in np.nonzero(astray)[0]:
                at = rows[r]
                for j, (ids, biased) in enumerate(choice):
                    biased = np.asarray(biased)[at]
                    flip, m = nearest_flip(
                        ids[at], biased, arch.top_k,
                        arch.held or (0, biased.shape[0]))
                    if m >= band:
                        continue
                    tried = [a.copy() for a in ids_by_layer]
                    tried[j][at] = flip
                    again = forward(params, toks, rows[r:r + 1], arch,
                                    band=band, round_to=round_to,
                                    faults=faults, choice=tried)[0][0]
                    err = float(jnp.abs(jnp.asarray(program_logits[r], F32)
                                        - again).max()
                                / jnp.abs(again).max())
                    trail = judge(again[None], served[r:r + 1], [False],
                                  ulps=ulps, dtype_eps=dtype_eps)
                    if err <= logit_limit and trail["ok"]:
                        swapped.append((int(r), j, m))
                        break
        if swapped:
            mine = np.zeros(n, bool)
            mine[[r for r, _, _ in swapped]] = True
            trails = np.where(
                mine | ((out["ulps_by_row"] > ulps) & (margin < slack)), 0.0,
                out["ulps_by_row"])
            off = np.where(mine, 0.0, off)
            out.update(
                ok=bool(trails.max() <= ulps and off.max() <= logit_limit),
                worst_ulps=float(trails.max()),
                worst_row=int(trails.argmax()),
                excused=out["excused"] + len(swapped),
                logit_error=float(off.max()),
                logit_worst_row=int(off.argmax()),
                logit_rms=float(np.sqrt((off ** 2).sum()
                                        / max((held_to & ~mine).sum(), 1))),
                logit_error_by_row=off)
        out.update(swapped=swapped)
    out.update(ok=out["ok"] and not refused.any(),
               followed=int(((misfit > 0) & ~refused).sum()),
               refused=int(refused.sum()), worst_misfit=float(misfit.max()),
               misfit_by_layer=[f["misfit"][:last] for f in routing],
               program_margin=margin,
               held_pairs=sum(f["held_pairs"] for f in routing),
               pairs=sum(f["pairs"] for f in routing))
    return out
