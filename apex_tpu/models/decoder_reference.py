"""The plain reference of ``models/decoder.py``: the ``afmoe`` forward
pass (Arcee Trinity) in straightforward float32 ``jax.numpy``, with no
kernel, cache, scan or batching.

    x0 = E[tokens] * sqrt(h);  logits = n_f(x_L) @ W_head^T   (untied)
    layer:  a = n_1(x);  y = x + n_2(Attn(a));  m = n_3(y);
            x' = y + n_4(MLP(m))                    (sandwich norm)
    n(x) = x * rsqrt(mean(x^2) + eps) * g           (float32)
    Attn:   q, k, v, g = a W;  q <- n_q(q), k <- n_k(k) per head;
            window layers: rotary embedding on q and k (theta, whole
            head, rotate-half), query t sees keys t - window < j <= t;
            full layers: no positional embedding, every key j <= t;
            scores * d^-0.5, softmax, GQA;  (o * sigmoid(g)) W_o
    dense:  (silu(m W_gate) * (m W_up)) W_down
    experts: s = sigmoid(m W_r);  chosen = top-k of s + b;
            w = s[chosen] / (sum s[chosen] + 1e-20) * route_scale;
            Shared(m) + sum_e w_e Expert_e(m)

Given ``held = (first, count)`` it leaves out what the experts outside
that range would add, as the program does. Not on the forward path and
left out: the load-balancing loss, the selection bias's update rule,
the depth scaling of the norm gains (an initialisation).

It reads the program's parameter tree (the weights are the thing
compared) and nothing else of the program. Departures from the
published layout, none of them mathematics: the four attention
projections are stored as one matrix ``[q | k | v | g]``, kernels are
stored ``(in, out)``, a layer's experts are stacked and only the held
ones are stored.

Every product runs under ``jax.default_matmul_precision("highest")``
(on a TPU a float32 product is otherwise done in bf16). One layer's
weights are upcast at a time, one expert's at a time, and attention
runs in blocks of query rows, so the reference fits beside a server
that holds the bf16 model.

Where two scores lie closer at the router's cut than the served
type's rounding of the router's input, program and reference choose
different experts and a row differs by a whole expert's output with
neither side wrong. So the reference can be *given the program's
choice* (``forward(choice=...)``): at a row and layer where that
choice differs from its own only among experts whose scores, as the
reference computes them, lie within ``band`` of the cut, it follows
the program; anywhere else it keeps its own, and a program that chose
wrongly shows.

``round_to`` and ``faults`` are the controls (``tests/test_decoder.py``,
and on the chip ``benchmark/controls_trinity.py``): ``round_to`` rounds
every weight and every product through a lower type, and a fault
computes a *wrong* model (a window one key too wide, rotary positions
on the full layers too, weights taken from ``s + b``, the choice made
without ``b``, the router's product on bf16 inputs), which the comparison has to refuse.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
FAULTS = ("window_edge", "rope_on_full", "weight_by_biased", "bf16_router",
          "choice_without_bias")


class Arch(NamedTuple):
    """What the reference needs beside the weights."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    layers: Tuple[Tuple[str, str], ...]      # (attention kind, MLP kind)
    window: Optional[int]
    top_k: int = 0
    route_scale: float = 1.0
    held: Optional[Tuple[int, int]] = None   # (first, count); None = all
    eps: float = 1e-5
    theta: float = 10000.0


def _cast(x, round_to):
    x = jnp.asarray(x)
    return (x if round_to is None else x.astype(round_to)).astype(F32)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * g


def _rotary(t, positions, theta):
    d = t.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = positions.astype(F32)[:, None] * inv_freq
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))[:, None, :]
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))[:, None, :]
    t1, t2 = t[..., :d // 2], t[..., d // 2:]
    return t * cos + jnp.concatenate([-t2, t1], -1) * sin


@functools.partial(jax.jit, static_argnames=("arch", "rope", "round_to"))
def _qkvg(x, p, positions, *, arch: Arch, rope: bool, round_to):
    with jax.default_matmul_precision("highest"):
        r = functools.partial(_cast, round_to=round_to)
        nh, nkv, d = arch.num_heads, arch.num_kv_heads, arch.head_dim
        a = r(_rms(x, r(p["input_norm"]["scale"]), arch.eps))
        att = p["attention"]
        qkvg = r(a @ r(att["qkvg"]))
        q, k, v, g = jnp.split(
            qkvg, [nh * d, (nh + nkv) * d, (nh + 2 * nkv) * d], axis=-1)
        n = x.shape[0]
        q = _rms(q.reshape(n, nh, d), r(att["q_norm"]["scale"]), arch.eps)
        k = _rms(k.reshape(n, nkv, d), r(att["k_norm"]["scale"]), arch.eps)
        if rope:
            q = _rotary(q, positions, arch.theta)
            k = _rotary(k, positions, arch.theta)
        return r(q), r(k), r(v.reshape(n, nkv, d)), g


@functools.partial(jax.jit, static_argnames=("window", "round_to"))
def _attend(q, k, v, q_pos, k_pos, *, window, round_to):
    """``q`` (rows, heads, d) against every key (n, kv_heads, d)."""
    with jax.default_matmul_precision("highest"):
        rows, nh, d = q.shape
        nkv = k.shape[1]
        qg = q.reshape(rows, nkv, nh // nkv, d)
        s = jnp.einsum("rkgd,nkd->kgrn", qg, k) * d ** -0.5
        see = k_pos[None, :] <= q_pos[:, None]
        if window is not None:
            see &= k_pos[None, :] > q_pos[:, None] - window
        p = jax.nn.softmax(jnp.where(see, s, -jnp.inf), axis=-1)
        o = jnp.einsum("kgrn,nkd->rkgd", p, v).reshape(rows, nh * d)
        return _cast(o, round_to)


@functools.partial(jax.jit, static_argnames=("arch", "round_to"))
def _after_attention(x, o, g, p, *, arch: Arch, round_to):
    """``y = x + n_2((o * sigmoid(g)) W_o)`` and ``m = n_3(y)``."""
    with jax.default_matmul_precision("highest"):
        r = functools.partial(_cast, round_to=round_to)
        a = r((o * jax.nn.sigmoid(g)) @ r(p["attention"]["proj"]))
        y = r(x + _rms(a, r(p["post_attention_norm"]["scale"]), arch.eps))
        return y, r(_rms(y, r(p["pre_mlp_norm"]["scale"]), arch.eps))


@functools.partial(jax.jit, static_argnames=("round_to",))
def _gated(m, w_gate, w_up, w_down, *, round_to):
    with jax.default_matmul_precision("highest"):
        r = functools.partial(_cast, round_to=round_to)
        return r(r(jax.nn.silu(r(m @ r(w_gate))) * r(m @ r(w_up)))
                 @ r(w_down))


@functools.partial(jax.jit,
                   static_argnames=("arch", "band", "round_to", "faults"))
def _route(m, router, bias, theirs, *, arch: Arch, band, round_to, faults):
    """(weights (n, k), chosen (n, k), misfit (n,)).

    ``theirs`` (n, k) is the program's choice, or None. Its ``misfit``
    is how far outside the reference's own scores it lies: the
    reference's k-th biased score less the least one the program took,
    or the largest one the program left less the reference's (k+1)-th,
    whichever is more; 0 or less where the two choices are one. Up to
    ``band`` the reference takes the program's choice; past it the
    reference keeps its own."""
    with jax.default_matmul_precision("highest"):
        k = arch.top_k
        router = _cast(router, round_to)
        if "bf16_router" in faults:      # the product's inputs in bf16
            m, router = _cast(m, jnp.bfloat16), _cast(router, jnp.bfloat16)
        s = jax.nn.sigmoid(m @ router)
        biased = s if "choice_without_bias" in faults \
            else s + jnp.asarray(bias, F32)
        top, ids = jax.lax.top_k(biased, k + 1)
        chosen = ids[:, :k]
        misfit = jnp.zeros(biased.shape[:1], F32)
        if theirs is not None:
            at = jnp.arange(biased.shape[0])[:, None]
            taken = jnp.zeros(biased.shape, bool).at[at, theirs].set(True)
            misfit = jnp.maximum(
                top[:, k - 1] - jnp.where(taken, biased, jnp.inf).min(-1),
                jnp.where(taken, -jnp.inf, biased).max(-1) - top[:, k])
            misfit = jnp.where(taken.sum(-1) == k, misfit, jnp.inf)
            chosen = jnp.where((misfit <= band)[:, None], theirs, chosen)
        raw = jnp.take_along_axis(
            s + jnp.asarray(bias, F32) if "weight_by_biased" in faults else s,
            chosen, axis=-1)
        w = raw / (raw.sum(-1, keepdims=True) + 1e-20) * arch.route_scale
        return w, chosen, misfit


@functools.partial(jax.jit, static_argnames=("arch", "round_to"))
def _close_layer(y, mlp_out, p, *, arch: Arch, round_to):
    r = functools.partial(_cast, round_to=round_to)
    return r(y + _rms(mlp_out, r(p["post_mlp_norm"]["scale"]), arch.eps))


@functools.partial(jax.jit, static_argnames=("arch", "round_to"))
def _head(x, norm, head, *, arch: Arch, round_to):
    with jax.default_matmul_precision("highest"):
        r = functools.partial(_cast, round_to=round_to)
        return r(_rms(x, r(norm["scale"]), arch.eps)) @ r(head).T


@functools.partial(jax.jit, static_argnames=("round_to",))
def _add_expert(out, m, idx, w, mlp, e, *, round_to):
    """``out[idx] += w * Expert_e(m[idx])``: ``idx`` the rows routed to
    held expert ``e`` and ``w`` their weights, both padded (row 0 at
    weight 0) to one of a few lengths, so that a few programs serve
    every expert of every layer."""
    got = _gated(m[idx], mlp["w_gate"][e], mlp["w_up"][e], mlp["w_down"][e],
                 round_to=round_to)
    return out.at[idx].add(got * w[:, None])


def _experts(m, mlp, arch: Arch, round_to, faults, theirs=None,
             band: float = 0.0):
    """The held experts' part and the shared expert, one expert at a
    time over the rows routed to it (chosen on the host: plain, and
    the rows an expert sees are a few in a hundred). Returns the MLP's
    result and the layer's routing facts."""
    count = mlp["w_gate"].shape[0]
    first = arch.held[0] if arch.held is not None else 0
    w, chosen, misfit = _route(
        m, mlp["router"], mlp["select_bias"],
        None if theirs is None else jnp.asarray(theirs, jnp.int32),
        arch=arch, band=band, round_to=round_to, faults=faults)
    chosen_h, w_h = np.asarray(chosen), np.asarray(w)
    out = _gated(m, mlp["shared_gate"], mlp["shared_up"],
                 mlp["shared_down"], round_to=round_to) \
        if "shared_gate" in mlp else jnp.zeros_like(m)
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
    facts = {"misfit": np.asarray(misfit), "held_pairs": held_pairs, "pairs": int(chosen_h.size),
             "chosen": chosen_h}
    return out, facts


def forward(params, tokens, rows, arch: Arch, *, choice=None,
            band: float = 0.0, round_to=None, faults: Sequence[str] = (),
            row_block: int = 128):
    """Float32 logits (len(rows), vocab) of ONE sequence ``tokens``
    (1-D) at the positions ``rows``, and the routing facts of every
    expert layer (``chosen``; ``misfit`` a row, ``_route``'s;
    ``held_pairs`` / ``pairs``). ``choice`` holds, for
    each expert layer in order, the program's chosen experts
    (len(tokens), k) over the same tokens."""
    faults = tuple(sorted(faults))
    if set(faults) - set(FAULTS):
        raise ValueError(f"unknown faults {faults}; known: {FAULTS}")
    p = params["params"]
    tokens = jnp.asarray(tokens, jnp.int32)
    n = tokens.shape[0]
    positions = jnp.arange(n, dtype=jnp.int32)
    hidden = p["embedding"].shape[1]
    x = _cast(_cast(p["embedding"][tokens], round_to) * hidden ** 0.5,
              round_to)
    routing = []
    for i, (attention, mlp_kind) in enumerate(arch.layers):
        layer = p[f"layer_{i}"]
        window = arch.window if attention == "window" else None
        if window is not None and "window_edge" in faults:
            window += 1
        q, k, v, g = _qkvg(
            x, layer, positions, arch=arch, round_to=round_to,
            rope=attention == "window" or "rope_on_full" in faults)
        o = jnp.concatenate([
            _attend(q[a:a + row_block], k, v, positions[a:a + row_block],
                    positions, window=window, round_to=round_to)
            for a in range(0, n, row_block)])
        y, m = _after_attention(x, o, g, layer, arch=arch,
                                round_to=round_to)
        mlp = layer["mlp"]
        if mlp_kind == "experts":
            theirs = None if choice is None else choice[len(routing)]
            out, facts = _experts(m, mlp, arch, round_to, faults, theirs,
                                  float(band))
            routing.append(facts)
        else:
            out = _gated(m, mlp["gate"], mlp["up"], mlp["down"],
                         round_to=round_to)
        x = _close_layer(y, out, layer, arch=arch, round_to=round_to)
    logits = _head(x[jnp.asarray(rows)], p["final_norm"], p["head"],
                   arch=arch, round_to=round_to)
    return logits, routing


def held_margin(biased, k: int, held: Tuple[int, int]) -> np.ndarray:
    """How far each row of biased scores (n, experts) is from a choice
    that changes what the ``held = (first, count)`` experts add: the
    least, over the held experts, of a chosen one's score less the
    first left out's (it could drop out) and of the last chosen's less
    an unchosen one's (it could come in)."""
    biased = np.asarray(biased, np.float32)
    ranked = -np.partition(-biased, k, axis=-1)
    kth, left_out = ranked[:, :k].min(-1, keepdims=True), ranked[:, k:k + 1]
    ids = np.arange(biased.shape[1])
    here = (ids >= held[0]) & (ids < held[0] + held[1])
    is_in = biased >= kth
    out = np.where(here & is_in, biased - left_out, np.inf)
    come = np.where(here & ~is_in, kth - biased, np.inf)
    return np.minimum(out.min(-1), come.min(-1))


def teacher_forced(prompt, served, pad_to: int = 128):
    """The tokens a served sequence is teacher-forced on (prompt, then
    every served token but the last, padded with zeros to a multiple
    of ``pad_to``) and the rows whose logits decided the served
    tokens."""
    served = np.asarray(served)
    toks = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    rows = np.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
    return np.pad(toks, (0, -len(toks) % pad_to)), rows


def judge(logits, served, may_differ, *, ulps: float,
          dtype_eps: float) -> Dict[str, Any]:
    """Each served token must be the argmax of its row of ``logits``
    (the reference's, float32) or trail that row's largest logit by at
    most ``ulps`` ulps of it (``dtype_eps`` the served type's epsilon).
    A row that trails by more is ``excused`` if it is marked in
    ``may_differ`` (and counted: the caller caps the share), and fails
    the sequence if it is not."""
    logits, served = np.asarray(logits), np.asarray(served)
    may_differ = np.asarray(may_differ, bool)
    n = len(served)
    gap = logits.max(-1) - logits[np.arange(n), served]
    off = gap / (dtype_eps * np.abs(logits).max(-1))
    excused = (off > ulps) & may_differ
    held_to = ~excused
    return {"ok": bool((off[held_to] <= ulps).all()), "rows": int(n),
            "exact": int((gap == 0).sum()), "excused": int(excused.sum()),
            "may_differ": int(may_differ.sum()),
            "worst_ulps": float(off[held_to].max()) if held_to.any() else 0.0,
            "worst_row": int(np.argmax(np.where(held_to, off, -1.0))),
            "worst_excused_ulps": float(off[excused].max())
            if excused.any() else 0.0,
            "ulps_by_row": off}


def check_served(params, arch: Arch, prompt, served, *, ulps: float,
                 dtype_eps: float, choice=None, band: float = 0.0,
                 slack: float = 0.0, pad_to: int = 128, round_to=None,
                 faults: Sequence[str] = ()) -> Dict[str, Any]:
    """:func:`judge` of a served sequence, teacher-forced through the
    reference.

    ``choice`` is what the program's own router gave in a pass of its
    own over :func:`teacher_forced`'s tokens: for each expert layer
    ``(ids (n, k), biased scores (n, experts))``. The reference
    follows the ids within ``band`` (:func:`_route`). That pass is not
    the served one (no cache, other kernels' blocks): where a row's
    :func:`held_margin` by the *program's* scores is under ``slack``
    at some layer, the served step may have chosen otherwise than the
    pass did, and such a row is excused if it trails by more than
    ``ulps`` (no other row is). A row of the sequence, prompt or
    served, where the program's choice lies further than ``band``
    outside the reference's scores is ``refused``: no rounding explains
    it, and the sequence is not ok. Also counted: rows ``followed``, and
    the routed pairs that landed on held experts (the padding rows'
    pairs among them)."""
    toks, rows = teacher_forced(prompt, served, pad_to)
    logits, routing = forward(
        params, toks, rows, arch, band=band, round_to=round_to,
        faults=faults,
        choice=None if choice is None else [ids for ids, _ in choice])
    n, last = len(rows), rows[-1] + 1           # padding rows left out
    misfit = np.max([f["misfit"][:last] for f in routing], axis=0) \
        if routing else np.zeros(last)
    refused = misfit > band
    margin = np.full(n, np.inf)
    for _, biased in choice or ():
        biased = np.asarray(biased)[rows]
        margin = np.minimum(margin, held_margin(
            biased, arch.top_k, arch.held or (0, biased.shape[1])))
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
