"""A decoder built from a per-layer pattern.

``models/gpt.py`` is one block repeated (and scanned). The models this
file serves are not: window and full attention alternate, a leading
dense layer precedes expert layers, nine state-space layers stand
beside one attention layer. So the layers are data
(``DecoderConfig.layers``: for each layer the kind of its mixer and
its MLP kind) and the model is unrolled over them.

The block is data too (``DecoderConfig``), because the models differ
in it (``docs/serving.md`` "Window layers", ``docs/moe.md`` "Held
experts"):

- the embedding scaled by ``sqrt(hidden)``, by a multiplier of the
  model's own, or not; the head untied or the embedding again; the
  logits divided by a constant or not; what each half of a layer adds
  to the stream multiplied by a constant or not;
- where the norms stand: *sandwich*, four RMSNorms a layer
  (``y = x + n2(Attn(n1(x)))``, ``x' = y + n4(MLP(n3(y)))``), or *pre*,
  two (``y = x + Attn(n1(x))``, ``x' = y + MLP(n2(y))``);
- attention with ``head_dim`` of its own (not ``hidden / heads``) and
  GQA; QK-norm (an RMSNorm over the head) or none; scores scaled by
  ``head_dim^-0.5`` or by the model's own multiplier; a sigmoid output
  gate or none; *window* layers see the last ``window`` keys, *full*
  layers every key;
- a *latent* layer is DeepSeek-V2/V3's multi-head latent attention
  (MLA, ``DecoderConfig.mla``): queries through a low-rank latent,
  keys and values up-projected from one normed latent a token
  ``c_kv`` beside one rotated key ``k_r`` that every head shares; what
  it caches is the row ``[c_kv | k_r | 0]`` (:class:`LatentAttention`,
  docs/serving.md "Latent attention");
- a *mamba* layer has no attention at all: its mixer is
  ``models/ssm.py``'s Mamba-2, whose state a sequence is a fixed size
  (``DecoderConfig.mamba``), not keys that grow with the context; a
  *mamba1* layer is the same with Mamba-1's selective scan, a decay for
  every channel and state (``DecoderConfig.selective``);
- a rotary scheme *for each kind of layer* (:class:`Rotary`): none,
  plain, or YaRN's blend of scaled and unscaled frequencies with its
  factor on cos and sin;
- a SiLU-gated dense MLP, or ``moe.held.HeldMoEMLP`` under the sigmoid
  or the softmax router, holding a share of the experts or all.

What a configuration does not say is Arcee's ``afmoe`` (Trinity): the
scaled embedding, sandwich norm, the gate, plain rotary on the
window layers and none on the full ones, the sigmoid router.
JetBrains' ``mellum`` (``benchmark/drivers/serve_mellum.py``) is
pre-norm with no scale and no gate, plain rotary on its window layers
and YaRN on its full ones, the softmax router over experts all held.
IBM's ``granitemoehybrid`` (``benchmark/drivers/serve_granite.py``) is
pre-norm, nine ``mamba`` layers and one full attention layer a period,
no QK-norm and no rotary at all, scores times 1/128, multipliers on
the embedding, the residuals and the logits, a tied head, the softmax
router with a shared expert.
AI21's ``jamba`` (Jamba2-3B, ``benchmark/drivers/serve_jamba.py``) is
pre-norm, thirteen ``mamba1`` layers (with RMSNorms on ``dt``, ``B``
and ``C`` inside the mixer) and one full attention layer a period,
one KV head, no QK-norm and no rotary, a dense MLP on every layer, a
tied head.
Zhipu's ``glm4_moe_lite`` (GLM-4.7-Flash,
``benchmark/drivers/serve_glm.py``) is pre-norm, ``latent`` layers
throughout with rotary on the shared key's 64 dimensions only (pairs
interleaved), one leading dense layer, then the sigmoid router over
64 experts with a shared expert, an untied head.

It offers the serving hooks ``GPTModel`` offers (``positions``,
``kv_ctx``, ``return_kv``; ``config.kv_heads`` / ``head_dim`` /
``max_seq_len`` / ``num_layers`` / ``attention_window``), so
``serving.make_decode_step`` and ``ContinuousBatcher`` take it as they
take a ``GPTModel``; a model with ``mamba`` or ``mamba1`` layers also takes
``state_ctx``, its lanes' recurrent states (``config.num_kv_layers``
is then fewer than ``num_layers``: the paged pool holds only the
layers that HAVE keys). Single device: no mesh annotations.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu.models.cached_attention import (
    cached_attention, cached_latent_attention, first_context)
from apex_tpu.models.ssm import (Mamba2Config, Mamba2Mixer, SelectiveMixer,
                                 SelectiveSSMConfig)
from apex_tpu.moe.held import HeldMoEConfig, HeldMoEMLP, gated_mlp
from apex_tpu.ops.layer_norm import fused_rms_norm
from apex_tpu.ops.rope import fused_apply_rotary_pos_emb_cached

ATTENTION_KINDS = ("full", "window", "latent")
# the kinds whose layers hold a recurrent state, not keys
STATE_KINDS = ("mamba", "mamba1")
MIXER_KINDS = ATTENTION_KINDS + STATE_KINDS
MLP_KINDS = ("dense", "experts")
NORM_PLACEMENTS = ("sandwich", "pre")


@dataclasses.dataclass(frozen=True)
class Rotary:
    """One kind of layer's rotary embedding: plain at ``theta``, or,
    with ``factor`` over 1, YaRN: position interpolation by ``factor``
    for the slow frequencies, none for the fast, a linear ramp between
    the dimensions that turn ``beta_fast`` and ``beta_slow`` times over
    the ``original_max_position`` the model was trained at (truncated
    to whole dimensions), and ``attention_factor`` on cos and sin."""

    theta: float
    factor: float = 1.0
    original_max_position: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0

    def __post_init__(self):
        if self.factor != 1.0 and self.original_max_position <= 0:
            raise ValueError("YaRN needs original_max_position, the "
                             "positions the model was trained at")

    def inv_freq(self, d: int):
        """(d / 2,) float32: the angle a position turns each pair by."""
        i = jnp.arange(0, d, 2, dtype=jnp.float32)
        plain = self.theta ** (-i / d)
        if self.factor == 1.0:
            return plain

        def dim_of(turns):            # the pair that turns so many times
            return (d * math.log(self.original_max_position
                                 / (turns * 2 * math.pi))
                    / (2 * math.log(self.theta)))

        low = max(math.floor(dim_of(self.beta_fast)), 0)
        high = min(math.ceil(dim_of(self.beta_slow)), d - 1)
        ramp = jnp.clip((i / 2 - low) / max(high - low, 1e-3), 0.0, 1.0)
        return plain / self.factor * ramp + plain * (1.0 - ramp)


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """The sizes of a ``latent`` layer (DeepSeek-V2/V3's MLA, under
    their own keys): the query's latent, the keys' and values' latent
    ``c_kv``, each head's unrotated and rotated parts of q and k, and
    its value. The rotated key is one a token, shared by the heads."""

    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def row_width(self) -> int:
        """The cached row ``[c_kv | k_r | 0]``: ``c_kv`` and ``k_r``
        padded with zeros to whole 128-lane tiles (576 -> 640), the
        width the device would lay it out at anyway and the one
        ``ops/kv_gather.py`` copies whole."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    vocab_size: int
    hidden_size: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    max_seq_len: int
    # one (mixer kind, MLP kind) a layer
    layers: Tuple[Tuple[str, str], ...]
    ffn_hidden_size: int                      # the dense MLP's width
    attention_window: Optional[int] = None    # of the "window" layers
    expert_ffn_size: int = 0
    num_experts: int = 0                      # the router's width
    experts_per_token: int = 0
    held_experts: Optional[Tuple[int, int]] = None   # (first, count)
    shared_ffn_size: int = 0
    route_scale: float = 1.0
    rms_eps: float = 1e-5
    rope_theta: float = 10000.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    softmax_impl: Optional[str] = None        # the kernels' impl
    # the block (module docstring)
    norms: str = "sandwich"                   # one of NORM_PLACEMENTS
    embedding_scale: bool = True              # x0 = E[tokens] sqrt(hidden)
    output_gate: bool = True
    # ((attention kind, Rotary or None), ...), every kind the layers
    # have; None: plain at rope_theta on window layers, none on full
    rotary: Optional[Tuple[Tuple[str, Optional[Rotary]], ...]] = None
    router: str = "sigmoid"                   # one of moe.held.ROUTERS
    qk_norm: bool = True                      # an RMSNorm over q's, k's head
    attention_scale: Optional[float] = None   # None: head_dim ** -0.5
    # x0 = E[tokens] times this, for a model that does not scale by
    # sqrt(hidden): given only with embedding_scale=False
    embedding_multiplier: Optional[float] = None
    residual_multiplier: float = 1.0          # y = x + this * Mixer(...)
    logits_divisor: float = 1.0
    tied_head: bool = False                   # logits = n(x) E^T
    # the "mamba" layers' own sizes (the mixer takes its hidden size,
    # types, eps and impl from here where it is built); set exactly
    # when there is such a layer
    mamba: Optional[Mamba2Config] = None
    # the "latent" layers' sizes; set exactly when there is such a
    # layer, and then every layer that has keys is one
    mla: Optional[MLAConfig] = None
    # the "mamba1" layers' sizes (Mamba-1's selective scan), as
    # ``mamba`` is for the "mamba" layers; a model has one kind or the
    # other (a state slot holds one shape)
    selective: Optional[SelectiveSSMConfig] = None

    def __post_init__(self):
        if self.norms not in NORM_PLACEMENTS:
            raise ValueError(f"norms is one of {NORM_PLACEMENTS}, "
                             f"got {self.norms!r}")
        if self.rotary is not None:
            kinds = {a for a, _ in self.layers if a not in STATE_KINDS}
            if {k for k, _ in self.rotary} != kinds:
                raise ValueError(
                    f"rotary names each kind of layer once ({sorted(kinds)}"
                    f"), got {[k for k, _ in self.rotary]}")
        for attention, mlp in self.layers:
            if attention not in MIXER_KINDS or mlp not in MLP_KINDS:
                raise ValueError(
                    f"a layer is (one of {MIXER_KINDS}, one of "
                    f"{MLP_KINDS}), got {(attention, mlp)}")
        if self.num_heads % self.num_kv_heads:
            raise ValueError(f"num_kv_heads ({self.num_kv_heads}) must "
                             f"divide num_heads ({self.num_heads})")
        windowed = any(a == "window" for a, _ in self.layers)
        if windowed != (self.attention_window is not None):
            raise ValueError("attention_window is set exactly when some "
                             "layer is a window layer")
        if any(m == "experts" for _, m in self.layers):
            self.moe_cfg()                    # validates the expert sizes
        for kind, sizes in (("mamba", self.mamba),
                            ("mamba1", self.selective)):
            if (sizes is not None) != any(a == kind for a, _ in self.layers):
                raise ValueError(
                    f"{'selective' if kind == 'mamba1' else 'mamba'} is set "
                    f"exactly when some layer is a {kind} layer")
        if self.mamba is not None and self.selective is not None:
            raise ValueError("mamba and mamba1 layers in one model: a state "
                             "slot holds one kind of state")
        kinds = {a for a, _ in self.layers if a not in STATE_KINDS}
        if (self.mla is not None) != ("latent" in kinds) or (
                "latent" in kinds and kinds != {"latent"}):
            raise ValueError(
                "mla is set exactly when some layer is a latent layer, and "
                "then every layer with keys is one (the paged pool holds "
                "one kind of row)")
        if self.embedding_scale and self.embedding_multiplier is not None:
            raise ValueError(
                "embedding_scale (x0 = E[tokens] sqrt(hidden)) and "
                f"embedding_multiplier ({self.embedding_multiplier}) both "
                "say what the embedding is scaled by: give one")

    @property
    def num_layers(self) -> int:
        return len(self.layers)

    @property
    def kv_layers(self) -> Tuple[int, ...]:
        """The layers that have keys, in order: layer ``kv_layers[j]``
        is layer ``j`` of the paged pool."""
        return tuple(i for i, (a, _) in enumerate(self.layers)
                     if a not in STATE_KINDS)

    @property
    def num_kv_layers(self) -> int:
        return len(self.kv_layers)

    @property
    def state_layers(self) -> Tuple[int, ...]:
        """The layers that hold a recurrent state, in order: layer
        ``state_layers[j]`` is layer ``j`` of a state slot."""
        return tuple(i for i, (a, _) in enumerate(self.layers)
                     if a in STATE_KINDS)

    def state_shapes(self):
        """What a sequence holds beside its K/V, a state slot:
        ``((shape, dtype), ...)`` with the state layers leading, or
        ``()`` for a model whose layers all have keys."""
        sizes = self.mamba if self.mamba is not None else self.selective
        if sizes is None:
            return ()
        n = len(self.state_layers)
        return tuple(((n, *shape), dtype)
                     for shape, dtype in sizes.state_shapes(self.dtype))

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads

    def cache_row(self) -> Tuple[int, int, int]:
        """What the paged pool holds a token a layer
        (``serving.KVCache.for_config``): ``(kv_heads, width, pools)``,
        a K and a V row of ``kv_heads x head_dim``, or a latent model's
        one row of ``mla.row_width`` in one pool."""
        if self.mla is not None:
            return 1, self.mla.row_width, 1
        return self.num_kv_heads, self.head_dim, 2

    def rotary_of(self, kind: str) -> Optional[Rotary]:
        if self.rotary is None:
            return (Rotary(self.rope_theta) if kind in ("window", "latent")
                    else None)
        return dict(self.rotary)[kind]

    def moe_cfg(self) -> HeldMoEConfig:
        return HeldMoEConfig(
            hidden_size=self.hidden_size,
            expert_ffn_size=self.expert_ffn_size,
            num_experts=self.num_experts, top_k=self.experts_per_token,
            held=self.held_experts, route_scale=self.route_scale,
            shared_ffn_size=self.shared_ffn_size, dtype=self.dtype,
            param_dtype=self.param_dtype, router=self.router)


def _flash(cfg: DecoderConfig, *args, **kw):
    from apex_tpu.ops.attention import flash_attention

    if cfg.attention_scale is not None:
        kw["softmax_scale"] = cfg.attention_scale
    return flash_attention(*args, impl=cfg.softmax_impl, **kw)


def _gather(cfg: DecoderConfig, *args):
    from apex_tpu.ops.kv_gather import kv_gather

    return kv_gather(*args, impl=cfg.softmax_impl)


def _zero_ctx(cfg: DecoderConfig, *args):
    from apex_tpu.ops.kv_gather import zero_context

    return zero_context(*args, impl=cfg.softmax_impl)


class RMSNorm(nn.Module):
    """``x * rsqrt(mean(x^2) + eps) * g`` in float32 over the last dim
    (the XLA form of ``ops/layer_norm.py``: it fuses into its
    neighbours, and a decode step's 16 rows are no kernel's tile)."""

    eps: float

    @nn.compact
    def __call__(self, x):
        g = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                       jnp.float32)
        return fused_rms_norm(x, g, eps=self.eps, impl="xla")


def rotary(t, positions, inv_freq, factor: float = 1.0):
    """Rotary embedding over the whole head, rotate-half convention:
    ``t`` (b, s, heads, d) at ``positions`` (b, s) in the sequence,
    ``inv_freq`` (d / 2,) a position's angle for each pair, ``factor``
    on cos and sin."""
    angles = positions.astype(jnp.float32)[..., None] * inv_freq
    angles = jnp.concatenate([angles, angles], axis=-1)[:, :, None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    return fused_apply_rotary_pos_emb_cached(t, cos, sin, impl="xla")


class DecoderAttention(nn.Module):
    config: DecoderConfig
    kind: str

    @nn.compact
    def __call__(self, x, positions, *, kv_ctx=None):
        """``x`` (b, s, hidden) -> (b, s, hidden), this call's K/V in
        the kernel layout (b, kv_heads, s, head_dim), and what the next
        layer of this kind gathers its context into (``kv_ctx[1]`` is
        what this one does: ``cached_attention``)."""
        cfg = self.config
        b, s, _ = x.shape
        nh, nkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        window = cfg.attention_window if self.kind == "window" else None
        init = nn.initializers.normal(stddev=0.02)
        # one product for q, k, v and, where the output is gated, the
        # gate: [q | k | v | g]
        gated = cfg.output_gate
        cuts = [nh * d, (nh + nkv) * d, (nh + 2 * nkv) * d]
        w = self.param("qkvg" if gated else "qkv", init,
                       (cfg.hidden_size, cuts[2] + gated * nh * d),
                       cfg.param_dtype)
        q, k, v, *g = jnp.split(jnp.dot(x, w.astype(cfg.dtype)),
                                cuts if gated else cuts[:2], axis=-1)
        q = q.reshape(b, s, nh, d)
        if cfg.qk_norm:
            q = RMSNorm(cfg.rms_eps, name="q_norm")(q)
        k = k.reshape(b, s, nkv, d)
        if cfg.qk_norm:
            k = RMSNorm(cfg.rms_eps, name="k_norm")(k)
        v = v.reshape(b, s, nkv, d)
        rope = cfg.rotary_of(self.kind)
        if rope is not None:
            inv_freq = rope.inv_freq(d)
            q = rotary(q, positions, inv_freq, rope.attention_factor)
            k = rotary(k, positions, inv_freq, rope.attention_factor)
        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        held = ()
        if kv_ctx is not None:
            o, held = cached_attention(
                q, k, v, kv_ctx, window=window, dtype=cfg.dtype,
                flash=lambda *a, **kw: _flash(cfg, *a, **kw),
                gather=lambda *a: _gather(cfg, *a))
        elif window is not None:
            with jax.named_scope("attention_window"):
                o = _flash(cfg, q, k, v, causal=True, window_size=window)
        else:
            o = _flash(cfg, q, k, v, causal=True)
        o = o.transpose(0, 2, 1, 3).reshape(b, s, nh * d)
        if gated:
            gate = jax.nn.sigmoid(g[0].astype(jnp.float32))
            o = o * gate.astype(cfg.dtype)
        wo = self.param("proj", init, (nh * d, cfg.hidden_size),
                        cfg.param_dtype)
        return jnp.dot(o, wo.astype(cfg.dtype)), (k, v), held


def interleaved(t):
    """DeepSeek-V3's rotary layout, in the rotate-half convention
    :func:`rotary` computes: the pairs it turns together are adjacent
    dimensions ``(2i, 2i + 1)``, so they are brought to ``i`` and
    ``i + d / 2`` first. Queries and keys are reordered alike, so
    their products are the interleaved form's."""
    return jnp.concatenate([t[..., 0::2], t[..., 1::2]], axis=-1)


class LatentAttention(nn.Module):
    """Multi-head latent attention (``DecoderConfig.mla``):

        c_q = n_q(x W_DQ);  [q_nope | q_rope] = c_q W_UQ   (per head)
        [c_kv | k_r] = x W_DKV;  c_kv <- n_kv(c_kv)
        [k_nope | v] = c_kv W_UKV                          (per head)
        q_rope, k_r rotated (interleaved pairs); k_r shared by heads
        scores (q_nope . k_nope + q_rope . k_r) (qk_head_dim)^-0.5
        o = softmax(scores) v, causal;  out = o W_O

    What a token leaves in the cache is the row ``[c_kv | k_r | 0]``
    (``MLAConfig.row_width``). A call of several tokens (a whole
    prompt, a chunk) takes the *expanded* form: the context's rows and
    its own are up-projected into per-head K and V and the flash
    kernel attends. A decode call takes the *absorbed* form:
    ``q_lat = q_nope W_UK^T`` scores the rows as they are, each head's
    value is ``c_kv`` itself (``ops/mla_decode.py`` reads each lane's
    live rows once for both, where they lie in the pool, and the
    token's own row last), and ``W_UV`` goes on the result. The form is
    chosen by the call's shape alone."""

    config: DecoderConfig

    @nn.compact
    def __call__(self, x, positions, *, kv_ctx=None):
        """As ``DecoderAttention``: (b, s, hidden) out, this call's
        rows ``(b, 1, s, row_width)`` (the one pool's), and what the
        next latent layer gathers into."""
        cfg, mla = self.config, self.config.mla
        b, s, _ = x.shape
        nh, dt = cfg.num_heads, cfg.dtype
        lat, nope, rope = (mla.kv_lora_rank, mla.qk_nope_head_dim,
                           mla.qk_rope_head_dim)
        vd, qk, width = mla.v_head_dim, mla.qk_head_dim, mla.row_width
        scale = qk ** -0.5
        init = nn.initializers.normal(stddev=0.02)

        def param(name, *shape):
            return self.param(name, init, shape, cfg.param_dtype).astype(dt)

        c_q = RMSNorm(cfg.rms_eps, name="q_norm")(
            jnp.dot(x, param("q_down", cfg.hidden_size, mla.q_lora_rank)))
        q = jnp.dot(c_q.astype(dt), param("q_up", mla.q_lora_rank, nh * qk))
        q_nope, q_rope = jnp.split(q.reshape(b, s, nh, qk), [nope], axis=-1)
        ckr = jnp.dot(x, param("kv_down", cfg.hidden_size, lat + rope))
        c_kv = RMSNorm(cfg.rms_eps, name="kv_norm")(ckr[..., :lat])
        rot = cfg.rotary_of("latent")
        inv_freq = rot.inv_freq(rope)
        q_rope = rotary(interleaved(q_rope), positions, inv_freq,
                        rot.attention_factor).astype(dt)
        k_r = rotary(interleaved(ckr[..., lat:])[:, :, None, :], positions,
                     inv_freq, rot.attention_factor)[:, :, 0]
        row = jnp.concatenate(
            [c_kv.astype(dt), k_r.astype(dt),
             jnp.zeros((b, s, width - lat - rope), dt)], axis=-1)[:, None]
        w_ukv = param("kv_up", lat, nh * (nope + vd))
        # a flash call needs one size for q, k and v: the narrower is
        # padded with zeros, which change no score and no value
        d = max(qk, vd)

        def pad(t):
            return t if t.shape[-1] == d else jnp.pad(
                t, [(0, 0)] * (t.ndim - 1) + [(0, d - t.shape[-1])])

        def expanded(rows, **kw):
            with jax.named_scope("mla_expanded"):
                n = rows.shape[2]
                kv = jnp.dot(rows[:, 0, :, :lat], w_ukv).reshape(
                    b, n, nh, nope + vd)
                k_shared = jnp.broadcast_to(
                    rows[:, 0, :, None, lat:lat + rope], (b, n, nh, rope))
                k = jnp.concatenate([kv[..., :nope], k_shared], axis=-1)
                qf = jnp.concatenate([q_nope, q_rope], axis=-1)
                qf, k, v = (pad(t).transpose(0, 2, 1, 3)
                            for t in (qf, k, kv[..., nope:]))
                o = _flash(cfg, qf, k, v, causal=True, softmax_scale=scale,
                           **kw)
                return o[..., :vd].transpose(0, 2, 1, 3)

        def absorbed(pools, layer, tables, lens, row_new):
            from apex_tpu.ops.mla_decode import mla_decode

            with jax.named_scope("mla_absorbed"):
                w = w_ukv.reshape(lat, nh, nope + vd)
                q_lat = jnp.einsum("bhn,chn->bhc", q_nope[:, 0],
                                   w[..., :nope],
                                   preferred_element_type=jnp.float32)
                qf = jnp.concatenate(
                    [q_lat.astype(dt), q_rope[:, 0],
                     jnp.zeros((b, nh, width - lat - rope), dt)], axis=-1)
                o = mla_decode(qf, pools[0], layer, tables, lens, row_new,
                               value_dim=lat, scale=scale,
                               impl=cfg.softmax_impl)
                o = jnp.einsum("bhc,chv->bhv", o.astype(dt), w[..., nope:],
                               preferred_element_type=jnp.float32)
                return o.astype(dt)[:, None]

        held = ()
        if kv_ctx is not None:
            o, held = cached_latent_attention(
                row, kv_ctx, dtype=dt, expanded=expanded, absorbed=absorbed,
                gather=lambda *a: _gather(cfg, *a))
        else:
            o = expanded(row)
        o = o.reshape(b, s, nh * vd).astype(dt)
        return jnp.dot(o, param("proj", nh * vd, cfg.hidden_size)), (
            row,), held


class DenseMLP(nn.Module):
    config: DecoderConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        h, f = cfg.hidden_size, cfg.ffn_hidden_size
        init = nn.initializers.normal(stddev=0.02)
        return gated_mlp(
            x, self.param("gate", init, (h, f), cfg.param_dtype),
            self.param("up", init, (h, f), cfg.param_dtype),
            self.param("down", init, (f, h), cfg.param_dtype), cfg.dtype)


class DecoderLayer(nn.Module):
    config: DecoderConfig
    attention: str                 # the mixer's kind, one of MIXER_KINDS
    mlp: str

    @nn.compact
    def __call__(self, x, positions, *, kv_ctx=None, state_ctx=None,
                 lengths=None):
        """One layer. An attention layer returns ``(x', (k, v),
        held)`` as ``DecoderAttention`` gives them; a ``mamba`` or
        ``mamba1`` layer ``(x', the state pools, ())``: ``state_ctx``
        and ``lengths`` are ``ssm.Mamba2Mixer``'s (None: every lane from
        zeros, and None comes back)."""
        cfg = self.config
        norm = lambda name: RMSNorm(cfg.rms_eps, name=name)  # noqa: E731
        # a sandwich also norms what each half adds to the stream
        after = norm if cfg.norms == "sandwich" else (
            lambda name: lambda t: t)
        if cfg.residual_multiplier != 1.0:
            # in float32: 0.22 is no bf16 number
            scaled = lambda t: (  # noqa: E731
                t.astype(jnp.float32) * cfg.residual_multiplier
            ).astype(t.dtype)
        else:
            scaled = lambda t: t  # noqa: E731
        # each half of the layer under its part of the model
        # (telemetry.compiled.PARTS), norms and residual with it
        if self.attention in STATE_KINDS:
            with jax.named_scope("mixer"):
                with jax.named_scope("mamba_mixer"):
                    mixer, sizes = ((Mamba2Mixer, cfg.mamba)
                                    if self.attention == "mamba" else
                                    (SelectiveMixer, cfg.selective))
                    a, kv = mixer(
                        sizes, hidden_size=cfg.hidden_size,
                        rms_eps=cfg.rms_eps, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype, impl=cfg.softmax_impl,
                        name="mixer")(
                        norm("input_norm")(x), state_ctx=state_ctx,
                        lengths=lengths)
                y = x + scaled(after("post_attention_norm")(a))
            held = ()
        else:
            with jax.named_scope("attention"):
                mixer = (LatentAttention(cfg, name="attention")
                         if self.attention == "latent" else
                         DecoderAttention(cfg, self.attention,
                                          name="attention"))
                a, kv, held = mixer(norm("input_norm")(x), positions,
                                    kv_ctx=kv_ctx)
                y = x + scaled(after("post_attention_norm")(a))
        # an expert layer's shared MLP opens ``mlp`` inside (moe/held.py)
        with jax.named_scope("experts" if self.mlp == "experts" else "mlp"):
            m = norm("pre_mlp_norm")(y)
            if self.mlp == "experts":
                m = HeldMoEMLP(cfg.moe_cfg(), name="mlp")(m)
            else:
                m = DenseMLP(cfg, name="mlp")(m)
            return y + scaled(after("post_mlp_norm")(m)), kv, held


class PatternDecoder(nn.Module):
    """Token ids (b, s) -> logits (s, b, vocab) in float32, the layout
    ``GPTModel`` returns them in."""

    config: DecoderConfig

    @nn.compact
    def __call__(self, tokens, *, positions=None, kv_ctx=None,
                 state_ctx=None, return_kv=False):
        """``positions`` (b, s) or (s,): positions in the sequence
        (default ``arange(s)``); they turn the rotary embeddings and
        nothing else. ``kv_ctx = (pools, tables, ctx_lens[, win])``
        runs the cached paths (``models/cached_attention.py``):
        ``pools`` the paged pools, K and V, or a ``latent`` model's one
        pool of rows. ``return_kv=True`` also returns this call's rows
        of each pool, stacked (num_kv_layers, b, kv_heads, s,
        head_dim): the layers that have keys, in order.

        ``state_ctx = (pools, slots, lengths, fresh)``, for a model
        with ``mamba`` or ``mamba1`` layers: the state pools whole (``(slots + 1,
        state layers, ...)`` each: ``serving/kv_cache.py``), each
        lane's slot (b,), its real rows (b,) (None: all ``s``) and
        whether it starts its sequence here (b,) bool: from zeros
        then, whatever its slot held. Each such layer reads its lanes'
        state out of the pools and writes the new one back; the pools
        are then returned last. Without it every sequence starts from
        zeros and its state is dropped."""
        cfg = self.config
        b, s = tokens.shape
        init = nn.initializers.normal(stddev=0.02)
        table = self.param("embedding", init,
                           (cfg.vocab_size, cfg.hidden_size), cfg.param_dtype)
        with jax.named_scope("embed"):
            x = table[tokens]
            if cfg.embedding_multiplier is not None:
                x = x.astype(jnp.float32) * cfg.embedding_multiplier
            elif cfg.embedding_scale:
                x = x.astype(jnp.float32) * (cfg.hidden_size ** 0.5)
            x = x.astype(cfg.dtype)
            if positions is None:
                positions = jnp.arange(s, dtype=jnp.int32)
            positions = jnp.broadcast_to(jnp.asarray(positions), (b, s))
        kvs = []
        # what the next layer of each kind gathers its context into
        # (cached_attention): zeros before the first; nothing for the
        # latent layers of a decode call, which read the pool in place
        into = {}
        if kv_ctx is not None:
            windows = {"window": cfg.attention_window}
            into = {kind: () if kind == "latent" and s == 1 else
                    first_context(kv_ctx, window=windows.get(kind),
                                  zero=lambda *a: _zero_ctx(cfg, *a))
                    for kind in dict.fromkeys(a for a, _ in cfg.layers)
                    if kind not in STATE_KINDS}
        pools = slots = lengths = fresh = None
        if state_ctx is not None:
            pools, slots, lengths, fresh = state_ctx
        for i, (attention, mlp) in enumerate(cfg.layers):
            layer = DecoderLayer(cfg, attention, mlp, name=f"layer_{i}")
            if attention in STATE_KINDS:
                x, pools, _ = layer(
                    x, positions, lengths=lengths,
                    state_ctx=(None if pools is None else (
                        pools, cfg.state_layers.index(i), slots, fresh)))
                continue
            # the layer's own layer of the paged pool: its place among
            # the layers that have keys
            x, kv, into[attention] = layer(
                x, positions,
                kv_ctx=(None if kv_ctx is None
                        else (len(kvs), into[attention], *kv_ctx)))
            kvs.append(kv)
        with jax.named_scope("head"):
            x = RMSNorm(cfg.rms_eps, name="final_norm")(x)
            head = table if cfg.tied_head else self.param(
                "head", init, (cfg.vocab_size, cfg.hidden_size),
                cfg.param_dtype)
            logits = jnp.einsum("bsh,vh->sbv", x, head.astype(cfg.dtype),
                                preferred_element_type=jnp.float32)
            if cfg.logits_divisor != 1.0:
                logits = logits / cfg.logits_divisor
        out = (logits,)
        if return_kv:
            with jax.named_scope("cache"):
                out += (tuple(jnp.stack(rows) for rows in zip(*kvs)),)
        if state_ctx is not None:
            out += (pools,)
        return out if len(out) > 1 else logits


__all__ = ["DecoderConfig", "MLAConfig", "Mamba2Config", "PatternDecoder",
           "Rotary", "SelectiveSSMConfig"]
