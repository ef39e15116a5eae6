"""Megatron-style GPT — the flagship model family.

TPU re-design of the reference's standalone GPT test fixture
(ref: apex/transformer/testing/standalone_gpt.py,
standalone_transformer_lm.py — embedding + L x [LN, parallel attention,
LN, parallel MLP] + final LN + tied vocab head, trained with
vocab-parallel cross entropy). Built entirely from apex_tpu parallel
layers, so one module serves:

  - single device (plain apply; layers degrade to dense)
  - tensor parallel (+ sequence parallel) inside shard_map over "tensor"
  - pipeline parallel on the GSPMD mesh's `pipe` axis (the scan-layers
    stack split stage-major by `mesh.pipeline.PipelineSpec`)

`gpt_param_specs` derives the PartitionSpec tree for the step boundary
(the analog of the reference's per-layer process-group wiring).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from apex_tpu.normalization import fused_layer_norm
from apex_tpu.transformer.functional import AttnMaskType, FusedScaleMaskSoftmax
from apex_tpu.transformer.parallel_state import TENSOR_AXIS
from apex_tpu.transformer.tensor_parallel import (
    ColumnParallelLinear,
    RowParallelLinear,
    VocabParallelEmbedding,
    vocab_parallel_cross_entropy,
)
from apex_tpu.transformer.tensor_parallel.layers import _inside_axis
from apex_tpu.mesh import annotate as _gspmd


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50304
    max_seq_len: int = 1024
    hidden_size: int = 1024
    num_layers: int = 24
    num_heads: int = 16
    # GQA/MQA: number of shared kv heads; None = num_heads (MHA). The
    # fused QKV projection narrows to h + 2*num_kv_heads*head_dim and
    # the flash kernel shares each kv head across its q-head group
    # without materializing a repeat (ops/attention.py index maps).
    num_kv_heads: Optional[int] = None
    # sliding-window (local) attention: each query sees its last
    # `attention_window` keys up to the diagonal. flash backend only.
    attention_window: Optional[int] = None
    ffn_hidden_size: Optional[int] = None   # default 4*hidden
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    sequence_parallel: bool = False
    softmax_impl: Optional[str] = None
    attention_dropout: float = 0.0
    hidden_dropout: float = 0.0
    # "softmax" (fused masked softmax), "flash" (Pallas flash kernel),
    # or "ring" (context-parallel ring attention over the "context"
    # axis — run the model inside shard_map with tokens sharded along
    # seq and pass global `positions`)
    attention_backend: str = "flash"
    # lax.scan over stacked layer params (one compiled layer body
    # instead of num_layers inlined copies). Compile time and program
    # size become depth-independent (the scanned GPT-2 345M train step
    # compiles for a v5e in ~10 s). False restores per-layer param
    # names ("layer_{i}") for name-addressed checkpoints.
    scan_layers: bool = True
    # Mixture-of-Experts (docs/moe.md): num_experts=0 is the dense
    # model — every knob below is inert and the param tree is
    # byte-identical to a pre-MoE checkpoint. num_experts>0 swaps the
    # dense ParallelMLP for apex_tpu.moe.MoEMLP on designated layers
    # (layer i is MoE iff i % moe_layer_freq == moe_layer_freq - 1;
    # scan_layers needs freq 1 — homogeneous scan bodies).
    num_experts: int = 0
    moe_top_k: int = 2
    moe_layer_freq: int = 1
    # "dropless" (sort + group-GEMM, no drops) or "capacity"
    # (GShard (E, C) buffers; the mesh all-to-all EP path)
    moe_impl: str = "dropless"
    moe_capacity_factor: float = 1.25
    # Switch aux-loss weight folded into the training loss by
    # make_gpt_pretrain_step (0 trains without load balancing)
    moe_aux_loss_weight: float = 0.01

    def __post_init__(self):
        if self.num_kv_heads is not None and self.num_kv_heads < 1:
            raise ValueError(
                f"num_kv_heads must be >= 1 or None, got {self.num_kv_heads}")
        nkv = self.kv_heads
        if self.num_heads % nkv:
            raise ValueError(
                f"num_kv_heads ({nkv}) must divide num_heads "
                f"({self.num_heads})")
        if self.attention_window is not None:
            if self.attention_backend != "flash":
                raise ValueError(
                    "attention_window requires attention_backend='flash' "
                    f"(got {self.attention_backend!r})")
            if self.attention_window < 1:
                raise ValueError("attention_window must be >= 1")
        if nkv != self.num_heads and self.attention_backend == "ring":
            raise ValueError(
                "GQA (num_kv_heads != num_heads) is not supported by the "
                "ring backend")
        if self.num_experts < 0:
            raise ValueError(
                f"num_experts must be >= 0, got {self.num_experts}")
        if self.num_experts > 0:
            if self.moe_impl not in ("dropless", "capacity"):
                raise ValueError(
                    "moe_impl must be 'dropless' or 'capacity', got "
                    f"{self.moe_impl!r}")
            if not (1 <= self.moe_top_k <= self.num_experts):
                raise ValueError(
                    f"moe_top_k ({self.moe_top_k}) must be in "
                    f"[1, num_experts={self.num_experts}]")
            if self.moe_layer_freq < 1:
                raise ValueError(
                    f"moe_layer_freq must be >= 1, got "
                    f"{self.moe_layer_freq}")
            if self.scan_layers and self.moe_layer_freq != 1:
                raise ValueError(
                    "scan_layers requires homogeneous layers: "
                    f"moe_layer_freq={self.moe_layer_freq} needs "
                    "scan_layers=False (or set moe_layer_freq=1)")

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def ffn(self) -> int:
        return self.ffn_hidden_size or 4 * self.hidden_size

    def is_moe_layer(self, i: int) -> bool:
        """Layer ``i`` runs the MoE MLP (every ``moe_layer_freq``-th
        layer, counting so freq 2 puts MoE on the odd layers)."""
        return (self.num_experts > 0
                and i % self.moe_layer_freq == self.moe_layer_freq - 1)

    def moe_cfg(self):
        """The :class:`~apex_tpu.moe.MoEConfig` this config's MoE
        layers run."""
        from apex_tpu.moe import MoEConfig

        return MoEConfig(
            hidden_size=self.hidden_size,
            ffn_hidden_size=self.ffn,
            num_experts=self.num_experts,
            top_k=self.moe_top_k,
            capacity_factor=self.moe_capacity_factor,
            dtype=self.dtype,
            param_dtype=self.param_dtype)

    # GPT-2 345M (BASELINE configs[3]: ref run_gpt_minimal_test.py)
    @staticmethod
    def gpt2_345m(**kw) -> "GPTConfig":
        return GPTConfig(hidden_size=1024, num_layers=24, num_heads=16,
                         max_seq_len=1024, **kw)


def _flash(cfg: "GPTConfig", q, k, v, *, kv_segment_ids=None, **kw):
    """``flash_attention`` over (b, heads, s, head_dim) arrays.

    Every (batch, head) pair is independent, so under an armed GSPMD
    mesh the kernel runs per shard of them (mesh/annotate.py
    ``on_shards`` — the compiler cannot partition a Mosaic kernel
    itself): batch on the ``batch`` axis, heads on ``model``, where the
    column-parallel qkv left them. A contiguous slice of q heads and
    the same slice of kv heads are whole GQA groups (``__call__``)."""
    from apex_tpu.mesh.mesh import BATCH_AXIS, MODEL_AXIS
    from apex_tpu.ops.attention import flash_attention

    bh = P(BATCH_AXIS, MODEL_AXIS, None, None)
    args, specs = [q, k, v], [bh, bh, bh]
    if kv_segment_ids is not None:
        args.append(kv_segment_ids)
        specs.append(P(BATCH_AXIS, None))

    def attend(q, k, v, kv_seg=None):
        return flash_attention(q, k, v, kv_segment_ids=kv_seg,
                               impl=cfg.softmax_impl, **kw)

    island = _gspmd.on_shards(attend, cfg.softmax_impl, tuple(specs), bh)
    if island is not attend and kw.get("dropout_rate", 0.0) > 0.0:
        # the mask is hashed from shard-local (batch, head) indices:
        # every shard would draw the same one
        raise NotImplementedError(
            "attention dropout inside the flash kernel is not supported "
            "on a >1-device GSPMD mesh")
    return island(*args)


def _paged_specs():
    """Where the paged pools, the block tables and a gathered context
    lie under the mesh: kv heads on ``model``, lanes on ``batch``."""
    from apex_tpu.mesh.mesh import BATCH_AXIS, MODEL_AXIS

    return (P(None, None, None, MODEL_AXIS, None), P(BATCH_AXIS, None),
            P(BATCH_AXIS, MODEL_AXIS, None, None))


def _gather_ctx(cfg: "GPTConfig", pools, layer, tables, lens, into):
    """Layer ``layer`` of the paged ``pools`` (K and V, each (layers,
    blocks, block_size, kv_heads, head_dim)) through the block
    ``tables`` (b, w), as K and V in ``_flash``'s (b, kv_heads, w *
    block_size, head_dim) layout,
    as far as each lane's ``lens`` (b,) and into the pair ``into``
    (``_zero_ctx``'s or the last layer's): ``ops/kv_gather.py``, one
    pass over the live context. Lanes and kv heads are independent, so
    under an armed GSPMD mesh the kernel runs per shard of them, as
    ``_flash``'s does."""
    from apex_tpu.ops.kv_gather import kv_gather

    n = len(pools)

    def gather(*args):
        return kv_gather(args[:n], *args[n:n + 3], args[n + 3:],
                         impl=cfg.softmax_impl)

    pool, rows, ctx = _paged_specs()
    return _gspmd.on_shards(
        gather, cfg.softmax_impl,
        (pool,) * n + (P(), rows, P(rows[0])) + (ctx,) * len(into),
        (ctx,) * n)(*pools, layer, tables, lens, *into)


def _zero_ctx(cfg: "GPTConfig", pools, tables):
    """What a program call's first layer gathers its context into
    (``ops/kv_gather.py`` ``zero_context``), made per shard like the
    gather that writes into it."""
    from apex_tpu.ops.kv_gather import zero_context

    def zero(pool, tables):
        # zero_context reads the pools' count, shape and type alone:
        # one of them is all the island is handed
        return zero_context((pool,) * len(pools), tables,
                            impl=cfg.softmax_impl)

    pool, rows, ctx = _paged_specs()
    return _gspmd.on_shards(
        zero, cfg.softmax_impl, (pool, rows), ctx)(pools[0], tables)


class _LayerNorm(nn.Module):
    """``FusedLayerNorm``'s parameters and kernel over the seq-major
    (s, b, hidden) interior. Rows are independent, so under an armed
    GSPMD mesh the kernel runs per shard of them (``on_shards``, as
    ``_flash``): batch on the ``batch`` axis, where ``constrain_hidden``
    left it, hidden whole, scale and bias replicated."""

    hidden_size: int

    @nn.compact
    def __call__(self, x):
        from apex_tpu.mesh.mesh import BATCH_AXIS

        shape = (self.hidden_size,)
        w = self.param("scale", nn.initializers.ones, shape, jnp.float32)
        b = self.param("bias", nn.initializers.zeros, shape, jnp.float32)
        rows = P(None, BATCH_AXIS, None)
        return _gspmd.on_shards(
            fused_layer_norm, None, (rows, P(), P()), rows)(x, w, b)


class ParallelAttention(nn.Module):
    """Self attention: column-parallel fused QKV, causal fused softmax,
    row-parallel output projection (ref standalone_transformer_lm.py
    ParallelAttention).

    Serving hooks (apex_tpu/serving, docs/serving.md):

    - ``return_kv=True`` additionally returns this call's K/V in the
      kernel ``(b, kv_local, s, head_dim)`` layout — what a prefill
      step writes into the paged cache — and, third, what the next
      layer gathers its context into (``()`` without ``kv_ctx``).
    - ``kv_ctx=(layer, into, (k_pool, v_pool), tables, ctx_lens[, win])``
      is the cached path (``models/cached_attention.py``; with
      ``attention_window`` the layer gathers through ``win``, the tail
      of each lane's table, and masks by true positions). ``into`` is
      the (K, V) this layer gathers into: ``_zero_ctx``'s for the
      first, then the last layer's third result.
      ``k_pool``/``v_pool`` are the paged pools, whole
      (layers, blocks, block_size, kv_local, head_dim), ``layer`` this
      layer's index into them, ``tables`` (b, w) the block tables and
      ``ctx_lens`` (b,) how many cached positions of each lane are
      written. The layer gathers its own context (``_gather_ctx``),
      straight into the kernel's (b, kv_local, L, head_dim) layout
      with ``L = w * block_size``; per-sequence lengths ride the flash
      kernel's segment-id masking, so no causal geometry is hard-wired
      to the input shape.
    - With ``s == 1`` (decode) the token's own K/V goes INTO its slot
      ``ctx_lens[b]`` of the gathered context — the slot
      ``append_kv`` writes after the layers have run — and the keys
      ``j <= ctx_lens[b]`` are live: exactly ``L`` keys, nothing
      concatenated, nothing padded.
    - With ``s > 1`` the same hook is the chunk-resumable prefill
      path (chunked prefill, docs/serving.md): the s chunk tokens
      attend the gathered context (its first ``ctx_lens[b]`` slots)
      PLUS themselves causally, via the flash kernel's ``sk > sq``
      causal offset — key layout ``[ctx | chunk]``, query i sees key
      slot j iff ``j <= i + L``, and the per-lane segment ids drop the
      unwritten context tail.
    """

    config: GPTConfig

    @nn.compact
    def __call__(self, x, *, positions=None, deterministic=True,
                 kv_ctx=None, return_kv=False):
        cfg = self.config
        h = cfg.hidden_size
        inside = _inside_axis(TENSOR_AXIS)
        tp = lax.axis_size(TENSOR_AXIS) if inside else 1
        if cfg.num_heads % tp or cfg.kv_heads % tp:
            raise ValueError(
                f"tensor-parallel size {tp} must divide num_heads "
                f"({cfg.num_heads}) and kv heads ({cfg.kv_heads})")
        heads_local = cfg.num_heads // tp
        kv_local = cfg.kv_heads // tp
        head_dim = h // cfg.num_heads

        # Fused QKV projection, GQA-narrowed: full width is
        # h + 2*kv_heads*head_dim, laid out as one chunk per kv group —
        # [q_0..q_{g-1} | k | v] x kv_heads, g = q heads per kv head.
        # A contiguous TP slice of the output dim is then whole kv
        # groups, so the dense and TP-sharded interpretations of the
        # same weights agree exactly (Megatron's fused-QKV slab trick;
        # for MHA this degenerates to the per-head [q|k|v] layout).
        group = heads_local // kv_local
        qkv = ColumnParallelLinear(
            output_size=(cfg.num_heads + 2 * cfg.kv_heads) * head_dim,
            gather_output=False,
            sequence_parallel_enabled=cfg.sequence_parallel,
            param_dtype=cfg.param_dtype, dtype=cfg.dtype, name="qkv",
        )(x)
        qkv = _gspmd.constrain_column_parallel(qkv)
        s, b = qkv.shape[0], qkv.shape[1]
        qkv = qkv.reshape(s, b, kv_local, (group + 2) * head_dim)
        q, k, v = jnp.split(
            qkv, [group * head_dim, (group + 1) * head_dim], axis=-1)
        # q head g*group+j shares kv head g — matches the flash kernel's
        # `q_head // group` kv index map (ops/attention.py)
        q = q.reshape(s, b, heads_local, head_dim)
        k = k.reshape(s, b, kv_local, head_dim)
        v = v.reshape(s, b, kv_local, head_dim)
        # kernel-layout K/V of THIS call's tokens — the cache payload
        kv_new = (k.transpose(1, 2, 0, 3), v.transpose(1, 2, 0, 3))

        def _out(ctx, held=()):
            out = RowParallelLinear(
                output_size=h, input_is_parallel=True,
                sequence_parallel_enabled=cfg.sequence_parallel,
                param_dtype=cfg.param_dtype, dtype=cfg.dtype, name="proj",
            )(ctx)
            out = _gspmd.constrain_hidden(out)
            return (out, kv_new, held) if return_kv else out

        if kv_ctx is not None:
            # the cached paths: queries against this layer's gathered
            # cache prefix + themselves. Validity is data (ctx_lens),
            # not block geometry, so every sequence in the batch may
            # sit at a different length; masked-out slots are the trash
            # block's garbage and padded tail (serving/kv_cache.py).
            if cfg.attention_backend == "ring":
                raise ValueError(
                    "kv_ctx decode is not supported by the ring backend")
            from apex_tpu.models.cached_attention import cached_attention

            # tests read the gathered context back through the sow
            # (a no-op unless "intermediates" is mutable)
            ctx, held = cached_attention(
                q.transpose(1, 2, 0, 3), *kv_new, kv_ctx,
                flash=lambda *a, **kw: _flash(cfg, *a, **kw),
                gather=lambda *a: _gather_ctx(cfg, *a),
                dtype=cfg.dtype, window=cfg.attention_window,
                sow=lambda kv: self.sow("intermediates", "kv_ctx", kv))
            ctx = ctx.transpose(2, 0, 1, 3).reshape(
                s, b, heads_local * head_dim)
            return _out(ctx, held)

        if cfg.attention_backend in ("flash", "ring"):
            # (s, b, heads, d) -> (b, heads, s, d)
            qb, kb, vb = (t.transpose(1, 2, 0, 3) for t in (q, k, v))
            if cfg.attention_backend == "ring":
                from apex_tpu.transformer.context_parallel import (
                    ring_attention,
                )
                ctx = ring_attention(
                    qb, kb, vb, causal=True,
                    q_positions=positions, kv_positions=positions,
                    impl=cfg.softmax_impl)
            else:
                drop = (cfg.attention_dropout
                        if cfg.attention_dropout > 0.0 and not deterministic
                        else 0.0)
                ctx = _flash(
                    cfg, qb, kb, vb, causal=True,
                    window_size=cfg.attention_window,
                    dropout_rate=drop,
                    dropout_rng=(self.make_rng("dropout")
                                 if drop > 0.0 else None))
            ctx = ctx.transpose(2, 0, 1, 3).reshape(
                s, b, heads_local * head_dim)
            return _out(ctx)

        # softmax backend materializes (s, s) scores; share kv heads by
        # broadcast (the O(S^2) buffer dominates memory here anyway)
        if kv_local != heads_local:
            rep = heads_local // kv_local
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)

        # (b*heads, s, d)
        def to_bhsd(t):
            return t.transpose(1, 2, 0, 3).reshape(b * heads_local, s, head_dim)

        q, k, v = to_bhsd(q), to_bhsd(k), to_bhsd(v)
        scores = jnp.einsum(
            "bsd,btd->bst", q, k, preferred_element_type=jnp.float32
        ) / jnp.sqrt(head_dim).astype(jnp.float32)
        probs = FusedScaleMaskSoftmax(
            attn_mask_type=AttnMaskType.causal, impl=cfg.softmax_impl
        )(scores.reshape(b, heads_local, s, s).astype(cfg.dtype))
        if cfg.attention_dropout > 0.0 and not deterministic:
            probs = nn.Dropout(rate=cfg.attention_dropout)(
                probs, deterministic=False
            )
        ctx = jnp.einsum(
            "bhst,bhtd->bhsd", probs,
            v.reshape(b, heads_local, s, head_dim),
            preferred_element_type=jnp.float32,
        ).astype(cfg.dtype)
        # (b, hl, s, d) -> (s, b, hl*d)
        ctx = ctx.transpose(2, 0, 1, 3).reshape(s, b, heads_local * head_dim)
        return _out(ctx)


class ParallelMLP(nn.Module):
    """Column(4h, no gather) -> gelu -> Row(h) (ref ParallelMLP)."""

    config: GPTConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        hcol = ColumnParallelLinear(
            output_size=cfg.ffn, gather_output=False,
            sequence_parallel_enabled=cfg.sequence_parallel,
            param_dtype=cfg.param_dtype, dtype=cfg.dtype, name="fc1",
        )(x)
        hcol = _gspmd.constrain_column_parallel(hcol)
        hcol = jax.nn.gelu(hcol, approximate=True)
        return _gspmd.constrain_hidden(RowParallelLinear(
            output_size=cfg.hidden_size, input_is_parallel=True,
            sequence_parallel_enabled=cfg.sequence_parallel,
            param_dtype=cfg.param_dtype, dtype=cfg.dtype, name="fc2",
        )(hcol))


class GPTLayer(nn.Module):
    """Pre-LN transformer block (ref ParallelTransformerLayer).

    ``kv_ctx``/``return_kv`` pass through to
    :class:`ParallelAttention` (the serving decode/prefill hooks).

    ``moe`` selects the MLP: None lets the config decide (every layer
    when ``num_experts>0`` with ``moe_layer_freq=1`` — the scan case);
    the unrolled :class:`GPTModel` path passes
    ``cfg.is_moe_layer(i)`` explicitly. The MoE MLP keeps the dense
    block's ``mlp`` submodule name, so a dense config's param tree is
    untouched (docs/moe.md)."""

    config: GPTConfig
    moe: Optional[bool] = None

    @nn.compact
    def __call__(self, x, *, positions=None, deterministic=True,
                 kv_ctx=None, return_kv=False):
        cfg = self.config
        # each half of the block under its part of the model
        # (telemetry.compiled.PARTS), norm and residual with it
        with jax.named_scope("attention"):
            a = ParallelAttention(cfg, name="attention")(
                _LayerNorm(cfg.hidden_size, name="input_norm")(x),
                positions=positions, deterministic=deterministic,
                kv_ctx=kv_ctx, return_kv=return_kv,
            )
            if return_kv:
                a, kv, held = a
            if cfg.hidden_dropout > 0.0 and not deterministic:
                a = nn.Dropout(rate=cfg.hidden_dropout)(
                    a, deterministic=False)
            x = x + a
        use_moe = (self.moe if self.moe is not None
                   else cfg.is_moe_layer(0) and cfg.moe_layer_freq == 1)
        # (a GPT block's MoE MLP counts as ``mlp`` too: its module is
        # named so, and the innermost name on a path is its part)
        with jax.named_scope("mlp"):
            if use_moe:
                from apex_tpu.moe import MoEMLP

                m = MoEMLP(cfg.moe_cfg(), impl=cfg.moe_impl, name="mlp")(
                    _LayerNorm(cfg.hidden_size, name="post_norm")(x)
                )
            else:
                m = ParallelMLP(cfg, name="mlp")(
                    _LayerNorm(cfg.hidden_size, name="post_norm")(x)
                )
            if cfg.hidden_dropout > 0.0 and not deterministic:
                m = nn.Dropout(rate=cfg.hidden_dropout)(
                    m, deterministic=False)
            y = x + m
        return (y, kv, held) if return_kv else y


class _GPTScanBlock(nn.Module):
    """scan body: carry = hidden states; broadcast inputs = positions.
    ``deterministic`` is a static module attribute so the dropout
    branch stays Python-level (no traced bool inside the scan)."""

    config: GPTConfig
    deterministic: bool = True

    @nn.compact
    def __call__(self, x, positions):
        y = GPTLayer(self.config, name="layer")(
            x, positions=positions, deterministic=self.deterministic)
        return y, None


class _GPTScanBlockKV(nn.Module):
    """scan body for the serving paths: same ``layers/layer`` param
    tree as :class:`_GPTScanBlock` (the two bodies are
    checkpoint-compatible), but each layer additionally reads its own
    context out of the paged pools — ``layer`` is the scanned input;
    the pools, block tables and lengths in ``kv_ctx`` are the same
    for every layer (None for prefill) — and emits its new K/V as a
    stacked scan output. The carry holds, beside the hidden states,
    the pair the next layer gathers its context into
    (``cached_attention``'s ``into``)."""

    config: GPTConfig
    deterministic: bool = True

    @nn.compact
    def __call__(self, carry, layer, kv_ctx, positions):
        x, into = carry
        y, kv, into = GPTLayer(self.config, name="layer")(
            x, positions=positions, deterministic=self.deterministic,
            kv_ctx=None if kv_ctx is None else (layer, into, *kv_ctx),
            return_kv=True)
        return (y, into), kv


class GPTModel(nn.Module):
    """Full GPT LM. Input token ids (b, s); returns vocab-parallel
    logits in (s, b, vocab[/tp]) layout (Megatron sbh convention)."""

    config: GPTConfig

    @nn.compact
    def __call__(self, tokens, *, positions=None, deterministic=True,
                 kv_ctx=None, return_kv=False):
        """``positions`` int32 override the default ``arange(s)``:
        shape (s,) for one shared schedule (context-sharded sequences,
        attention_backend="ring") or (b, s) per-sequence (the serving
        decode path, where every sequence sits at its own offset) — a
        single-token forward at position t needs only ``positions`` and
        the cache, never the full prefix.

        ``kv_ctx=((k_pool, v_pool), tables, ctx_lens[, win])`` — the paged
        pools (num_layers, blocks, block_size, kv_heads, head_dim),
        the block tables (b, w) and the cached length of each lane
        (b,) — runs the cached paths: every layer gathers its own
        context out of the pools, which are read and never written,
        sliced or carried here (:class:`ParallelAttention`).
        ``return_kv=True`` additionally returns the per-layer K/V of
        this call, stacked (num_layers, b, kv_heads, s, head_dim) —
        both are the serving tier's hooks (apex_tpu/serving)."""
        cfg = self.config
        b, s = tokens.shape
        emb = VocabParallelEmbedding(
            num_embeddings=cfg.vocab_size, embedding_dim=cfg.hidden_size,
            param_dtype=cfg.param_dtype, dtype=cfg.dtype, name="embedding",
        )
        with jax.named_scope("embed"):
            x = emb(tokens)                               # (b, s, h)
            pos = self.param(
                "position_embedding",
                nn.initializers.normal(stddev=0.02),
                (cfg.max_seq_len, cfg.hidden_size), cfg.param_dtype,
            )
            if positions is None:
                pos_emb = pos[None, :s]
            else:
                positions = jnp.asarray(positions)
                pos_emb = jnp.take(pos, positions, axis=0)
                if positions.ndim == 1:
                    pos_emb = pos_emb[None]               # (1, s, h)
            x = _gspmd.constrain_batch_major(x + pos_emb.astype(cfg.dtype))
            x = _gspmd.constrain_hidden(x.transpose(1, 0, 2))  # (s, b, h)

        if cfg.sequence_parallel and _inside_axis(TENSOR_AXIS):
            from apex_tpu.transformer.tensor_parallel import (
                scatter_to_sequence_parallel_region,
            )
            x = scatter_to_sequence_parallel_region(x)

        serving = return_kv or kv_ctx is not None
        kvs = None
        # what the first layer gathers its context into, and each
        # later one what the last gave back (cached_attention)
        into = ()
        if kv_ctx is not None:
            from apex_tpu.models.cached_attention import first_context

            into = first_context(
                kv_ctx, window=cfg.attention_window,
                zero=lambda *a: _zero_ctx(cfg, *a))
        if cfg.scan_layers:
            if serving:
                scan = nn.scan(
                    _GPTScanBlockKV,
                    variable_axes={"params": 0, "intermediates": 0},
                    split_rngs={"params": True, "dropout": True},
                    length=cfg.num_layers,
                    in_axes=(0, nn.broadcast, nn.broadcast),
                )
                # the scan's own work (stacking what a layer returns,
                # its counter) is no part's: it reads ``layer_scan``
                with jax.named_scope("layer_scan"):
                    (x, _), kvs = scan(cfg, deterministic, name="layers")(
                        (x, into),
                        jnp.arange(cfg.num_layers, dtype=jnp.int32),
                        kv_ctx, positions)
            else:
                scan = nn.scan(
                    _GPTScanBlock,
                    variable_axes={"params": 0, "intermediates": 0},
                    split_rngs={"params": True, "dropout": True},
                    length=cfg.num_layers,
                    in_axes=nn.broadcast,
                )
                with jax.named_scope("layer_scan"):
                    x, _ = scan(cfg, deterministic, name="layers")(
                        x, positions)
        else:
            per_layer = []
            for i in range(cfg.num_layers):
                ctx = None if kv_ctx is None else (i, into, *kv_ctx)
                x = GPTLayer(cfg, moe=cfg.is_moe_layer(i),
                             name=f"layer_{i}")(
                    x, positions=positions, deterministic=deterministic,
                    kv_ctx=ctx, return_kv=serving)
                if serving:
                    x, kv, into = x
                    per_layer.append(kv)
            if serving:
                with jax.named_scope("cache"):
                    kvs = (jnp.stack([kv[0] for kv in per_layer]),
                           jnp.stack([kv[1] for kv in per_layer]))
        with jax.named_scope("head"):
            x = _LayerNorm(cfg.hidden_size, name="final_norm")(x)

        if cfg.sequence_parallel and _inside_axis(TENSOR_AXIS):
            from apex_tpu.transformer.tensor_parallel import (
                gather_from_sequence_parallel_region,
            )
            x = gather_from_sequence_parallel_region(
                x, tensor_parallel_output_grad=True
            )

        # tied LM head: logits = x @ E^T over the local vocab shard
        # (ref parallel_lm_logits: copy op so dL/dx is allreduced)
        if _inside_axis(TENSOR_AXIS):
            from apex_tpu.transformer.tensor_parallel import (
                copy_to_tensor_model_parallel_region,
            )
            x = copy_to_tensor_model_parallel_region(x)
        table = emb.variables["params"]["embedding"]
        with jax.named_scope("head"):
            logits = _gspmd.constrain_logits(jnp.einsum(
                "sbh,vh->sbv", x.astype(jnp.float32),
                table.astype(jnp.float32),
            ))
        if return_kv:
            return logits, kvs
        return logits


def gpt_loss_fn(logits, labels, axis_name: str = TENSOR_AXIS):
    """Mean CE over tokens; vocab-parallel when inside the mesh.

    logits: (s, b, vocab[/tp]) ; labels: (b, s)
    """
    with jax.named_scope("loss"):
        labels_sb = labels.transpose(1, 0)
        if _inside_axis(axis_name):
            losses = vocab_parallel_cross_entropy(logits, labels_sb,
                                                  axis_name=axis_name)
        else:
            lse = jax.scipy.special.logsumexp(logits, axis=-1)
            tgt = jnp.take_along_axis(logits, labels_sb[..., None],
                                      -1)[..., 0]
            losses = lse - tgt
        return jnp.mean(losses)


# -- partition specs -------------------------------------------------------


def gpt_param_specs(params: Any) -> Any:
    """PartitionSpec tree for a GPTModel param pytree: column kernels
    split on the output dim, row kernels on the input dim, the embedding
    on the vocab dim, everything else replicated."""

    def spec_for(path, leaf):
        names = [str(getattr(k, "key", k)) for k in path]
        joined = "/".join(names)
        if "embedding" in joined and names[-1] == "embedding":
            spec = P(TENSOR_AXIS, None)
        elif ("qkv" in joined or "fc1" in joined) and names[-1] == "kernel":
            spec = P(TENSOR_AXIS, None)
        elif ("qkv" in joined or "fc1" in joined) and names[-1] == "bias":
            spec = P(TENSOR_AXIS)
        elif ("proj" in joined or "fc2" in joined) and names[-1] == "kernel":
            spec = P(None, TENSOR_AXIS)
        elif names[-1] in ("w1", "w2"):
            # MoE expert weights (E, h, ffn) / (E, ffn, h): shard the
            # EXPERT dim on the model axis — expert parallelism rides
            # the same mesh axis tensor parallelism does (docs/moe.md);
            # the router gate stays replicated (falls through to P())
            spec = P(TENSOR_AXIS, None, None)
        else:
            return P()
        if "layers" in names:
            # scan_layers stacks layer params with a leading layer
            # axis; the TP sharding moves one dim to the right
            spec = P(None, *spec)
        return spec

    return jax.tree_util.tree_map_with_path(spec_for, params)
