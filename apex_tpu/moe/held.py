"""An expert layer that is told which experts it holds.

Expert parallelism gives each chip a contiguous share of a layer's
routed experts. The layer here is that chip's part (docs/moe.md "Held
experts"): the router keeps its published width and its experts per
token, every token is routed over ALL the experts, and the chip
computes what its own ``held = (first, count)`` experts add to the
result. Token-expert pairs whose expert lives elsewhere are dropped
before the grouped products, so the products and the expert weights
read scale with the pairs held; among the held nothing is dropped. On
one chip the layer runs without its exchange: what the absent experts
would add is left out, and nothing stands in for them.

The router is one of two, by configuration (``HeldMoEConfig.router``).
The *sigmoid* kind (DeepSeek-V3 / ``afmoe``): scores ``s = sigmoid(x
W_r)`` in float32, the choice by ``s + b`` with ``b`` a per-expert
selection bias that takes no part in the weights, the weights
``s[chosen]`` renormalised over the chosen and scaled. The *softmax*
kind (the Qwen3-MoE family, ``mellum``): ``p = softmax(x W_r)`` over
all the experts in float32, the choice the top-k of ``p``, the weights
``p[chosen]`` renormalised over the chosen; no bias and no scale.
Experts are SiLU-gated three-matrix MLPs; a shared expert of the same
kind, where the model has one, is computed by every chip alike.

``held=None`` is the layer whole: every expert is here, no pair is
dropped, and the grouped products see ``tokens x top_k`` rows.

The held experts' three products take one of two forms, chosen from
the call's static shapes by ``expert_form`` and by nothing else.
*Grouped* (``grouped_experts``): the pairs sorted by expert, three
grouped products over the held pairs (``ops/moe_grouped.py``
``moe_grouped``: ``lax.ragged_dot`` as one Pallas kernel, entered
through one jit so that a program lowers one kernel body a shape, not
one a call site, which every warm start would pay for again), the
results scattered back; it reads only the experts some pair chose,
which is what a prefill or chunk call (hundreds of rows) and a call
with under a pair an expert want. *Dense* (``dense_experts``): every
held expert multiplies every row once, three plain batched matmuls
over weights read where they lie, the unchosen (row, expert) results
dropped by a select; it is
what a decode call with a pair or more an expert and at most 128
rows wants, where nearly every held expert is touched anyway and the
grouped product at a few rows a group runs far under its bytes'
bound. Both compute the same sum, bf16 operands under float32
accumulation with float32 combine weights; the dense form rounds less
on the way.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.ops.moe_grouped import moe_grouped


ROUTERS = ("sigmoid", "softmax")


@dataclasses.dataclass(frozen=True)
class HeldMoEConfig:
    hidden_size: int
    expert_ffn_size: int
    num_experts: int                 # the router's width
    top_k: int
    # (first, count): the contiguous experts this chip holds; None = all
    held: Optional[Tuple[int, int]] = None
    route_scale: float = 1.0
    shared_ffn_size: int = 0         # 0 = no shared expert
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    router: str = "sigmoid"          # one of ROUTERS

    def __post_init__(self):
        if self.router not in ROUTERS:
            raise ValueError(f"router is one of {ROUTERS}, "
                             f"got {self.router!r}")
        if self.router == "softmax" and self.route_scale != 1.0:
            raise ValueError("the softmax router has no route_scale")
        first, count = self.held_range
        if not (0 <= first and count >= 1
                and first + count <= self.num_experts):
            raise ValueError(
                f"held experts {self.held} do not lie within the router's "
                f"{self.num_experts}")
        if not (1 <= self.top_k <= self.num_experts):
            raise ValueError(f"top_k ({self.top_k}) must be in "
                             f"[1, num_experts={self.num_experts}]")

    @property
    def held_range(self) -> Tuple[int, int]:
        return self.held if self.held is not None else (0, self.num_experts)


def _router_logits(x, gate_kernel):
    """The router's product in float32 at the highest precision (a
    TPU's default float32 product is a bf16 one): the choice is a
    comparison of scores, and those at the cut lie close."""
    return jnp.dot(x.astype(jnp.float32), gate_kernel.astype(jnp.float32),
                   precision=lax.Precision.HIGHEST)


def sigmoid_router(x, gate_kernel, select_bias, k: int, *,
                   route_scale: float = 1.0):
    """x (n, h), gate (h, E), bias (E,) -> (weights (n, k) float32,
    expert ids (n, k) int32, scores (n, E) float32)."""
    scores = jax.nn.sigmoid(_router_logits(x, gate_kernel))
    _, ids = lax.top_k(scores + select_bias.astype(jnp.float32), k)
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    weights = chosen / (chosen.sum(-1, keepdims=True) + 1e-20) * route_scale
    return weights, ids.astype(jnp.int32), scores


def softmax_router(x, gate_kernel, k: int):
    """x (n, h), gate (h, E) -> (weights (n, k) float32, expert ids
    (n, k) int32, probabilities (n, E) float32): the softmax over ALL
    the experts first, then the top-k of it, renormalised over the
    chosen."""
    probs = jax.nn.softmax(_router_logits(x, gate_kernel), axis=-1)
    chosen, ids = lax.top_k(probs, k)
    weights = chosen / chosen.sum(-1, keepdims=True)
    return weights, ids.astype(jnp.int32), probs


#: the most rows a dense expert call may have. The dense products do
#: ``rows`` FLOPs a byte of bf16 weights and a v5e's ridge is 240 FLOP/B
#: (197 TFLOP/s over 819 GB/s), so up to 128 rows the MXU's time stays
#: under about half of the bytes' time; every prefill and chunk program
#: of the benchmark's cells has 512 rows or more.
DENSE_MAX_ROWS = 128


def expert_form(rows: int, top_k: int, num_experts: int) -> str:
    """Which form the held experts' three products take in a call of
    ``rows`` tokens, each routed to ``top_k`` of the router's
    ``num_experts``: ``"dense"`` or ``"grouped"``. A function of the
    call's static shapes alone; no field, variable or argument
    overrides it.

    *dense* where ``rows * top_k >= num_experts`` and ``rows <=
    DENSE_MAX_ROWS``; *grouped* otherwise.

    With a mean of one pair or more an expert, at least 63% of the
    held experts are touched in expectation (``1 - 1/e``; 86% at two
    pairs), so a product over every held expert reads at most ~1.6
    times the bytes a grouped one must, and reads them as a plain
    batched matmul, where the grouped product at a few rows a group
    runs far under its bytes' bound. Below one pair an expert the
    grouped form's skipping of the untouched experts wins by
    multiples. The second edge is ``DENSE_MAX_ROWS``'s: the dense
    form computes ``num_experts / top_k`` times the pairs' work, which
    costs nothing while the weights' bytes bound the call.

    Measured, ms a layer's three products alone, grouped / dense (one
    v5e, bf16, near-even routing; PERF.md section 5, PR 36):
    16 rows x top-8 over 64, all held, 793 MB: 3.72 / 1.12 (dense at
    86% of the bytes' bound); 64 x top-10 over 72, 9 held, 170 MB:
    0.49 / 0.29; 16 x top-4 over 256, 32 held, 1812 MB (a quarter of a
    pair an expert): 0.44 / 2.51; at 128 rows the first two read
    5.52 / 1.12 and 0.63 / 0.36. The grouped products by
    ``moe_grouped`` at the prefill-type shapes, ms, ``ragged_dot`` ->
    kernel (PR 39; docs/moe.md has the table): 1024 rows x top-8 over
    64, 7.69 -> 1.78; x top-10 over 72, 9 held, 0.92 -> 0.44; x top-4
    over 256, 32 held, 5.86 -> 2.56; Trinity's decode shape (16 rows)
    0.54 -> 0.49, which leaves it grouped by this rule."""
    if rows * top_k >= num_experts and rows <= DENSE_MAX_ROWS:
        return "dense"
    return "grouped"


def held_experts(x, weights, ids, w_gate, w_up, w_down,
                 held: Tuple[int, int], dtype, *, num_experts: int):
    """What the held experts add: ``sum_e w_e Expert_e(x)`` over the
    chosen experts ``e`` in ``[first, first + count)``.

    x (n, h); weights / ids (n, k), the ids over the router's
    ``num_experts``; w_gate / w_up (count, h, f) and w_down (count, f,
    h), the held experts' own. The form of the three products is
    ``expert_form``'s, from ``n``, ``k`` and ``num_experts``."""
    form = expert_form(x.shape[0], ids.shape[1], num_experts)
    return EXPERT_FORMS[form](x, weights, ids, w_gate, w_up, w_down, held,
                              dtype)


def grouped_experts(x, weights, ids, w_gate, w_up, w_down,
                    held: Tuple[int, int], dtype):
    """``held_experts`` by grouped products. Pairs are sorted by
    local expert with the absent ones last, and the group sizes count
    the held pairs only: the grouped products (``moe_grouped``, on a
    TPU a kernel that visits the row tiles its group sizes cover and
    reads each touched expert's weights once) stop there."""
    first, count = held
    n, h = x.shape
    k = ids.shape[1]
    local = ids - first
    flat = jnp.where((local >= 0) & (local < count), local,
                     count).reshape(-1)                   # (n*k,)
    order = jnp.argsort(flat, stable=True)
    inv = jnp.argsort(order)
    rows = x.astype(dtype)[order // k]                    # (n*k, h) sorted
    sizes = jnp.bincount(flat, length=count + 1)[:count].astype(jnp.int32)
    with jax.named_scope("moe_experts"), jax.named_scope("grouped"):
        gate = moe_grouped(rows, w_gate.astype(dtype), sizes)
        up = moe_grouped(rows, w_up.astype(dtype), sizes)
        out = moe_grouped(jax.nn.silu(gate) * up, w_down.astype(dtype),
                          sizes)
    # rows past the held pairs were never computed: whatever lies there
    # is dropped by the mask, not multiplied by a zero weight
    w_sorted = weights.reshape(-1)[order]
    out = jnp.where((flat[order] < count)[:, None],
                    out.astype(jnp.float32) * w_sorted[:, None], 0.0)
    return out[inv].reshape(n, k, h).sum(axis=1).astype(dtype)


def dense_experts(x, weights, ids, w_gate, w_up, w_down,
                  held: Tuple[int, int], dtype):
    """``held_experts`` by batched dense products: every held expert
    multiplies every row once, the weights read where they lie as
    three plain batched matmuls, and a row's result is the sum over
    its chosen, held experts. No sort, no gather of rows, no scatter
    back. Operands in ``dtype``, accumulation and the combine weights
    in float32, one cast at the end."""
    first, count = held
    f32 = jnp.float32
    # (n, k, count): pair j of row i chose held expert e. A pair whose
    # expert lives elsewhere matches none
    hit = (ids - first)[:, :, None] == jnp.arange(count, dtype=ids.dtype)
    chosen = hit.any(axis=1).T                                # (count, n)
    combine = jnp.where(hit, weights.astype(f32)[:, :, None],
                        0.0).sum(axis=1).T                    # (count, n)
    rows = jnp.broadcast_to(x.astype(dtype), (count, *x.shape))
    with jax.named_scope("moe_experts"), jax.named_scope("dense"):
        gate = jnp.einsum("enh,ehf->enf", rows, w_gate.astype(dtype),
                          preferred_element_type=f32)
        up = jnp.einsum("enh,ehf->enf", rows, w_up.astype(dtype),
                        preferred_element_type=f32)
        out = jnp.einsum("enf,efh->enh",
                         (jax.nn.silu(gate) * up).astype(dtype),
                         w_down.astype(dtype), preferred_element_type=f32)
    # an expert a row did not choose was computed all the same:
    # whatever lies there is dropped by the mask, not multiplied by a
    # zero weight
    out = jnp.where(chosen[:, :, None], out * combine[:, :, None], 0.0)
    return out.sum(axis=0).astype(dtype)


EXPERT_FORMS = {"dense": dense_experts, "grouped": grouped_experts}


def gated_mlp(x, w_gate, w_up, w_down, dtype):
    """``(silu(x W_gate) * (x W_up)) W_down``."""
    x = x.astype(dtype)
    return jnp.dot(jax.nn.silu(jnp.dot(x, w_gate.astype(dtype)))
                   * jnp.dot(x, w_up.astype(dtype)), w_down.astype(dtype))


class HeldMoEMLP(nn.Module):
    """Router over all experts, the held experts' part of the result,
    and the shared expert (module docstring). Input and output
    ``(..., hidden)``. ``return_routing=True`` also returns ``(weights,
    ids, scores)`` for tests. Applied with ``mutable=["routing"]`` the
    layer also leaves its choice there (``ids`` (n, k) and what it was
    made from, (n, experts): the sigmoid router's ``biased`` scores,
    the softmax router's ``probs``), for a reference that is to be
    given the program's choice; otherwise that costs nothing and
    changes no program."""

    config: HeldMoEConfig

    @nn.compact
    def __call__(self, x, *, return_routing: bool = False):
        cfg = self.config
        h, f = cfg.hidden_size, cfg.expert_ffn_size
        first, count = cfg.held_range
        init = nn.initializers.normal(stddev=0.02)
        gate = self.param("router", init, (h, cfg.num_experts),
                          cfg.param_dtype)
        w_gate = self.param("w_gate", init, (count, h, f), cfg.param_dtype)
        w_up = self.param("w_up", init, (count, h, f), cfg.param_dtype)
        w_down = self.param("w_down", init, (count, f, h), cfg.param_dtype)
        lead = x.shape[:-1]
        softmax = cfg.router == "softmax"
        # the router and the routed experts are the ``experts`` part of
        # the model, the shared expert its ``mlp``
        # (telemetry.compiled.PARTS)
        with jax.named_scope("experts"):
            x2 = x.reshape(-1, h)
            with jax.named_scope("moe_router"):
                if softmax:
                    weights, ids, scores = softmax_router(x2, gate,
                                                          cfg.top_k)
                else:
                    bias = self.param("select_bias", nn.initializers.zeros,
                                      (cfg.num_experts,), jnp.float32)
                    weights, ids, scores = sigmoid_router(
                        x2, gate, bias, cfg.top_k,
                        route_scale=cfg.route_scale)
            if (self.is_mutable_collection("routing")
                    and not self.is_initializing()):
                self.sow("routing", "ids", ids)
                if softmax:
                    self.sow("routing", "probs", scores)
                else:
                    self.sow("routing", "biased",
                             scores + bias.astype(jnp.float32))
            out = held_experts(x2, weights, ids, w_gate, w_up, w_down,
                               (first, count), cfg.dtype,
                               num_experts=cfg.num_experts)
        if cfg.shared_ffn_size:
            fs = cfg.shared_ffn_size
            with jax.named_scope("mlp"):
                out = out + gated_mlp(
                    x2,
                    self.param("shared_gate", init, (h, fs),
                               cfg.param_dtype),
                    self.param("shared_up", init, (h, fs), cfg.param_dtype),
                    self.param("shared_down", init, (fs, h),
                               cfg.param_dtype),
                    cfg.dtype)
        with jax.named_scope("experts"):
            out = out.reshape(*lead, h)
        if return_routing:
            return out, (weights, ids, scores)
        return out


__all__ = ["EXPERT_FORMS", "HeldMoEConfig", "HeldMoEMLP", "dense_experts",
           "expert_form", "gated_mlp", "grouped_experts", "held_experts",
           "sigmoid_router", "softmax_router"]
