"""What ``glm-4.7-flash``'s cut holds and what a step of it must move,
from the configuration file alone: the arithmetic that ``PERF.md`` and
the configuration's ``why`` quote, kept where a test can check it
(``tests/test_benchmark_glm.py``), and the price of the one kernel
this configuration brings, ``ops/mla_decode.py``
(:func:`mla_decode_call`). The expanded form's flash calls are priced
by ``costs.attention_call`` as it stands (q and k of 256, v of 256).
"""

from __future__ import annotations

import json
import os
from typing import Dict, Tuple

from benchmark.costs import ITEMSIZE

BF16 = 2

with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "configs", "glm-4.7-flash.json")) as _f:
    ROPE = json.load(_f)["qk_rope_head_dim"]


def row_width(config: Dict) -> int:
    """The cached latent row ``[c_kv | k_r | 0]``, padded to whole
    128-lane tiles (``models/decoder.py`` ``MLAConfig.row_width``)."""
    return -(-(config["kv_lora_rank"] + config["qk_rope_head_dim"])
             // 128) * 128


def latent_bytes_per_token(config: Dict) -> int:
    """Bytes of latent rows a token adds, over the cut's layers (every
    layer is a latent one)."""
    return config["num_hidden_layers"] * row_width(config) * BF16


def pool_bytes(config: Dict) -> int:
    """The latent pool: the engine's blocks and the trash block."""
    engine = config["engine"]
    return ((engine["num_blocks"] + 1) * engine["block_size"]
            * latent_bytes_per_token(config))


def parameters(config: Dict) -> Dict[str, int]:
    """Parameters of the cut by part: a latent attention layer, the
    dense MLP, one routed expert, the shared expert, the router (its
    selection bias with it), the vocabulary (embedding and untied
    head); ``total`` as the cut holds them (the held experts only)."""
    h, nh = config["hidden_size"], config["num_attention_heads"]
    ql, kl = config["q_lora_rank"], config["kv_lora_rank"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    attention = (h * ql + ql + ql * nh * (nope + rope) + h * (kl + rope)
                 + kl + kl * nh * (nope + config["v_head_dim"])
                 + nh * config["v_head_dim"] * h)
    expert = 3 * h * config["moe_intermediate_size"]
    width = config["deployment"]["router_width"]
    out = {"attention": attention,
           "dense": 3 * h * config["intermediate_size"],
           "expert": expert, "shared": config["n_shared_experts"] * expert,
           "router": h * width + width,
           "vocabulary": 2 * config["vocab_size"] * h}
    layers = config["num_hidden_layers"]
    dense = config["first_k_dense_replace"]
    out["total"] = (layers * (attention + 2 * h) + dense * out["dense"]
                    + (layers - dense) * (
                        config["n_routed_experts"] * expert + out["shared"]
                        + out["router"])
                    + out["vocabulary"] + h)
    return out


def decode_step_bytes(config: Dict, lanes: int, width: int) -> Dict[str, int]:
    """The bytes a decode step of ``lanes`` sequences at a table of
    ``width`` blocks reads, by part: every weight on a decode token's
    path (latent attention, the dense MLP, the held experts, which the
    dense form reads whole, the shared experts and routers, the head),
    and the latent rows ``mla_decode`` is handed, every one of them."""
    p = parameters(config)
    layers = config["num_hidden_layers"]
    experts = layers - config["first_k_dense_replace"]
    return {
        "attention_weights": layers * p["attention"] * BF16,
        "mlp_weights": (config["first_k_dense_replace"] * p["dense"]
                        + experts * (config["n_routed_experts"] * p["expert"]
                                     + p["shared"] + p["router"])) * BF16,
        "head": p["vocabulary"] // 2 * BF16,
        "latents": (lanes * width * config["engine"]["block_size"]
                    * latent_bytes_per_token(config))}


def _size(t, dims) -> int:
    n = ITEMSIZE[t]
    for dim in dims:
        n *= dim
    return n


def mla_decode_call(results, operands) -> Tuple[float, float]:
    """(operations, bytes) of one call of the ``mla_decode`` kernel, from
    the shapes of its results and operands as the trace's HLO text gives
    them. The latent rows ``(b, 1, L, W)`` are the operand with four
    dimensions, the absorbed query ``(b, heads, W)`` the other bf16
    one, the result ``(b, heads, v)`` with ``v`` the latent's width.
    What the algorithm needs: every row the call is handed read once,
    for key and value both; the query and the lengths read and the
    result written once; for each head and row, ``2 (v + rope)``
    operations for its score and ``2 v`` for its share of the value."""
    rows = next(d for t, d in operands if len(d) == 4)
    out = next(d for t, d in results if len(d) == 3)
    lanes, heads, value = out
    keys = lanes * rows[2]
    flops = 2.0 * heads * keys * (value + ROPE + value)
    nbytes = sum(_size(t, d) for t, d in list(results) + list(operands))
    return flops, float(nbytes)
