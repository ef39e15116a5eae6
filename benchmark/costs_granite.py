"""What ``granite-4.0-h-small``'s cut holds and what a step of it must
move, from the configuration file alone: the arithmetic that
``PERF.md`` and the cell's ``why`` quote, kept where a test can check
it (``tests/test_benchmark_granite.py``), and the price of the one
kernel this configuration brings, ``ops/ssm_step.py``
(:func:`ssm_step_call`). The chunked form is products the compiler
has; ``costs.attention_call`` prices the one attention layer's calls
as it stands.
"""

from __future__ import annotations

from typing import Dict, Tuple

from benchmark.costs import ITEMSIZE

BF16, F32 = 2, 4


def session_state_bytes(config: Dict) -> int:
    """Bytes of one session's recurrent state over the cut's ``mamba``
    layers: ``S`` in float32 and the convolution's carried rows in
    bf16, whatever the session's length."""
    heads, p, n = (config["mamba_n_heads"], config["mamba_d_head"],
                   config["mamba_d_state"])
    conv_dim = heads * p + 2 * config["mamba_n_groups"] * n
    layers = config["layer_types"].count("mamba")
    return layers * (heads * p * n * F32
                     + (config["mamba_d_conv"] - 1) * conv_dim * BF16)


def kv_bytes_per_token(config: Dict) -> int:
    """Bytes of K and V a token adds, over the layers that have keys."""
    head_dim = config["hidden_size"] // config["num_attention_heads"]
    return (config["layer_types"].count("attention") * 2
            * config["num_key_value_heads"] * head_dim * BF16)


def parameters(config: Dict) -> Dict[str, int]:
    """Parameters of the cut by part: a ``mamba`` mixer, an attention
    mixer, one routed expert, the shared MLP, the router, the tied
    vocabulary; ``total`` as the cut holds them (the held experts
    only)."""
    h = config["hidden_size"]
    inner = config["mamba_n_heads"] * config["mamba_d_head"]
    conv_dim = inner + 2 * config["mamba_n_groups"] * config["mamba_d_state"]
    mamba = (h * (inner + conv_dim + config["mamba_n_heads"])
             + conv_dim * (config["mamba_d_conv"] + 1)
             + 3 * config["mamba_n_heads"] + inner + inner * h)
    head_dim = h // config["num_attention_heads"]
    attention = (h * (config["num_attention_heads"]
                      + 2 * config["num_key_value_heads"]) * head_dim
                 + config["num_attention_heads"] * head_dim * h)
    expert = 3 * h * config["intermediate_size"]
    shared = 3 * h * config["shared_intermediate_size"]
    router = h * config["deployment"]["router_width"]
    out = {"mamba": mamba, "attention": attention, "expert": expert,
           "shared": shared, "router": router,
           "vocabulary": config["vocab_size"] * h}
    types = config["layer_types"]
    out["total"] = (types.count("mamba") * mamba
                    + types.count("attention") * attention
                    + len(types) * (config["num_local_experts"] * expert
                                    + shared + router + 2 * h)
                    + out["vocabulary"] + h)
    return out


def decode_step_bytes(config: Dict, lanes: int) -> Dict[str, int]:
    """The bytes a decode step of ``lanes`` sessions cannot avoid, by
    part: every lane's state read and written once, the ``mamba``
    mixers' weights, the held experts' and the shared MLPs' weights,
    the attention mixer's, the tied head."""
    p = parameters(config)
    types = config["layer_types"]
    return {
        "state": 2 * lanes * session_state_bytes(config),
        "mamba_weights": types.count("mamba") * p["mamba"] * BF16,
        "expert_weights": len(types) * BF16 * (
            config["num_local_experts"] * p["expert"] + p["shared"]
            + p["router"]),
        "attention_weights": types.count("attention") * p["attention"]
        * BF16,
        "head": p["vocabulary"] * BF16}


def ssm_step_call(results, operands) -> Tuple[float, float]:
    """(operations, bytes) of one call of the ``ssm_step`` kernel, from
    the shapes of its results and operands as the trace's HLO text
    gives them. The pool ``(slots, layers, H, P, N)`` is the operand
    with five dimensions, and the lanes are the leading dimension of
    the first float32 operand. What the algorithm needs: each LANE's
    state of one layer read once and written once (not the pool: the
    other slots and layers are not touched), every other operand read
    and the other result written once, and 6 H P N operations a lane
    (``a S``, ``(d x) B``, their sum, ``S' C`` and its sum over N)."""
    def size(t, dims):
        n = ITEMSIZE[t]
        for dim in dims:
            n *= dim
        return n

    pool_type, pool = next((t, d) for t, d in operands if len(d) == 5)
    lanes = next(d for t, d in operands if t == "f32" and len(d) == 4)[0]
    state = size(pool_type, pool[2:])
    nbytes = 2 * lanes * state
    for t, dims in list(results) + list(operands):
        if list(dims) != list(pool):
            nbytes += size(t, dims)
    return 6.0 * lanes * state / ITEMSIZE[pool_type], float(nbytes)
