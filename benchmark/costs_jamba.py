"""What ``ai21-jamba2-3b`` holds and what a step of it must move, from
the configuration file alone: the arithmetic that ``PERF.md`` and the
cell's ``why`` quote, kept where a test can check it
(``tests/test_benchmark_jamba.py``), and the price of the chunk-call
kernel this configuration brings, ``ops/ssm_scan.py``
(:func:`ssm_scan_call`). Its decode step is ``ops/ssm_step.py``'s
``ssm_step_selective``, priced by ``costs_granite.ssm_step_call`` as
Granite's step is: the pool is the operand with five dimensions, the
lanes lead the first float32 operand with four.
"""

from __future__ import annotations

from typing import Dict, Tuple

from benchmark.costs import ITEMSIZE
from benchmark.drivers.serve_jamba import layer_kinds

BF16, F32 = 2, 4

#: operations a (row, channel, state) of the scan: Delta A, its exp,
#: the decay times S, Delta x B (its product with B), the sum, the
#: product with C and its sum over N; D x and the gate are a channel's
SCAN_OPS = 8.0


def session_state_bytes(config: Dict) -> int:
    """Bytes of one session's recurrent state over the Mamba layers:
    ``S`` (E x N) in float32 and the convolution's carried rows of
    ``x`` in bf16, whatever the session's length."""
    inner = config["mamba_expand"] * config["hidden_size"]
    return layer_kinds(config).count("mamba") * (
        inner * config["mamba_d_state"] * F32
        + (config["mamba_d_conv"] - 1) * inner * BF16)


def kv_bytes_per_token(config: Dict) -> int:
    """Bytes of K and V a token adds, over the layers that have keys."""
    head_dim = config["hidden_size"] // config["num_attention_heads"]
    return (layer_kinds(config).count("attention") * 2
            * config["num_key_value_heads"] * head_dim * BF16)


def parameters(config: Dict) -> Dict[str, int]:
    """Parameters by part: a Mamba mixer, an attention mixer, the dense
    MLP, the tied vocabulary, and ``total``."""
    h = config["hidden_size"]
    inner = config["mamba_expand"] * h
    n, r = config["mamba_d_state"], config["mamba_dt_rank"]
    mamba = (h * 2 * inner                              # in_proj
             + inner * (config["mamba_d_conv"] + 1)     # conv and bias
             + inner * (r + 2 * n) + r + 2 * n          # x_proj, norms
             + r * inner + inner                        # dt_proj, bias
             + inner * n + inner                        # A_log, D
             + inner * h)                               # out_proj
    head_dim = h // config["num_attention_heads"]
    attention = (h * (config["num_attention_heads"]
                      + 2 * config["num_key_value_heads"]) * head_dim
                 + config["num_attention_heads"] * head_dim * h)
    mlp = 3 * h * config["intermediate_size"]
    kinds = layer_kinds(config)
    out = {"mamba": mamba, "attention": attention, "mlp": mlp,
           "vocabulary": config["vocab_size"] * h}
    out["total"] = (kinds.count("mamba") * mamba
                    + kinds.count("attention") * attention
                    + len(kinds) * (mlp + 2 * h) + out["vocabulary"] + h)
    return out


def decode_step_bytes(config: Dict, lanes: int) -> Dict[str, int]:
    """The bytes a decode step of ``lanes`` sessions cannot avoid, by
    part: every lane's state read and written once, the weights."""
    p = parameters(config)
    return {"state": 2 * lanes * session_state_bytes(config),
            "weights": p["total"] * BF16}


def ssm_scan_call(results, operands) -> Tuple[float, float]:
    """(operations, bytes) of one call of the ``ssm_scan`` kernel, from
    the shapes of its results and operands as the trace's HLO text
    gives them: the lanes' states ``(b, E/128, N, 128)`` (the first
    operand, and a result), the rows' ``Delta`` and ``x`` in float32
    and ``z`` ``(b, s, E)``, ``B`` and ``C`` turned ``(b, s/16, N,
    16)``, the decays ``(E/128, N, 128)`` and ``D``; ``y`` ``(b, s,
    E)`` out. What the algorithm needs: every operand read once and
    every result written once (each lane's state read once and written
    once among them), and :data:`SCAN_OPS` operations a row, channel
    and state. The bound ``peaks.json`` knows for this kernel is HBM's;
    it is bound by the vector and transcendental units, whose peaks the
    table does not hold."""
    def size(t, dims):
        n = ITEMSIZE[t]
        for dim in dims:
            n *= dim
        return n

    state = next(d for t, d in operands if len(d) == 4)
    b, s, E = next(d for t, d in operands if len(d) == 3)
    nbytes = sum(size(t, d) for t, d in list(results) + list(operands))
    return SCAN_OPS * b * s * E * state[2], float(nbytes)
