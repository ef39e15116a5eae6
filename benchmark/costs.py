"""The operations and bytes the algorithm needs, from shapes alone.

Kept with the benchmark so that no PR that claims a gain can change the
yardstick. A roofline share is the least time the chip could take (the
larger of operations over peak FLOP/s and bytes over peak bytes/s)
over the time the kernel took.
"""

from __future__ import annotations

from typing import Dict, Tuple


def parameters(config: Dict) -> int:
    """Parameters of a GPT-2 style decoder, the tied embedding counted
    once and at the source's vocabulary."""
    h, layers, ffn = config["n_embd"], config["n_layer"], config["n_inner"]
    per_layer = (3 * h * h + 3 * h) + (h * h + h) \
        + (h * ffn + ffn) + (ffn * h + h) + 4 * h
    return (config["vocab_size"] * h + config["n_positions"] * h
            + layers * per_layer + 2 * h)


def train_flops_per_token(config: Dict, seq_len: int) -> float:
    """Forward and backward: 6 N for the matrix products with the
    parameters plus 12 L h s for the attention scores and values over
    the whole (unmasked) context, as Megatron counts it."""
    return (6.0 * parameters(config)
            + 12.0 * config["n_layer"] * config["n_embd"] * seq_len)


def attention(heads: int, sq: int, sk: int, d: int, *,
              causal: bool) -> float:
    """Operations of QK^T and PV over ``heads`` independent heads,
    ``sq`` queries against ``sk`` keys of size ``d``: 2 d operations
    for each query-key pair and product. Under a causal mask whose
    diagonal ends at the last key, query i sees sk - sq + i + 1 keys."""
    pairs = sq * sk - (sq * (sq - 1) / 2 if causal else 0)
    return 4.0 * heads * pairs * d


ITEMSIZE = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1,
            "u8": 1, "pred": 1, "f64": 8, "s64": 8}


def attention_call(results, operands) -> Tuple[float, float]:
    """(operations, bytes) of one call of the flash attention kernel,
    from the shapes of its results and operands as the trace's HLO text
    gives them (``[(dtype, dims), ...]``). q is the first bf16 operand
    ``[..., sq, d]``, k the second ``[..., sk, d]``; every leading
    dimension is an independent head. Causal with the diagonal at the
    end of the keys, which is what every call of the model is (one
    query against its whole context is the case sq = 1). A forward call
    (three such operands) is two products, QK^T and PV; a backward call
    that returns dk and dv is four (S, dP, dV, dK), one that returns dq
    three (S, dP, dQ). Bytes: every operand read and every result
    written once."""
    big = [dims for t, dims in operands if t == "bf16" and len(dims) >= 3]
    q, k = big[0], big[1]
    heads = 1
    for n in q[:-2]:
        heads *= n
    out = [dims for t, dims in results if t == "bf16" and len(dims) >= 3]
    products = 2 if len(big) == 3 else (4 if len(out) == 2 else 3)
    flops = attention(heads, q[-2], k[-2], q[-1], causal=True) \
        * products / 2
    nbytes = 0
    for t, dims in list(results) + list(operands):
        n = ITEMSIZE[t]
        for dim in dims:
            n *= dim
        nbytes += n
    return flops, float(nbytes)


def least_seconds(flops: float, nbytes: float, peaks: Dict) -> Tuple[float, str]:
    """The least time the chip could take, and which bound gives it."""
    by_compute = flops / peaks["bf16_flops_per_s"]
    by_memory = nbytes / peaks["hbm_bytes_per_s"]
    return ((by_compute, "compute") if by_compute >= by_memory
            else (by_memory, "memory"))
