"""The plain reference: a GPT-2 style decoder in straightforward float32
``jax.numpy``, with no kernel, cache, scan or batching trick.

Pre-LN blocks: ``x += proj(attn(ln1(x)))``, ``x += fc2(gelu(fc1(ln2(x))))``
with learned positions, causal softmax attention, tanh-GELU (GPT-2's
``gelu_new``), a final LayerNorm and a head tied to the embedding
(Radford et al. 2019; Hugging Face ``GPT2Model``). It reads the
program's parameter tree — the weights are the thing compared — and
nothing else of the program. Departures from the published model, all
the program's layout and none of them mathematics: linear kernels are
stored ``(out, in)``, and the fused QKV rows are laid out head by head
as ``[q | k | v]``.

Every matrix product runs under ``jax.default_matmul_precision
("highest")``: on a TPU a float32 product is otherwise done in bf16.
One layer's weights are upcast at a time, so the reference fits beside
a server that holds the bf16 model.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _ln(x, p, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _linear(x, p):
    return x @ p["kernel"].T + p["bias"]


@functools.partial(jax.jit, static_argnames=("heads",))
def block(x, layer, *, heads: int):
    """One pre-LN block over ``x`` (batch, seq, hidden); ``layer`` is
    one layer's parameters in any float type."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a.astype(F32), layer)
        b, s, h = x.shape
        d = h // heads
        qkv = _linear(_ln(x, p["input_norm"]), p["attention"]["qkv"])
        q, k, v = (qkv.reshape(b, s, heads, 3, d)[:, :, :, i]
                   for i in range(3))
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
        causal = jnp.tril(jnp.ones((s, s), bool))
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
        ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, h)
        x = x + _linear(ctx, p["attention"]["proj"])
        m = _linear(_ln(x, p["post_norm"]), p["mlp"]["fc1"])
        m = jax.nn.gelu(m, approximate=True)
        return x + _linear(m, p["mlp"]["fc2"])


def hidden(params, tokens, *, heads: int):
    """Final-norm hidden states (batch, seq, hidden) of ``tokens``."""
    p = params["params"]
    tokens = jnp.asarray(tokens, jnp.int32)
    s = tokens.shape[1]
    x = (p["embedding"]["embedding"][tokens].astype(F32)
         + p["position_embedding"][:s].astype(F32)[None])
    stacked = p["layers"]["layer"]
    n_layers = jax.tree.leaves(stacked)[0].shape[0]
    for i in range(n_layers):
        x = block(x, jax.tree.map(lambda a: a[i], stacked), heads=heads)
    return _ln(x, jax.tree.map(lambda a: a.astype(F32), p["final_norm"]))


@jax.jit
def _head(x, table):
    with jax.default_matmul_precision("highest"):
        return x @ table.astype(F32).T


def logits_rows(params, tokens, rows, *, heads: int):
    """Float32 logits (len(rows), vocab) of one sequence ``tokens``
    (1-D) at the positions ``rows``."""
    x = hidden(params, np.asarray(tokens)[None], heads=heads)[0]
    return _head(x[jnp.asarray(rows)],
                 params["params"]["embedding"]["embedding"])


def loss(params, tokens, labels, *, heads: int):
    """Mean next-token cross entropy of ``tokens`` (batch, seq) against
    ``labels`` (batch, seq), one sequence at a time."""
    table = params["params"]["embedding"]["embedding"]
    labels = np.asarray(labels)
    total = 0.0
    for i in range(len(labels)):
        x = hidden(params, np.asarray(tokens)[i:i + 1], heads=heads)[0]
        lg = _head(x, table)
        lse = jax.scipy.special.logsumexp(lg, axis=-1)
        total += float((lse - lg[jnp.arange(len(lg)), labels[i]]).sum())
    return total / labels.size
