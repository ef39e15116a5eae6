"""The plain reference of ONE training step: what the parameters change
by when ``reference.py``'s model takes its first AdamW step on a batch.

    loss   = mean next-token cross entropy over the batch (``reference``)
    g      = d loss / d params, float32, products at "highest"
    first step from zero moments, bias-corrected (Kingma & Ba 2015;
    decoupled decay, Loshchilov & Hutter 2019):
    m = (1 - b1) g,  v = (1 - b2) g^2,  m^ = g,  v^ = g^2
    p' = p - lr * (g / (|g| + eps) + wd * p)

No kernel, no mesh, no flat buffer, no bf16: one sequence's gradient at
a time, a block at a time (a few small programs to compile), summed. It reads the program's parameter tree and nothing else
of the program. The loss alone cannot see a gradient that was not
summed over the data shards of a mesh (the first loss is forward only,
and a later loss hardly moves); the parameters' change can.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference

F32 = jnp.float32


@jax.jit
def _top(x, final_norm, table, labels):
    """(summed cross entropy of one sequence, its gradient by the last
    block's output, the final norm and the tied table)."""
    def total(x, final_norm, table):
        lg = reference._ln(x, final_norm) @ table.T
        lse = jax.scipy.special.logsumexp(lg, axis=-1)
        return (lse - lg[jnp.arange(lg.shape[0]), labels]).sum()

    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(total, argnums=(0, 1, 2))(
            x, final_norm, table)


@functools.partial(jax.jit, static_argnames=("heads",))
def _block_back(x, layer, dy, *, heads: int):
    """``reference.block``'s gradient by its input and by its layer's
    parameters, given the gradient ``dy`` by its output."""
    with jax.default_matmul_precision("highest"):
        _, pull = jax.vjp(
            lambda x, layer: reference.block(x, layer, heads=heads), x, layer)
        return pull(dy)


@jax.jit
def _embed(table, positions, tokens):
    return (table[tokens] + positions[:tokens.shape[0]])[None]


@jax.jit
def _layer(stacked, i):
    return jax.tree.map(lambda a: a[i], stacked)


@functools.partial(jax.jit, donate_argnums=(0,))
def _add_layer(grad, d_layer, i):
    p = grad["params"]
    stacked = jax.tree.map(lambda g, d: g.at[i].add(d),
                           p["layers"]["layer"], d_layer)
    return {"params": {**p, "layers": {"layer": stacked}}}


@functools.partial(jax.jit, donate_argnums=(0,))
def _add_ends(grad, d_norm, d_table, tokens, dx):
    """The final norm's, the tied table's (as the head, and as the
    embedding of ``tokens``) and the positions' part."""
    p = grad["params"]
    return {"params": {
        **p, "final_norm": jax.tree.map(jnp.add, p["final_norm"], d_norm),
        "embedding": {"embedding": (p["embedding"]["embedding"]
                                    + d_table).at[tokens].add(dx)},
        "position_embedding":
            p["position_embedding"].at[:dx.shape[0]].add(dx)}}


@jax.jit
def _zeros(tree):
    return jax.tree.map(jnp.zeros_like, tree)


@functools.partial(jax.jit, donate_argnums=(0,))
def _scaled(grad, by):
    return jax.tree.map(lambda g: g * by, grad)


def gradient(params, tokens, labels, *, heads: int):
    """(mean loss, float32 gradient tree) over ``tokens`` / ``labels``
    (batch, seq): one sequence at a time, a block at a time (forward
    through ``reference.block`` keeping each block's input, then back
    through the blocks in reverse), summed."""
    tokens, labels = np.asarray(tokens), np.asarray(labels)
    params = jax.tree.map(lambda a: a.astype(F32), params)
    p = params["params"]
    table = p["embedding"]["embedding"]
    stacked = p["layers"]["layer"]
    n_layers = jax.tree.leaves(stacked)[0].shape[0]
    layers = [_layer(stacked, i) for i in range(n_layers)]
    loss, grad = 0.0, _zeros(params)
    for toks, labs in zip(tokens, labels):
        toks = jnp.asarray(toks)
        x = _embed(table, p["position_embedding"], toks)
        inputs = []
        for layer in layers:
            inputs.append(x)
            x = reference.block(x, layer, heads=heads)
        value, (dx, d_norm, d_table) = _top(x[0], p["final_norm"], table,
                                            jnp.asarray(labs))
        loss += float(value)
        dx = dx[None]
        for i in reversed(range(n_layers)):
            dx, d_layer = _block_back(inputs[i], layers[i], dx, heads=heads)
            grad = _add_layer(grad, d_layer, i)
        grad = _add_ends(grad, d_norm, d_table, toks, dx[0])
    return loss / labels.size, _scaled(grad, 1.0 / labels.size)


@functools.partial(jax.jit, static_argnames=("lr", "eps", "weight_decay"))
def first_adamw_change(params, grad, *, lr: float, eps: float,
                       weight_decay: float):
    """``p' - p`` of the first AdamW step from zero moments."""
    return jax.tree.map(
        lambda p, g: -lr * (g / (jnp.abs(g) + eps)
                            + weight_decay * p.astype(F32)), params, grad)


def change_gap(theirs, ours) -> float:
    """``|theirs - ours| / |ours|`` over two trees of parameter changes
    on the host (the 2-norm over every element): 0 where they agree, 1
    where ``theirs`` is no change at all."""
    pairs = list(zip(jax.tree.leaves(theirs), jax.tree.leaves(ours)))
    num = sum(float(np.sum((np.asarray(a, np.float32) - b) ** 2,
                           dtype=np.float64)) for a, b in pairs)
    den = sum(float(np.sum(np.square(b), dtype=np.float64))
              for _, b in pairs)
    return float(np.sqrt(num / den))
