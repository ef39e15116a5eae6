"""Long-context training with context parallelism (ring attention).

The reference tops out at Megatron sequence parallelism (activations
seq-sharded between TP matmuls) and a 512-token fmha; this example shows
the beyond-reference long-context path: the sequence dim sharded over
the mesh's context axis, causal attention computed exactly by
``ring_attention_sharded`` (zig-zag balanced KV rotation via ppermute,
recompute backward, O(s_local) per-device memory), or by Ulysses
all-to-all when heads divide the cp size.

A tiny copy-task transformer trains end to end with the sequence split
across 4 simulated devices; per-device attention never materializes more
than its local shard's scores:

    python examples/long_context/train_long_context.py \
        --seq 512 --cp 4 --steps 30 --attn ring
"""

import argparse
import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from apex_tpu.normalization import FusedRMSNorm
from apex_tpu.optimizers import FusedAdam
from apex_tpu.transformer import parallel_state as ps
from apex_tpu.transformer.context_parallel import (
    ring_attention_sharded,
    ulysses_attention_sharded,
)


def init_params(key, vocab, hidden, heads, layers):
    ks = jax.random.split(key, 2 * layers + 2)
    params = {
        "embed": jax.random.normal(ks[0], (vocab, hidden)) * 0.02,
        "layers": [],
    }
    for i in range(layers):
        params["layers"].append({
            "qkv": jax.random.normal(ks[2 * i + 1],
                                     (hidden, 3 * hidden)) * 0.02,
            "out": jax.random.normal(ks[2 * i + 2], (hidden, hidden)) * 0.02,
            "norm": jnp.ones((hidden,)),
        })
    return params


def forward(params, tokens, mesh, heads, attn):
    """(batch, S) tokens -> (batch, S, vocab) logits; attention runs
    sequence-sharded over the context axis."""
    h = params["embed"][tokens]                      # (b, S, hidden)
    hidden = h.shape[-1]
    hd = hidden // heads
    for lp in params["layers"]:
        x = FusedRMSNorm(hidden).apply(
            {"params": {"scale": lp["norm"]}}, h)
        qkv = x @ lp["qkv"]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        # (b, S, hidden) -> (b, heads, S, hd)
        split = lambda t: t.reshape(  # noqa: E731
            t.shape[0], t.shape[1], heads, hd).transpose(0, 2, 1, 3)
        if attn == "ring":
            o = ring_attention_sharded(
                split(q), split(k), split(v), mesh, causal=True,
                zigzag=True, batch_axis=None)
        else:
            o = ulysses_attention_sharded(
                split(q), split(k), split(v), mesh, causal=True,
                batch_axis=None, impl=None)
        o = o.transpose(0, 2, 1, 3).reshape(h.shape)
        h = h + o @ lp["out"]
    return h @ params["embed"].T


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--cp", type=int, default=4)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch-size", type=int, default=2)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--vocab", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--attn", choices=("ring", "ulysses"), default="ring")
    args = ap.parse_args(argv)

    ps.destroy_model_parallel()
    mesh = ps.initialize_model_parallel(context_parallel_size=args.cp)

    # copy task: predict token shifted by one (learnable with attention)
    rng = np.random.RandomState(0)
    toks = jnp.asarray(
        rng.randint(2, args.vocab, (args.batch_size, args.seq + 1)),
        jnp.int32)
    x, y = toks[:, :-1], toks[:, 1:]

    params = init_params(jax.random.PRNGKey(0), args.vocab, args.hidden,
                         args.heads, args.layers)
    opt = FusedAdam(lr=args.lr, impl="xla")
    state = opt.init(params)

    @jax.jit
    def step(state, x, y):
        def loss_fn(p):
            logits = forward(p, x, mesh, args.heads, args.attn)
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
            return -jnp.mean(
                jnp.take_along_axis(logp, y[..., None], -1))

        p = state.space.unpack(state.master)
        loss, grads = jax.value_and_grad(loss_fn)(p)
        _, state2 = opt.step(state, grads)
        return state2, loss

    loss = None
    for i in range(args.steps):
        state, loss = step(state, x, y)
        if i % 10 == 0 or i == args.steps - 1:
            print(f"step {i:4d}  loss {float(loss):.4f}")
    ps.destroy_model_parallel()
    return float(loss)


if __name__ == "__main__":
    from apex_tpu import compile_cache

    compile_cache.enable()
    sys.exit(0 if np.isfinite(main()) else 1)
