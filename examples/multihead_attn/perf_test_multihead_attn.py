"""Multihead-attention standalone perf sweep
(ref: apex/contrib/examples/multihead_attn/perf_test_multihead_attn.py).

Sweeps batch size for a stack of Self/Encdec multihead-attention layers
and reports per-layer step time, comparing the fused Pallas path
(impl='fast') against the score-materializing reference path
(--ref -> impl='default'). CUDA events become the chained-iteration
timing protocol (queue all trials inside one jitted loop, fence once).

    python examples/multihead_attn/perf_test_multihead_attn.py \
        --seq-length 64 --num-seqs-start 10 --num-seqs-stop 120
"""

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.contrib.multihead_attn import (
    EncdecMultiheadAttn,
    SelfMultiheadAttn,
)


def build_layer(args, impl):
    cls = EncdecMultiheadAttn if args.encdec_attn else SelfMultiheadAttn
    return cls(
        embed_dim=args.hidden_dim, num_heads=args.heads, dropout=0.1,
        bias=args.biases, include_norm_add=args.norm_add, impl=impl,
        dtype=jnp.bfloat16 if jax.default_backend() != "cpu"
        else jnp.float32,
    )


def main(argv=None):
    p = argparse.ArgumentParser(
        description="Multihead Attention Standalone Test")
    p.add_argument("--seq-length", default=64, type=int)
    p.add_argument("--num-seqs-start", default=10, type=int)
    p.add_argument("--num-seqs-stop", default=120, type=int)
    p.add_argument("--num-seqs-inc", default=5, type=int)
    p.add_argument("--trials", default=20, type=int)
    p.add_argument("--warmup-trials", default=5, type=int)
    p.add_argument("--layers", default=18, type=int)
    p.add_argument("--hidden-dim", default=1024, type=int)
    p.add_argument("--heads", default=16, type=int)
    p.add_argument("--encdec-attn", action="store_true")
    p.add_argument("--norm-add", action="store_true")
    p.add_argument("--ref", action="store_true",
                   help="reference (score-materializing) implementation")
    p.add_argument("--fwd", action="store_true",
                   help="only execute the forward pass")
    p.add_argument("--biases", action="store_true")
    args = p.parse_args(argv)
    if args.trials < 1:
        p.error("--trials must be >= 1")

    impl = "default" if args.ref else (
        "fast" if jax.default_backend() not in ("cpu",) else "interpret")
    layer = build_layer(args, impl)
    rng = np.random.RandomState(111)
    rows = []

    for seqs in range(args.num_seqs_start, args.num_seqs_stop + 1,
                      args.num_seqs_inc):
        x = jnp.asarray(
            rng.randn(args.seq_length, seqs, args.hidden_dim)
            .astype(np.float32) * 0.5, layer.dtype)
        kv = x
        init_args = (x,) if not args.encdec_attn else (x, kv)
        params = layer.init(jax.random.PRNGKey(0), *init_args,
                            is_training=False)

        def stack(p, x):
            h = x
            for i in range(args.layers):
                call = (h,) if not args.encdec_attn else (h, kv)
                out, _ = layer.apply(
                    p, *call, is_training=True,
                    rngs={"dropout": jax.random.PRNGKey(i)})
                h = out
            return jnp.sum(h.astype(jnp.float32) ** 2)

        if args.fwd:
            fn = jax.jit(stack)
        else:
            fn = jax.jit(jax.value_and_grad(stack))

        out = None
        for _ in range(args.warmup_trials):
            out = fn(params, x)
        if out is not None:     # fence the warmup (if any)
            jax.device_get(jax.tree.leaves(out)[0])
        t0 = time.perf_counter()
        for _ in range(args.trials):
            out = fn(params, x)
        jax.device_get(jax.tree.leaves(out)[0])
        elapsed = (time.perf_counter() - t0) / args.trials
        per_layer_ms = elapsed * 1e3 / args.layers
        rows.append((seqs, per_layer_ms))
        mode = "fwd" if args.fwd else "fwd+bwd"
        print(f"[{'encdec' if args.encdec_attn else 'self'} {impl:9s} "
              f"{mode}] seqs={seqs:4d} x seq={args.seq_length} "
              f"-> {per_layer_ms:8.3f} ms/layer")
    return rows


if __name__ == "__main__":
    from apex_tpu import compile_cache

    compile_cache.enable()
    sys.exit(0 if main() else 1)
