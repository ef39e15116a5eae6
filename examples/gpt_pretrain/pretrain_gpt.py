"""GPT pretraining on the GSPMD mesh — the full L5 stack.

The reference exercises this workload class through its transformer test
harness (ref: tests/L0/run_transformer/run_gpt_minimal_test.py,
gpt_scaling_test.py: parallel_state groups + Megatron layers + 1F1B
schedule); this example is the runnable equivalent on the ONE mesh
substrate: `initialize_mesh(batch, pipe, model)`, a pipeline schedule
on the ``pipe`` axis (1F1B by default; ``--schedule interleaved_1f1b``
with ``--model-chunks 2`` for the interleaved variant), tensor
parallelism from the plan's NamedShardings, fused Adam on the flat
master buffer inside the same donated program, and orbax checkpoint +
exact resume.

Run (CPU mesh):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python pretrain_gpt.py --steps 20 --tp 2 --pp 2

Run (TPU slice): drop the env vars; pick tp/pp to match the topology.
"""

from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu import mesh as gmesh
from apex_tpu.models.gpt import GPTConfig
from apex_tpu.models.pretrain import (
    init_gpt_pretrain_params,
    make_gpt_pretrain_step,
)
from apex_tpu.optimizers import FusedAdam


def parse_args(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--tp", type=int, default=2)
    p.add_argument("--pp", type=int, default=2)
    p.add_argument("--schedule", type=str, default="1f1b",
                   choices=("gpipe", "1f1b", "interleaved_1f1b",
                            "async_1f1b"))
    p.add_argument("--model-chunks", type=int, default=1,
                   help="model chunks per stage (>1 selects the "
                        "interleaved schedule)")
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--global-batch", type=int, default=8)
    p.add_argument("--micro-batches", type=int, default=2)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--hidden", type=int, default=128)
    p.add_argument("--layers", type=int, default=4)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--vocab", type=int, default=1024)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--bf16", action="store_true",
                   help="bf16 compute (O5-style: fp32 master in the "
                        "fused optimizer state)")
    p.add_argument("--save", type=str, default="",
                   help="orbax checkpoint dir; if it already holds a "
                        "checkpoint, training resumes from it exactly")
    return p.parse_args(argv)


def synthetic_batch(rng, n, seq, vocab):
    """Deterministic token stream (the reference's minimal tests build
    synthetic text in-process the same way, run_gpt_minimal_test.py)."""
    toks = rng.randint(0, vocab, (n, seq + 1)).astype(np.int32)
    return jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])


def main(argv=None):
    args = parse_args(argv)
    gmesh.initialize_mesh(model=args.tp, pipe=args.pp)
    sizes = gmesh.axis_sizes()
    print(f"mesh: dp={sizes['batch']} tp={args.tp} pp={args.pp} "
          f"devices={len(jax.devices())}")

    cfg = GPTConfig(
        vocab_size=args.vocab, max_seq_len=args.seq,
        hidden_size=args.hidden, num_layers=args.layers,
        num_heads=args.heads, attention_backend="flash",
        dtype=jnp.bfloat16 if args.bf16 else jnp.float32,
    )
    params = init_gpt_pretrain_params(cfg, jax.random.PRNGKey(0))
    opt = FusedAdam(lr=args.lr, weight_decay=0.01)
    build = make_gpt_pretrain_step(
        cfg, opt, schedule=args.schedule,
        num_microbatches=args.micro_batches,
        num_model_chunks=args.model_chunks)
    try:
        step, state = build(params)

        # checkpoint/resume: the fused optimizer's state_dict (flat
        # master, slots, step count) round-trips through orbax as plain
        # pytrees — the bitwise-resume recipe pinned by
        # tests/test_checkpoint.py. The master buffer IS the params, so
        # one state_dict covers both.
        start = 0
        ckptr = ckpt_path = None
        if args.save:
            import orbax.checkpoint as ocp

            ckptr = ocp.StandardCheckpointer()
            ckpt_path = os.path.join(os.path.abspath(args.save), "latest")
            if os.path.isdir(ckpt_path):
                target = {"opt": opt.state_dict(state),
                          "step": jnp.zeros((), jnp.int32)}
                restored = ckptr.restore(ckpt_path, target)
                state = opt.load_state_dict(state, restored["opt"])
                start = int(restored["step"])
                print(f"resumed from {ckpt_path} at step {start}")

        rng = np.random.RandomState(0)
        loss = None
        t0 = time.perf_counter()
        for i in range(start, args.steps):
            inputs, labels = synthetic_batch(
                rng, args.global_batch, args.seq, args.vocab)
            state, loss = step(state, inputs, labels)
            if i % 5 == 0 or i == args.steps - 1:
                jax.block_until_ready(loss)
                dt = time.perf_counter() - t0
                tok_s = args.global_batch * args.seq * (i - start + 1) / dt
                bubble = getattr(step, "last_bubble_fraction", None)
                extra = (f"  bubble {bubble:.3f}"
                         if bubble is not None else "")
                print(f"step {i:4d}  loss {float(np.ravel(loss)[0]):.4f}"
                      f"  {tok_s:,.0f} tok/s{extra}")
        if ckptr is not None:
            ckptr.save(ckpt_path,
                       {"opt": opt.state_dict(state),
                        "step": jnp.asarray(args.steps, jnp.int32)},
                       force=True)
            ckptr.wait_until_finished()
            print(f"saved checkpoint to {ckpt_path}")
    finally:
        gmesh.destroy_mesh()
    return float(np.ravel(loss)[0]) if loss is not None else float("nan")


if __name__ == "__main__":
    from apex_tpu import compile_cache

    compile_cache.enable()
    main()
