"""Minimal DDP + amp example — BASELINE configs[0] (MNIST-MLP parity run)
(ref: examples/simple/distributed/distributed_data_parallel.py, 65 LoC:
torch.distributed.launch + apex.parallel.DistributedDataParallel +
amp O1).

TPU version: one process, one mesh — the "launcher" is the device mesh
itself (``initialize_model_parallel``), DDP is grad-psum over the data
axis inside ``shard_map``, and amp O1 is a precision policy + loss
scaler carried functionally.

Run (CPU mesh):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python distributed_data_parallel.py --steps 50
"""

from __future__ import annotations

import argparse
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from apex_tpu import amp
from apex_tpu.optimizers import FusedSGD
from apex_tpu.parallel import DistributedDataParallel
from apex_tpu.transformer import parallel_state as ps


def mnist_mlp_params(key, hidden=128):
    k1, k2, k3 = jax.random.split(key, 3)
    init = jax.nn.initializers.he_normal()
    return {
        "fc1": {"w": init(k1, (784, hidden), jnp.float32),
                "b": jnp.zeros((hidden,), jnp.float32)},
        "fc2": {"w": init(k2, (hidden, hidden), jnp.float32),
                "b": jnp.zeros((hidden,), jnp.float32)},
        "out": {"w": init(k3, (hidden, 10), jnp.float32),
                "b": jnp.zeros((10,), jnp.float32)},
    }


def mlp_apply(p, x):
    x = jax.nn.relu(x @ p["fc1"]["w"] + p["fc1"]["b"])
    x = jax.nn.relu(x @ p["fc2"]["w"] + p["fc2"]["b"])
    return x @ p["out"]["w"] + p["out"]["b"]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch-size", type=int, default=64,
                    help="global batch (split over the data axis)")
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--opt-level", default="O1")
    args = ap.parse_args(argv)

    mesh = ps.initialize_model_parallel()   # all devices on the data axis
    dp = ps.get_data_parallel_world_size()
    print(f"mesh: data={dp}")

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(args.batch_size, 784), jnp.float32)
    y = jnp.asarray(rng.randint(0, 10, args.batch_size), jnp.int32)

    params = mnist_mlp_params(jax.random.PRNGKey(0))
    opt = FusedSGD(lr=args.lr, momentum=0.9, impl="xla")
    # amp.initialize: casts params per opt-level, builds scaler state,
    # inits the optimizer from the fp32 masters (ref amp O1/O2 flow)
    params, opt_state, amp_state = amp.initialize(
        params, opt, opt_level=args.opt_level)
    scaler = amp.make_scaler(amp_state.properties)
    sstate = amp_state.scalers[0]
    ddp = DistributedDataParallel()

    def local_loss(p, x, y):
        logits = mlp_apply(p, x).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        return jnp.mean(lse - jnp.take_along_axis(
            logits, y[:, None], -1)[:, 0])

    @jax.jit
    def step(params, opt_state, sstate, x, y):
        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(P(), P(ps.DATA_AXIS), P(ps.DATA_AXIS)),
            out_specs=(P(), P()), check_vma=False)
        def grads_fn(p, x, y):
            loss, g = jax.value_and_grad(
                lambda p: scaler.scale_loss(local_loss(p, x, y), sstate))(p)
            return loss, ddp.allreduce_grads(g)   # psum-mean over "data"

        scaled_loss, grads = grads_fn(params, x, y)
        new_params, opt_state = opt.step(
            opt_state, grads, grad_scale=sstate.loss_scale,
            skip_if_nonfinite=True)
        sstate2 = scaler.update(sstate, opt_state.found_inf)
        return new_params, opt_state, sstate2, scaled_loss

    for i in range(args.steps):
        params, opt_state, sstate, sloss = step(
            params, opt_state, sstate, x, y)
        if i % 10 == 0 or i == args.steps - 1:
            loss = float(sloss) / float(sstate.loss_scale)
            print(f"step {i:4d}  loss {loss:.4f}  "
                  f"scale {float(sstate.loss_scale):.0f}")

    ps.destroy_model_parallel()
    return float(sloss) / float(sstate.loss_scale)


if __name__ == "__main__":
    from apex_tpu import compile_cache

    compile_cache.enable()
    main()
