"""DCGAN amp example — multiple models / optimizers / losses
(ref: examples/dcgan/main_amp.py, 274 LoC: amp.initialize with two
models+optimizers and num_losses=3, separate scale_loss per loss).

The TPU point of this example is the multi-scaler choreography: G and D
keep independent loss-scaler states (``num_losses=2``) and each
backward uses its own scale, exactly the reference's
``amp.scale_loss(errD, optimizerD, loss_id=0/1)`` pattern, expressed
functionally.

Run (CPU smoke):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python main_amp.py --steps 5 --image-size 16
"""

from __future__ import annotations

import argparse

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu import amp
from apex_tpu.optimizers import FusedAdam


class Generator(nn.Module):
    feat: int = 16
    channels: int = 3

    @nn.compact
    def __call__(self, z):        # z (b, nz) -> (b, s, s, c)
        b = z.shape[0]
        x = nn.Dense(4 * 4 * self.feat * 2)(z)
        x = x.reshape(b, 4, 4, self.feat * 2)
        x = nn.relu(nn.GroupNorm(num_groups=4)(x))
        x = nn.ConvTranspose(self.feat, (4, 4), strides=(2, 2))(x)
        x = nn.relu(nn.GroupNorm(num_groups=4)(x))
        x = nn.ConvTranspose(self.channels, (4, 4), strides=(2, 2))(x)
        return jnp.tanh(x)


class Discriminator(nn.Module):
    feat: int = 16

    @nn.compact
    def __call__(self, x):        # (b, s, s, c) -> (b,)
        x = nn.Conv(self.feat, (4, 4), strides=(2, 2))(x)
        x = nn.leaky_relu(x, 0.2)
        x = nn.Conv(self.feat * 2, (4, 4), strides=(2, 2))(x)
        x = nn.leaky_relu(x, 0.2)
        x = x.reshape(x.shape[0], -1)
        return nn.Dense(1)(x)[:, 0]


def bce_logits(logits, target):
    # stable BCE-with-logits (the reference uses BCELoss on sigmoid)
    return jnp.mean(jnp.maximum(logits, 0) - logits * target
                    + jnp.log1p(jnp.exp(-jnp.abs(logits))))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--nz", type=int, default=32)
    ap.add_argument("--image-size", type=int, default=16)
    ap.add_argument("--lr", type=float, default=2e-4)
    ap.add_argument("--opt-level", default="O1")
    args = ap.parse_args(argv)

    rng = np.random.RandomState(0)
    real = jnp.asarray(
        rng.rand(args.batch_size, args.image_size, args.image_size, 3) * 2
        - 1, jnp.float32)

    netG, netD = Generator(), Discriminator()
    z0 = jnp.asarray(rng.randn(args.batch_size, args.nz), jnp.float32)
    pG = netG.init(jax.random.PRNGKey(0), z0)
    pD = netD.init(jax.random.PRNGKey(1), real)

    optG = FusedAdam(lr=args.lr, betas=(0.5, 0.999), impl="xla")
    optD = FusedAdam(lr=args.lr, betas=(0.5, 0.999), impl="xla")
    # two models, two optimizers, two loss scalers — the functional form
    # of ref main_amp.py's amp.initialize([netD, netG],
    # [optimizerD, optimizerG], num_losses=3): each (model, optimizer)
    # pair is initialized against its own params, and the D/G losses
    # carry independent scaler states
    pD, sD, ampD = amp.initialize(pD, optD, opt_level=args.opt_level)
    pG, sG, ampG = amp.initialize(pG, optG, opt_level=args.opt_level)
    scaler = amp.make_scaler(ampD.properties)
    ssD, ssG = ampD.scalers[0], ampG.scalers[0]

    @jax.jit
    def stepD(pD, pG, sD, ssD, z, key):
        def lossD(p):
            fake = netG.apply(pG, z)
            out_real = netD.apply(p, real)
            out_fake = netD.apply(p, fake)
            return bce_logits(out_real, 1.0) + bce_logits(out_fake, 0.0)
        sloss, g = jax.value_and_grad(
            lambda p: scaler.scale_loss(lossD(p), ssD))(pD)
        pD2, sD = optD.step(sD, g, grad_scale=ssD.loss_scale,
                            skip_if_nonfinite=True)
        return pD2, sD, scaler.update(ssD, sD.found_inf), sloss

    @jax.jit
    def stepG(pG, pD, sG, ssG, z):
        def lossG(p):
            fake = netG.apply(p, z)
            return bce_logits(netD.apply(pD, fake), 1.0)
        sloss, g = jax.value_and_grad(
            lambda p: scaler.scale_loss(lossG(p), ssG))(pG)
        pG2, sG = optG.step(sG, g, grad_scale=ssG.loss_scale,
                            skip_if_nonfinite=True)
        return pG2, sG, scaler.update(ssG, sG.found_inf), sloss

    key = jax.random.PRNGKey(2)
    for i in range(args.steps):
        key, kz = jax.random.split(key)
        z = jax.random.normal(kz, (args.batch_size, args.nz))
        pD, sD, ssD, lD = stepD(pD, pG, sD, ssD, z, kz)
        pG, sG, ssG, lG = stepG(pG, pD, sG, ssG, z)
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i:4d}  lossD {float(lD)/float(ssD.loss_scale):.4f}"
                  f"  lossG {float(lG)/float(ssG.loss_scale):.4f}")
    return (float(lD) / float(ssD.loss_scale),
            float(lG) / float(ssG.loss_scale))


if __name__ == "__main__":
    from apex_tpu import compile_cache

    compile_cache.enable()
    main()
