"""Porting a reference amp O1 model with zero registration.

The reference flow (ref: apex amp docs, examples/dcgan/main_amp.py):

    model, optimizer = amp.initialize(model, optimizer, opt_level="O1")
    ...
    with amp.scale_loss(loss, optimizer) as scaled_loss:
        scaled_loss.backward()

where every ``torch.nn.functional`` call inside the model is patched to
the shipped classification (convs/linears fp16, softmax/losses fp32,
ref apex/amp/lists/functional_overrides.py:18-92). The apex_tpu
equivalent: write the model against ``amp.F`` — the same shipped
classification as a policy-aware functional namespace — and let
``amp.initialize`` activate the policy. Nothing else to register.

The training loop runs the fused train-step path
(``optimizers.make_train_step``): everything the reference's
``scale_loss`` block does imperatively — unscale, overflow check,
skip-step, scale schedule — plus the optimizer update compiles into
ONE jitted, donation-aware program, and the gradients are taken
straight into the flat master buffer (``space.grad_fn``) so the hot
loop never packs a per-leaf tree.

Run (CPU ok): python examples/amp_functional/main.py
"""

import numpy as np

import jax
import jax.numpy as jnp

from apex_tpu import amp
from apex_tpu.optimizers import FusedSGD, make_train_step

F = amp.F


def model(params, x):
    # whitelist ops run in the policy compute dtype (fp16 under O1,
    # bf16 under O4); blacklist ops compute fp32 — exactly the
    # reference's patched-namespace behavior, visible in the dtypes
    h = F.conv2d(x, params["conv_w"], params["conv_b"], padding=1)
    h = F.relu(h)                                   # matches input dtype
    h = h.reshape(h.shape[0], -1)
    h = F.linear(h, params["fc1_w"], params["fc1_b"])
    h = F.layer_norm(h, h.shape[-1])                # fp32 always
    h = F.gelu(h)
    return F.linear(h, params["fc2_w"], params["fc2_b"])


def main():
    rng = np.random.RandomState(0)
    n, c, s, classes = 64, 3, 8, 10
    X = jnp.asarray(rng.randn(n, c, s, s).astype(np.float32))
    Y = jnp.asarray(rng.randint(0, classes, (n,)))

    params = {
        "conv_w": jnp.asarray(rng.randn(8, c, 3, 3).astype(np.float32) * 0.2),
        "conv_b": jnp.zeros((8,)),
        "fc1_w": jnp.asarray(
            rng.randn(32, 8 * s * s).astype(np.float32) * 0.05),
        "fc1_b": jnp.zeros((32,)),
        "fc2_w": jnp.asarray(rng.randn(classes, 32).astype(np.float32) * 0.1),
        "fc2_b": jnp.zeros((classes,)),
    }

    opt = FusedSGD(lr=0.05, momentum=0.9)
    # O1: fp16 compute via amp.F, fp32 masters, dynamic loss scaling
    params, opt_state, amp_state = amp.initialize(
        params, opt, opt_level="O1")

    def loss_fn(p):
        return F.cross_entropy(model(p, X), Y)     # fp32 loss (blacklist)

    # ONE compiled program per step: unscale (1/loss_scale) folded into
    # the fused update sweep, overflow-gated skip, scaler schedule
    # advanced — the whole `with amp.scale_loss(...)` flow. The state
    # and scaler-state arguments are DONATED: rebind both every step.
    scaler = amp.make_scaler(amp_state.properties)
    step = make_train_step(opt, scaler=scaler)
    scaler_state = amp_state.scalers[0]

    # grads of the SCALED loss, taken straight into the flat master
    # buffer — the ".backward()" line, with no per-leaf pack after it
    flat_vg = jax.jit(opt_state.space.grad_fn(
        lambda p, scale: loss_fn(p) * scale, with_value=True))

    l0 = loss = None
    for _ in range(30):
        scale = scaler_state.loss_scale
        scaled_loss, g = flat_vg(opt_state.master, scale)
        loss = float(scaled_loss) / float(scale)
        opt_state, scaler_state, _aux = step(opt_state, g, scaler_state)
        if l0 is None:
            l0 = loss
    print(f"O1 training: loss {l0:.4f} -> {loss:.4f} "
          f"(scale {float(scaler_state.loss_scale):.0f})")
    assert loss < l0, "loss did not improve"


if __name__ == "__main__":
    from apex_tpu import compile_cache

    compile_cache.enable()
    main()
