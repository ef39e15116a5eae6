"""``train.py``'s closed loop on a mesh of chips, and one more look at
what such a cell is there for: the exchange between the chips.

The loop, its clock, its counters and its check of the first loss are
``train``'s own (called, not copied). That check is forward only, and
the losses after it hardly move when a gradient is wrong: a gradient
that was never summed over the mesh's batch axis passes it. So, outside
the window, this driver takes ONE step of the same program from the
seed's weights on the seed's first batch and holds the *change of the
parameters* to the plain reference's (``benchmark/reference_step.py``:
float32 gradient, the first AdamW step written out):
``|change - reference's| / |reference's|`` must not pass the traffic
file's ``step_tolerance``. A state left unchanged reads 1.

Each run also shows what the check is for: the same step on a batch
whose every data shard holds the first shard's rows gives the first
shard's gradient alone, which is what the program would apply if the
sum over the batch axis were left out; its reading is printed beside
the true one and has to fail.
"""

from __future__ import annotations

import numpy as np

from benchmark import common, reference_step
from benchmark.drivers import train


def one_step(cfg, optimizer, mesh: dict, devices, params, tokens, labels):
    """The parameters' change over one step of the mesh program from
    ``params``, as a tree of numpy arrays."""
    import jax
    from jax.sharding import NamedSharding

    from apex_tpu import mesh as gmesh
    from apex_tpu.models.pretrain import make_gpt_pretrain_step

    gmesh.initialize_mesh(**mesh, devices=devices)
    try:
        step, state = make_gpt_pretrain_step(cfg, optimizer)(params)
        rows = NamedSharding(step.plan.mesh, step.plan.batch_spec)
        state, _ = step(state, jax.device_put(tokens, rows),
                        jax.device_put(labels, rows))
        after = jax.device_get(state.space.unpack(state.master))
        del state
    finally:
        gmesh.destroy_mesh()
    return jax.tree.map(lambda a, b: a - np.asarray(b, a.dtype), after,
                        jax.device_get(params))


def run(run) -> None:
    import jax

    from apex_tpu import optimizers

    train.run(run)
    config, traffic = run.config, run.traffic
    cfg = common.gpt_config(config)
    opt = dict(config["optimizer"])
    if opt.pop("name") != "FusedAdam":
        raise ValueError("reference_step writes out the first AdamW step; "
                         f"the configuration trains with {config['optimizer']}")
    batch, seq = traffic["batch"], traffic["seq_len"]
    shards = traffic["mesh"]["batch"]
    toks = common.zipf_tokens(np.random.default_rng([run.seed, 1]),
                              (batch, seq + 1), config["vocab_size"])
    tokens, labels = toks[:, :-1], toks[:, 1:]
    params = jax.device_put(common.init_params(cfg, run.seed),
                            run.devices[0])

    def step_of(tokens, labels):
        return one_step(cfg, optimizers.FusedAdam(**opt), traffic["mesh"],
                        run.devices, params, tokens, labels)

    _, grad = reference_step.gradient(params, tokens, labels,
                                      heads=cfg.num_heads)
    want = jax.device_get(reference_step.first_adamw_change(
        params, grad, lr=opt["lr"], eps=opt.get("eps", 1e-8),
        weight_decay=opt.get("weight_decay", 0.0)))
    del grad
    gap = reference_step.change_gap(step_of(tokens, labels), want)
    # the first data shard's rows on every shard: its gradient alone
    first = np.tile(np.arange(batch // shards), shards)
    unsummed = reference_step.change_gap(
        step_of(tokens[first], labels[first]), want)
    tol = traffic["step_tolerance"]
    run.correct = bool(run.correct and gap <= tol)
    run.notes.append(
        f"train: one step from the seed's weights changes the parameters "
        f"as the reference's first AdamW step does to within {gap:.4f} of "
        f"that change's norm (allowed {tol}; an unchanged state reads 1); "
        f"with one data shard's gradient alone, as if the sum over the "
        f"mesh's batch axis were left out, it reads {unsummed:.4f}")
