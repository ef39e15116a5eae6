"""Closed training loop: the trainer does all the work.

The traffic file gives the batch, the sequence length, the lead-in, how
often the loss is fetched and how many steps a traced run traces; the
configuration gives the model and the optimizer. ``--seed`` makes the
weights and every batch's tokens (ids with text-like 1/rank
frequencies, so the loss has something to fall to), and nothing that
decides a shape.

A fresh batch is made on the host and put on the device before the step
that uses it is enqueued; steps are enqueued ahead of the device and the
host waits only every ``loss_every`` steps, on the step before the one
just enqueued, so the device never waits for the host's fetch. The
window ends in ``block_until_ready`` and counts whole steps. A traffic
file with ``"mesh": {"batch": b, "model": m}`` runs the same step on the
GSPMD mesh of the cell's chips, the batch split over its batch axis.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import common, reference


def run(run) -> None:
    import jax

    from apex_tpu import optimizers
    from apex_tpu.models.pretrain import make_gpt_pretrain_step

    config, traffic = run.config, run.traffic
    cfg = common.gpt_config(config)
    batch, seq = traffic["batch"], traffic["seq_len"]
    every = traffic["loss_every"]
    opt = dict(config["optimizer"])
    optimizer = getattr(optimizers, opt.pop("name"))(**opt)
    if "mesh" in traffic:       # as a user arms it: it takes every chip
        from apex_tpu import mesh as gmesh

        gmesh.initialize_mesh(**traffic["mesh"], devices=run.devices)
    step, state = make_gpt_pretrain_step(cfg, optimizer)(
        common.init_params(cfg, run.seed))
    run.mark("weights and optimizer state")
    put = jax.device_put
    if not step.plan.is_identity():
        from jax.sharding import NamedSharding

        rows = NamedSharding(step.plan.mesh, step.plan.batch_spec)
        put = lambda x: jax.device_put(x, rows)  # noqa: E731
    rng = np.random.default_rng([run.seed, 1])
    vocab = config["vocab_size"]

    def next_batch():
        with common.span("bench.train.next_batch"):
            toks = common.zipf_tokens(rng, (batch, seq + 1), vocab)
            return put(toks[:, :-1]), put(toks[:, 1:])

    losses = []                 # (step index, device scalar or float)
    first_batch = None

    def steps_until(state, n_done, stop):
        """Enqueue steps until ``stop(n)`` says so; every ``every``-th
        step the host waits for the step before it."""
        nonlocal first_batch
        n = 0
        while not stop(n):
            tokens, labels = next_batch()
            if first_batch is None:
                first_batch = (np.asarray(tokens), np.asarray(labels))
            with common.span("bench.train.step"):
                state, loss = step(state, tokens, labels)
            losses.append([n_done + n, loss])
            if (n_done + n) % every == 0 and len(losses) > 1:
                losses[-2][1] = float(losses[-2][1])
            n += 1
        jax.block_until_ready(loss)
        return state, n

    state, lead = steps_until(state, 0, lambda n: n >= traffic["lead_in_steps"])
    run.mark("lead-in (the step program from the cache, or compiled)")
    compiles0 = run.compiles.n
    t0 = time.perf_counter()
    run.setup_s = t0 - run.t_start
    n_window = 0
    if run.trace:
        with common.traced(run.trace_dir) as took:
            state, n_window = steps_until(
                state, lead, lambda n: n >= traffic["trace_steps"])
        t0 += took["overhead_s"]
        run.counters["traced_steps"] = n_window
    state, n = steps_until(
        state, lead + n_window,
        lambda n: time.perf_counter() - t0 >= run.seconds)
    t1 = time.perf_counter()
    n_window += n
    run.window_compilations = run.compiles.n - compiles0
    run.window_s = t1 - t0
    tokens = n_window * batch * seq
    run.end_to_end = {"train_tok_s": tokens / run.window_s,
                      "setup_s": run.setup_s}
    run.counters.update(steps=n_window, tokens=tokens, batch=batch,
                        seq_len=seq, chips=len(run.devices))
    run.attempted = n_window

    # correct: every loss the host fetched is finite, the last is below
    # the first, and the first step's loss is the reference's on the
    # same weights (made again from the seed) and batch
    fetched = [(i, float(v)) for i, v in losses
               if isinstance(v, float) or i in (0, lead + n_window - 1)]
    values = np.asarray([v for _, v in fetched])
    run.failed = int((~np.isfinite(values)).sum())
    del state
    ref = reference.loss(common.init_params(cfg, run.seed), *first_batch,
                         heads=cfg.num_heads)
    if "mesh" in traffic:
        gmesh.destroy_mesh()
    tol = traffic["loss_tolerance"]
    run.correct = bool(run.failed == 0 and values[-1] < values[0]
                       and abs(values[0] - ref) <= tol)
    run.notes.append(
        f"train: {n_window} steps of {batch} x {seq} tokens in the window, "
        f"{len(values)} losses fetched, first {values[0]:.4f} (reference "
        f"{ref:.4f}, tolerance {tol}), last {values[-1]:.4f}")
