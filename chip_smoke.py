"""Does the program still start on the chip?

Drives the two main paths once, at the full width of models the repo
supports, through the entry points a user would call, with random
weights made from a seed:

    python chip_smoke.py            # one chip, three phases in one process
    python chip_smoke.py --chips 4  # four chips: the mesh step, nothing else

One chip:

- *trainer* — GPT-2 345M (hidden 1024, 24 layers, 16 heads, seq 1024,
  vocab 50304, bf16) through ``init_gpt_pretrain_params`` and
  ``make_gpt_pretrain_step(cfg, FusedAdam(...))`` as
  ``examples/gpt_pretrain/pretrain_gpt.py`` drives them. The batch is
  traffic, not width: the largest candidate whose compiled program
  leaves headroom in the device's memory. The loss must be finite and
  fall.
- *headline optimizer* — ``FusedLAMB`` as a user gets it by default,
  two steps on the same parameter tree with seeded gradients, and
  against the plain XLA two-stage schedule on a small tree.
- *server* — a GQA serving configuration of its own (vocab 32768,
  context 2048, hidden 1024, 12 layers, 16 heads, 4 KV heads, bf16)
  through ``KVCache.for_config``, ``make_decode_step`` and the
  ``ContinuousBatcher`` submit/step loop: short requests, one prefilled
  in chunks, one whose context passes 1024 tokens while decoding. Every
  request must finish with the tokens it asked for, and its greedy
  tokens must agree with a plain ``model.apply`` over its whole
  sequence.

Four chips (``--chips 4``): the same GPT-2 345M step through
``initialize_mesh(batch=2, model=2)`` against the same seed and global
batch on a one-device mesh in the same process; the losses must agree.

A compiled program that holds no ``tpu_custom_call`` is a failure (a
kernel gave way), as is any phase that fails: nothing is caught and
carried past. Without a TPU the script fails at once. One process
touches the chip. The last line of standard output is the result the
driver reads; the lines before it say what was seen.
"""

from __future__ import annotations

import argparse
import importlib.metadata
import json
import sys
import time

import numpy as np

SEED = 0
#: the share of the device's memory one compiled program may claim; the
#: rest is for what the process holds beside it (an init copy of the
#: parameters, the allocator's fragmentation)
HEADROOM = 0.75
#: |four-device loss - one-device loss| allowed at every step: bf16
#: activations, summed in another order across the model axis
MESH_LOSS_TOL = 0.05


def say(msg: str) -> None:
    print(f"# {msg}", flush=True)


def check(ok, msg: str) -> None:
    if not ok:
        raise AssertionError(f"chip_smoke: {msg}")


def gib(n) -> str:
    return f"{n / 2**30:.2f} GiB"


class CompileWatch:
    """What jax's monitoring says about compiles: the requests that
    consulted the persistent cache, the hits among them, and the
    seconds spent in the backend compiler (none on a hit)."""

    def __init__(self):
        import jax

        self.requests = self.hits = 0
        self.seconds = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def mark(self):
        return self.requests, self.hits, self.seconds, time.perf_counter()

    def since(self, mark) -> str:
        r, h, s, t = mark
        wall = time.perf_counter() - t
        if self.requests == r and self.seconds == s:
            return f"{wall * 1e3:.0f} ms wall, nothing compiled"
        return (f"{wall:.1f} s wall, {self.seconds - s:.1f} s in the "
                f"compiler, {self.hits - h} cache hit(s) of "
                f"{self.requests - r} request(s)")


def program_bytes(compiled) -> int:
    """What a compiled program claims on one device: arguments and
    results (a donated buffer counted once), temporaries and code."""
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes
            + ma.generated_code_size_in_bytes)


def memory_limit(device):
    """The device's memory as its allocator reports it; None where the
    backend reports none (the CPU)."""
    stats = device.memory_stats()
    return stats["bytes_limit"] if stats else None


def peak_line(devices) -> str:
    out = []
    for d in devices:
        stats = d.memory_stats()
        out.append(f"device {d.id}: not reported" if not stats else
                   f"device {d.id}: in use {gib(stats['bytes_in_use'])}, "
                   f"peak {gib(stats['peak_bytes_in_use'])}")
    return "; ".join(out)


def kernel_count(compiled, what: str, expect_kernels: bool) -> int:
    n = compiled.as_text().count("tpu_custom_call")
    say(f"{what}: {n} tpu_custom_call(s) in the compiled program")
    if expect_kernels:
        check(n > 0, f"{what} holds no tpu_custom_call: a kernel gave way")
    return n


def seeded_tokens(seed, batch, seq, vocab):
    toks = np.random.RandomState(seed).randint(
        0, vocab, (batch, seq + 1)).astype(np.int32)
    return toks[:, :-1], toks[:, 1:]


def pick_batch(step, state, cfg, candidates, device, watch):
    """The largest candidate batch whose compiled step leaves headroom
    on ``device``; returns ``(batch, compiled)``."""
    import jax
    import jax.numpy as jnp

    limit = memory_limit(device)
    for batch in candidates:
        tok = jax.ShapeDtypeStruct((batch, cfg.max_seq_len), jnp.int32)
        mark = watch.mark()
        compiled = step.lower(state, tok, tok).compile()
        need = program_bytes(compiled)
        ma = compiled.memory_analysis()
        say(f"train step at batch {batch}: program {gib(need)} "
            f"(arguments {gib(ma.argument_size_in_bytes)}, "
            f"{gib(ma.alias_size_in_bytes)} of them donated, temporaries "
            f"{gib(ma.temp_size_in_bytes)}) of "
            f"{gib(limit) if limit else 'an unreported limit'}; compile "
            f"{watch.since(mark)}")
        if limit is None or need <= HEADROOM * limit:
            say(f"batch chosen: {batch} (the largest candidate of "
                f"{list(candidates)} under {HEADROOM:.0%} of the device)")
            return batch, compiled
    raise AssertionError(
        f"chip_smoke: no batch of {list(candidates)} leaves headroom")


def run_steps(step, state, tokens, labels, steps, watch, what):
    """``steps`` steps on one seeded batch; the loss must be finite and
    fall. Returns ``(state, losses)``."""
    losses = []
    for i in range(steps):
        mark = watch.mark()
        state, loss = step(state, tokens, labels)
        losses.append(float(loss))          # waits for the device
        say(f"{what} step {i}: loss {losses[-1]:.4f} ({watch.since(mark)})")
    check(np.isfinite(losses).all(), f"{what}: non-finite loss {losses}")
    check(losses[-1] < losses[0], f"{what}: loss did not fall: {losses}")
    return state, losses


# -- phase: trainer ---------------------------------------------------------


def train_phase(cfg, *, batches, steps, watch, expect_kernels, lr=3e-4):
    import jax
    import jax.numpy as jnp

    from apex_tpu.models.pretrain import (
        init_gpt_pretrain_params,
        make_gpt_pretrain_step,
    )
    from apex_tpu.optimizers import FusedAdam

    device = jax.devices()[0]
    params = init_gpt_pretrain_params(cfg, jax.random.PRNGKey(SEED))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    say(f"trainer: {n_params / 1e6:.1f}M parameters, hidden "
        f"{cfg.hidden_size}, {cfg.num_layers} layers, {cfg.num_heads} "
        f"heads, seq {cfg.max_seq_len}, vocab {cfg.vocab_size}, "
        f"{jnp.dtype(cfg.dtype).name}")
    step, state = make_gpt_pretrain_step(
        cfg, FusedAdam(lr=lr, weight_decay=0.01))(params)
    del params                  # the flat master buffer holds them now
    batch, compiled = pick_batch(step, state, cfg, batches, device, watch)
    kernels = kernel_count(compiled, "train step", expect_kernels)
    tokens, labels = seeded_tokens(SEED, batch, cfg.max_seq_len,
                                   cfg.vocab_size)
    state, losses = run_steps(step, state, tokens, labels, steps, watch,
                              "train")
    check(bool(jnp.isfinite(state.master).all()),
          "trainer: non-finite parameters after the steps")
    say(f"trainer memory: {peak_line([device])}")
    return {"batch": batch, "losses": losses, "kernels": kernels}


# -- phase: headline optimizer ----------------------------------------------


def lamb_phase(cfg, *, steps, watch, expect_kernels):
    import jax
    import jax.numpy as jnp

    from apex_tpu.models.pretrain import init_gpt_pretrain_params
    from apex_tpu.optimizers import FusedLAMB

    # against the plain reference first, on a small tree: the default
    # schedule and the XLA two-stage math must agree
    small = {"w": (512, 1024), "b": (1024,), "odd": (3000,)}
    keys = jax.random.split(jax.random.PRNGKey(SEED), 2 * len(small))
    tree = {k: 0.02 * jax.random.normal(keys[i], s)
            for i, (k, s) in enumerate(small.items())}
    grads = {k: 1e-3 * jax.random.normal(keys[len(small) + i], s)
             for i, (k, s) in enumerate(small.items())}
    outs = []
    for opt in (FusedLAMB(lr=1e-3),
                FusedLAMB(lr=1e-3, impl="xla", segmented=False)):
        state, small_step = opt.init(tree), jax.jit(opt.step)
        for _ in range(steps):
            new, state = small_step(state, grads)
        outs.append(new)
    err = max(float(jnp.max(jnp.abs(a - b))) for a, b in
              zip(jax.tree.leaves(outs[0]), jax.tree.leaves(outs[1])))
    say(f"FusedLAMB default vs XLA two-stage on {len(small)} leaves, "
        f"{steps} steps: max |difference| {err:.2e}")
    check(err < 1e-5, f"FusedLAMB disagrees with its reference: {err}")

    # then two steps at size, as a user gets it by default
    params = init_gpt_pretrain_params(cfg, jax.random.PRNGKey(SEED))
    opt = FusedLAMB(lr=1e-3)
    state = opt.init(params)
    leaves, treedef = jax.tree.flatten(params)
    del params
    gkeys = jax.random.split(jax.random.PRNGKey(SEED + 1), len(leaves))
    grads = treedef.unflatten([
        1e-3 * jax.random.normal(k, x.shape, jnp.float32)
        for k, x in zip(gkeys, leaves)])
    del leaves
    first = np.asarray(state.master[:4096])
    lamb_step = jax.jit(lambda s, g: opt.step(s, g)[1], donate_argnums=(0,))
    mark = watch.mark()
    compiled = lamb_step.lower(state, grads).compile()
    say(f"FusedLAMB step over {state.space.total / 1e6:.1f}M elements "
        f"({len(state.seg_meta.small_segments)} one-pass segments, "
        f"{len(state.seg_meta.large)} large leaves): program "
        f"{gib(program_bytes(compiled))}; compile {watch.since(mark)}")
    kernels = kernel_count(compiled, "FusedLAMB step", expect_kernels)
    for _ in range(steps):
        state = compiled(state, grads)
    check(int(state.count) == steps, f"FusedLAMB took {int(state.count)} "
          f"steps, not {steps}")
    check(float(state.found_inf) == 0.0, "FusedLAMB saw a non-finite grad")
    check(bool(jnp.isfinite(state.master).all()),
          "FusedLAMB: non-finite parameters")
    check(not np.array_equal(first, np.asarray(state.master[:4096])),
          "FusedLAMB did not move the parameters")
    say(f"FusedLAMB took {steps} steps; memory: "
        f"{peak_line([jax.devices()[0]])}")
    return {"kernels": kernels}


# -- phase: server ----------------------------------------------------------


def greedy_reference(model, params, prompt, generated, dtype):
    """A plain ``model.apply`` over the whole served sequence (padded on
    the right to a multiple of 128, which a causal model's earlier rows
    do not see): at every generated position the served token must be
    the reference's argmax, or a near-tie with it — within one ulp, in
    the activations' dtype, of that row's largest logit, since the two
    paths round the activations at different points. Returns ``(exact
    matches, worst gap, its tolerance)``."""
    import jax
    import jax.numpy as jnp

    toks = np.concatenate([prompt, generated[:-1]]).astype(np.int32)
    first, n = len(prompt) - 1, len(generated)  # row first+i predicts [i]
    toks = np.pad(toks, (0, -len(toks) % 128))[None]
    rows = np.asarray(jax.jit(
        lambda p, t: model.apply(p, t)[first:first + n, 0])(
            params, jnp.asarray(toks)))
    gap = rows.max(-1) - rows[np.arange(n), generated]
    tol = float(jnp.finfo(dtype).eps) * np.abs(rows).max(-1)
    worst = int(np.argmax(gap - tol))
    check(bool((gap <= tol).all()),
          f"served greedy tokens disagree with model.apply: token "
          f"{worst} trails the reference's best logit by "
          f"{gap[worst]:.4f} (tolerance {tol[worst]:.4f})")
    return int((gap == 0).sum()), float(gap[worst]), float(tol[worst])


def serve_phase(cfg, *, num_blocks, max_batch, prefill_chunk, requests,
                past, watch, expect_kernels, block_size=16,
                min_width_bucket=32, min_seq_bucket=32):
    """``requests`` is ``[(id, prompt_len, max_new)]``, each checked
    against ``model.apply``; one request's context must pass ``past``
    tokens while decoding and one prompt must exceed
    ``prefill_chunk``."""
    import jax
    import jax.numpy as jnp

    from apex_tpu import serving
    from apex_tpu.models.gpt import GPTModel
    from apex_tpu.serving.kv_cache import bucket

    rng = np.random.RandomState(SEED)
    model = GPTModel(cfg)
    params = model.init(
        jax.random.PRNGKey(SEED),
        jnp.asarray(rng.randint(0, cfg.vocab_size, (1, 8)), jnp.int32))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    cache = serving.KVCache.for_config(cfg, num_blocks=num_blocks,
                                       block_size=block_size)
    say(f"server: {n_params / 1e6:.1f}M parameters, hidden "
        f"{cfg.hidden_size}, {cfg.num_layers} layers, {cfg.num_heads} "
        f"heads / {cfg.kv_heads} KV heads, context {cfg.max_seq_len}, "
        f"vocab {cfg.vocab_size}, {jnp.dtype(cfg.dtype).name}; pool "
        f"{num_blocks} blocks x {block_size} tokens = "
        f"{gib(cache.pool_bytes())}, max batch {max_batch}, prefill "
        f"chunk {prefill_chunk}")
    step_fn = serving.make_decode_step(model, cache)
    engine = serving.ContinuousBatcher(
        model, params, cache, max_batch=max_batch, step_fn=step_fn,
        min_width_bucket=min_width_bucket, min_seq_bucket=min_seq_bucket,
        prefill_chunk=prefill_chunk)
    state = cache.init_state()
    prompts = {rid: rng.randint(0, cfg.vocab_size, (plen,))
               for rid, plen, _ in requests}
    check(any(plen > prefill_chunk for _, plen, _ in requests),
          "no request is prefilled in chunks")
    check(any(plen <= past < plen + new for _, plen, new in requests),
          f"no request's context passes {past} tokens while decoding")
    for rid, _, max_new in requests:
        engine.submit(serving.Request(id=rid, prompt=prompts[rid],
                                      max_new_tokens=max_new))
    mark = watch.mark()
    steps, steady = 0, []
    limit = 4 * sum(new + plen // prefill_chunk + 1
                    for _, plen, new in requests)
    while not engine.idle():
        programs = sum(step_fn.compile_keys().values())
        t0 = time.perf_counter()
        state, _ = engine.step(state)
        steps += 1
        if sum(step_fn.compile_keys().values()) == programs:
            steady.append(time.perf_counter() - t0)
        check(steps <= limit, f"server still busy after {steps} steps")
    results = {r.id: r for r in engine.drain()}
    keys = step_fn.compile_keys()
    say(f"server answered {len(results)} request(s) in {steps} engine "
        f"steps; programs compiled on the way: {keys}; "
        f"{watch.since(mark)}; median of the {len(steady)} steps that "
        f"met no new program: {np.median(steady) * 1e3:.1f} ms")
    for rid, plen, max_new in requests:
        res = results[rid]
        say(f"request {rid}: prompt {plen}, asked {max_new}, got "
            f"{len(res.tokens)} token(s), finished by "
            f"{res.finish_reason!r}"
            + (f" ({res.error})" if res.error else ""))
        check(res.finish_reason == "length" and len(res.tokens) == max_new,
              f"request {rid} did not finish with its {max_new} tokens: "
              f"{res.finish_reason!r} {res.error or ''}")
    check(keys["prefill_chunk"] > 0, "no chunked prefill program ran")
    check(cache.blocks_in_use == 0, "the pool still holds blocks")

    # the decode program the longest context ran in, from shapes alone
    width = bucket(
        cache.blocks_for(max(plen + new for _, plen, new in requests)),
        min_width_bucket)
    mark = watch.mark()
    compiled = step_fn.lower("decode_step", params, state, max_batch,
                             width).compile()
    say(f"decode step at batch {max_batch}, context "
        f"{width * block_size}: program {gib(program_bytes(compiled))}; "
        f"compile {watch.since(mark)}")
    kernels = kernel_count(compiled, "decode step", expect_kernels)

    for rid, plen, _ in requests:
        served = np.asarray(results[rid].tokens)
        exact, gap, tol = greedy_reference(model, params, prompts[rid],
                                           served, cfg.dtype)
        say(f"request {rid} against a plain model.apply over its "
            f"{plen + len(served) - 1} tokens: {exact} of {len(served)} "
            f"served tokens are the reference's argmax, the nearest to "
            f"its tolerance trails the best logit by {gap:.4f} "
            f"(tolerance {tol:.4f})")
    say(f"server memory: {peak_line([jax.devices()[0]])}")
    return {"kernels": kernels, "programs": keys,
            "tokens": {rid: len(r.tokens) for rid, r in results.items()}}


# -- phase: the path across chips -------------------------------------------


def mesh_phase(cfg, *, batch_axis, model_axis, batches, steps, watch,
               expect_kernels, lr=3e-4):
    """The GSPMD mesh train step on ``batch_axis x model_axis`` devices
    against the same seed and global batch on a one-device mesh."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from apex_tpu import mesh as gmesh
    from apex_tpu.models.pretrain import (
        init_gpt_pretrain_params,
        make_gpt_pretrain_step,
    )
    from apex_tpu.optimizers import FusedAdam

    devices = jax.devices()[:batch_axis * model_axis]

    def build(**kw):
        params = init_gpt_pretrain_params(cfg, jax.random.PRNGKey(SEED))
        return make_gpt_pretrain_step(
            cfg, FusedAdam(lr=lr, weight_decay=0.01), **kw)(params)

    # what it is compared with: one device, same seed, same global batch
    one = Mesh(np.asarray(devices[:1]).reshape(1, 1, 1), gmesh.MESH_AXES)
    step, state = build(mesh=one)
    batch, _ = pick_batch(step, state, cfg, batches, devices[0], watch)
    tokens, labels = seeded_tokens(SEED, batch, cfg.max_seq_len,
                                   cfg.vocab_size)
    state, ref = run_steps(step, state, tokens, labels, steps, watch,
                           "one-device")
    del step, state

    # as a user calls it, where the mesh takes every device there is
    # (the chip run); tier-1 takes four of the CPU's eight
    mesh = gmesh.initialize_mesh(
        batch=batch_axis, model=model_axis,
        devices=None if len(jax.devices()) == len(devices) else devices)
    try:
        say(f"mesh {dict(zip(mesh.axis_names, mesh.devices.shape))}, "
            f"device ids in mesh order: "
            f"{[d.id for d in mesh.devices.flatten()]}")
        step, state = build()
        tok = jax.ShapeDtypeStruct((batch, cfg.max_seq_len), jnp.int32)
        mark = watch.mark()
        compiled = step.lower(state, tok, tok).compile()
        text = compiled.as_text()
        found = {op: len(re.findall(rf"\b{op}(?:-start)?\(", text))
                 for op in ("all-reduce", "all-gather", "reduce-scatter",
                            "all-to-all", "collective-permute")}
        say(f"mesh train step at global batch {batch}: "
            f"{gib(program_bytes(compiled))} on each device; collectives "
            f"in the program: {found}; compile {watch.since(mark)}")
        check(sum(found.values()) > 0, "no collective in the mesh program")
        kernels = kernel_count(compiled, "mesh train step", expect_kernels)
        state, got = run_steps(step, state, tokens, labels, steps, watch,
                               "mesh")
        say(f"memory after the mesh steps: {peak_line(devices)}")
        held = [stats["bytes_in_use"] for stats in
                (d.memory_stats() for d in devices) if stats]
        check(not held or min(held) > 0.5 * max(held),
              f"the devices do not all hold memory: {held}")
    finally:
        gmesh.destroy_mesh()
    worst = max(abs(a - b) for a, b in zip(ref, got))
    say(f"losses, one device: {[round(x, 4) for x in ref]}; mesh: "
        f"{[round(x, 4) for x in got]}; worst |difference| {worst:.4f} "
        f"(tolerance {MESH_LOSS_TOL})")
    check(worst <= MESH_LOSS_TOL,
          f"mesh and one-device losses differ by {worst}")
    return {"batch": batch, "losses": got, "reference": ref,
            "kernels": kernels}


# -- the run ----------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: the mesh train step across four chips and "
                         "what it is compared with, no other phase")
    args = ap.parse_args(argv)

    from apex_tpu import compile_cache

    cache_dir = compile_cache.enable()

    import jax
    import jax.numpy as jnp

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU — jax found {len(devices)} "
                 f"{devices[0].platform} device(s)")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"chips, jax found {len(devices)}")

    from apex_tpu import runtime
    from apex_tpu.models.gpt import GPTConfig
    from apex_tpu.telemetry.cost import chip_peak_tflops

    kind = devices[0].device_kind
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed as a package"
    say(f"jax {jax.__version__}, libtpu {libtpu}; {len(devices)} device(s) "
        f"of kind {kind!r} ({chip_peak_tflops(kind)} peak bf16 TFLOP/s in "
        f"the table), {gib(memory_limit(devices[0]))} each")
    say(f"compilation cache at {cache_dir}")
    say("host runtime: " + (
        "native library, built from apex_tpu/csrc/host_runtime.cpp"
        if runtime.native_available() else "numpy substitute"))

    watch = CompileWatch()
    gpt2 = GPTConfig.gpt2_345m(attention_backend="flash",
                               dtype=jnp.bfloat16)
    if args.chips == 4:
        mesh_phase(gpt2, batch_axis=2, model_axis=2, batches=(4, 2),
                   steps=3, watch=watch, expect_kernels=True)
    else:
        train_phase(gpt2, batches=(4, 2, 1), steps=4, watch=watch,
                    expect_kernels=True)
        lamb_phase(gpt2, steps=2, watch=watch, expect_kernels=True)
        serving_cfg = GPTConfig(
            vocab_size=32768, max_seq_len=2048, hidden_size=1024,
            num_layers=12, num_heads=16, num_kv_heads=4,
            dtype=jnp.bfloat16)
        serve_phase(
            serving_cfg, num_blocks=16384, max_batch=16,
            prefill_chunk=256, past=1024, watch=watch, expect_kernels=True,
            requests=[("short", 24, 16), ("short-2", 40, 12),
                      ("chunked", 600, 8), ("long", 1000, 48)])
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
