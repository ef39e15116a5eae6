"""Closed-loop serving of a fixed deck by a model built from a layer
pattern (``apex_tpu/models/decoder.py``): ``serve_closed``'s rule and
its clock, for models other than ``GPTModel``.

The deck, the clients, the dry deal and the step loop are
``serve_closed``'s own (imported, not copied); what differs is the
model that is built, the programs' keys (a model with window layers
dispatches two block tables a lane, and its keys carry both widths),
two counters read off the engine (positions gathered a step for a
window layer and for a full one) and the reference that decides
``correct`` (``benchmark/reference_trinity.py``), which is given the
program's own choice of experts (:func:`program_choice`). The counters and
samples carry ``serve_closed``'s names, so the per-layer metrics of the
serving cells read this driver's runs as they read that one's.

The one rule holds: the sequence of programs and shapes is a function
of the cell's files alone.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List

import numpy as np

from benchmark import common, reference_trinity
from benchmark.drivers.serve_closed import (Clients, Recorder, deal,
                                            make_engine, warm)

KINDS = {"sliding_attention": "window", "full_attention": "full"}


def decoder_config(config: dict):
    """The program's ``DecoderConfig`` for a configuration file: the
    source's widths under the source's own keys; the experts held and
    the router's width from ``deployment``."""
    import jax.numpy as jnp

    from apex_tpu.models.decoder import DecoderConfig

    assumed, deployment = config["assumed"], config["deployment"]
    dense = config["num_dense_layers"]
    layers = tuple(
        (KINDS[kind], "dense" if i < dense else "experts")
        for i, kind in enumerate(config["layer_types"]))
    if (len(layers) != config["num_hidden_layers"]
            or deployment["held_experts"][1] != config["num_experts"]):
        raise ValueError(
            "the configuration disagrees with itself: layer_types against "
            "num_hidden_layers, or the experts held against num_experts")
    return DecoderConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        max_seq_len=config["engine"]["max_context"], layers=layers,
        ffn_hidden_size=config["intermediate_size"],
        attention_window=(config["sliding_window"]
                          if any(a == "window" for a, _ in layers) else None),
        expert_ffn_size=config["moe_intermediate_size"],
        num_experts=deployment["router_width"],
        experts_per_token=config["num_experts_per_tok"],
        held_experts=tuple(deployment["held_experts"]),
        shared_ffn_size=(config["num_shared_experts"]
                         * config["moe_intermediate_size"]),
        route_scale=config["route_scale"], rms_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_theta"]),
        dtype=jnp.dtype(assumed["dtype"]),
        param_dtype=jnp.dtype(assumed["param_dtype"]))


def arch_of(cfg) -> "reference_trinity.Arch":
    return reference_trinity.Arch(
        num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
        head_dim=cfg.head_dim, layers=cfg.layers,
        window=cfg.attention_window, top_k=cfg.experts_per_token,
        route_scale=cfg.route_scale, held=cfg.held_experts,
        eps=cfg.rms_eps, theta=cfg.rope_theta)


def init_params(cfg, seed: int, assumed: dict):
    """The model's parameters, made on the device from the seed, leaf
    by leaf from the tree's shapes (no forward pass runs, and no two
    of the large float32 draws are alive at once): matrices
    N(0, init_std^2), gains 1, the selection bias
    N(0, select_bias_std^2)."""
    import functools

    import jax
    import jax.numpy as jnp

    from apex_tpu.models.decoder import PatternDecoder

    shapes = jax.eval_shape(
        lambda key: PatternDecoder(cfg).init(
            key, jnp.zeros((1, 8), jnp.int32)), jax.random.PRNGKey(0))
    leaves, tree = jax.tree_util.tree_flatten_with_path(shapes)

    @functools.partial(jax.jit, static_argnames=("shape", "dtype", "std"))
    def draw(key, *, shape, dtype, std):
        return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)

    key, out = common.seed_key(seed), []
    for i, (path, leaf) in enumerate(leaves):
        name = str(getattr(path[-1], "key", path[-1]))
        if name == "scale":
            out.append(jnp.ones(leaf.shape, leaf.dtype))
            continue
        std = assumed["select_bias_std" if name == "select_bias"
                      else "init_std"]
        out.append(draw(jax.random.fold_in(key, i), shape=leaf.shape,
                        dtype=leaf.dtype, std=std))
    return tree.unflatten(out)


class PatternRecorder(Recorder):
    """``serve_closed.Recorder`` for dispatches that may carry the
    window layers' tables: the key then ends in their width, as
    ``DecodeStep``'s own keys do."""

    def _key(self, key, window):
        return key if window is None else (*key, window[0].shape[1])

    def prefill_chunk(self, params, state, tokens, starts, lengths, tables,
                      sampling=None, window=None):
        return self._out(self._key(("prefill_chunk", *tokens.shape,
                                    tables.shape[1]), window), state)

    def decode(self, params, state, tokens, positions, tables,
               sampling=None, window=None):
        return self._out(self._key(("decode_step", tokens.shape[0],
                                    tables.shape[1]), window), state)


def reachable_programs(model, cfg, engine_cfg, deck, seed, vocab, steps):
    """``serve_closed.reachable_programs`` over a ``PatternRecorder``;
    also the step at which the last of the deck's first requests (one a
    client) had ended."""
    recorder = PatternRecorder()
    engine, _ = make_engine(model, None, cfg, engine_cfg, recorder)
    first_wave = {(c, 0) for c in range(len(deck))}
    ended_at = [0]

    def observe(i, t_submit, t_end, submitted, report, results):
        for res in results:
            if res.id in first_wave:
                first_wave.discard(res.id)
                ended_at[0] = i + 1

    deal(engine, None, Clients(deck, seed, vocab), lambda i: i >= steps,
         observe)
    return (list(dict.fromkeys(recorder.keys)), recorder.keys,
            None if first_wave else ended_at[0])


def warm_programs(step_fn, params, state, keys, has_window: bool):
    """Run each program once on zeros. Keys of a model without window
    layers are ``serve_closed``'s, and so is their warm-up."""
    import jax

    if not has_window:
        return warm(step_fn, params, state, keys)
    z = lambda *shape: np.zeros(shape, np.int32)  # noqa: E731
    out = None
    for fn, b, *rest in keys:
        if fn == "decode_step":
            out = step_fn.decode(params, state, z(b), z(b), z(b, rest[0]),
                                 window=(z(b, rest[1]), z(b)))
        elif fn == "prefill_step":
            out = step_fn.prefill(params, state, z(b, rest[0]), z(b),
                                  z(b, rest[1]))
        else:
            out = step_fn.prefill_chunk(
                params, state, z(b, rest[0]), z(b), z(b), z(b, rest[1]),
                window=(z(b, rest[2]), z(b)))
        state = out.cache
    if out is not None:
        jax.block_until_ready(out.next_token)
    return state


def program_choice(model, cfg, params, toks):
    """What the program's own routers chose over one sequence, outside
    the window: one pass of the model without the cache, applied with
    ``mutable=["routing"]``. For each expert layer, in order, ``(ids
    (n, k), biased scores (n, experts))``."""
    import jax

    @jax.jit
    def routed(params, toks):
        _, sown = model.apply(params, toks[None], mutable=["routing"])
        return sown["routing"]

    sown = jax.device_get(routed(params, toks))
    return [(sown[f"layer_{i}"]["mlp"]["ids"][0],
             sown[f"layer_{i}"]["mlp"]["biased"][0])
            for i, (_, mlp) in enumerate(cfg.layers) if mlp == "experts"]


def run(run) -> None:
    import jax
    import jax.numpy as jnp

    from apex_tpu.models.decoder import PatternDecoder

    config, traffic = run.config, run.traffic
    engine_cfg = config["engine"]
    cfg = decoder_config(config)
    model = PatternDecoder(cfg)
    deck, vocab = traffic["clients"], config["vocab_size"]
    lead, chunk = traffic["lead_in_steps"], engine_cfg["prefill_chunk"]
    max_batch = engine_cfg["max_batch"]
    window = cfg.attention_window

    keys, _, first_wave_ended = reachable_programs(
        model, cfg, engine_cfg, deck, run.seed, vocab,
        lead + traffic["horizon_steps"])
    run.mark("dry deal")
    params = jax.block_until_ready(
        init_params(cfg, run.seed, config["assumed"]))
    run.mark("weights")
    engine, cache = make_engine(model, params, cfg, engine_cfg)
    state = warm_programs(engine.step_fn, params, cache.init_state(), keys,
                          window is not None)
    run.mark("warm-up of the programs")
    run.notes.append(f"serve: {len(keys)} programs warmed: {keys}")
    run.notes.append(
        f"serve: the deck's first {len(deck)} requests have ended by step "
        f"{first_wave_ended}; the lead-in is {lead} steps")

    clients = Clients(deck, run.seed, vocab)
    left: Dict[Any, int] = {}            # prompt tokens not yet prefilled
    submitted_at: Dict[Any, float] = {}
    last_delivery: Dict[Any, float] = {}
    c = run.counters
    for k in ("steps", "decode_steps", "decoded", "prompt_tokens",
              "generated", "ended"):
        c[k] = 0
    s = run.samples
    for k in ("step_ms", "step_ms.decode_only", "ttft_ms", "itl_ms"):
        s[k] = []
    kept: List[tuple] = []               # (request, result) to check
    bad: List[str] = []

    def observe(i, t_submit, t_end, submitted, report, results):
        live = i >= lead                 # inside the window
        for req in submitted:
            left[req.id] = len(req.prompt)
            submitted_at[req.id] = t_submit
        got = list(report["decoded"])
        prompt_tokens = 0
        for rid in report["admitted"]:
            if left[rid] <= chunk:       # prefilled whole, this step
                prompt_tokens += left[rid]
                left[rid] = 0
                got.append(rid)
        for rid in report["prefilled"]:
            n = min(left[rid], chunk)
            prompt_tokens += n
            left[rid] -= n
            if left[rid] == 0:
                got.append(rid)
        for rid in dict.fromkeys(got):
            if live:
                if rid in last_delivery:
                    s["itl_ms"].append((t_end - last_delivery[rid]) * 1e3)
                else:
                    s["ttft_ms"].append((t_end - submitted_at[rid]) * 1e3)
            last_delivery[rid] = t_end
        for res in results:
            req = clients.open[res.id]
            for d in (left, submitted_at, last_delivery):
                d.pop(res.id, None)
            if (res.finish_reason != "length"
                    or len(res.tokens) != req.max_new_tokens):
                bad.append(f"{res.id}: {res.finish_reason} {res.error}")
            if live and len(kept) < traffic["check_among_first"]:
                kept.append((req, res))
        if not live:
            return
        c["steps"] += 1
        c["prompt_tokens"] += prompt_tokens
        c["generated"] += len(got)
        c["ended"] += len(results)
        ms = (t_end - t_submit) * 1e3
        s["step_ms"].append(ms)
        if report["decoded"]:
            c["decode_steps"] += 1
            c["decoded"] += len(report["decoded"])
            if not report["admitted"] and not report["prefilled"]:
                s["step_ms.decode_only"].append(ms)

    state, _ = deal(engine, state, clients, lambda i: i >= lead, observe)
    run.mark("lead-in")
    compiles0 = run.compiles.n
    gathered0 = dict(engine.gathered)    # a layer of each kind, so far
    t0 = time.perf_counter()
    run.setup_s = t0 - run.t_start
    done = lead

    def in_window(stop):
        nonlocal state, done
        state, n = deal(engine, state, clients, stop,
                        lambda i, *a: observe(i + done, *a))
        done += n

    if run.trace:
        with common.traced(run.trace_dir) as took:
            in_window(lambda i: i >= traffic["trace_steps"])
        t0 += took["overhead_s"]
        c["traced_steps"] = traffic["trace_steps"]
    in_window(lambda i: time.perf_counter() - t0 >= run.seconds)
    t1 = time.perf_counter()
    run.window_compilations = run.compiles.n - compiles0
    run.window_s = t1 - t0
    for kind, n in engine.gathered.items():
        c[f"gathered_{kind}"] = n - gathered0[kind]
    if done > lead + traffic["horizon_steps"]:
        bad.append(f"the window ran {done - lead} steps, past the "
                   f"{traffic['horizon_steps']} the programs were found for")

    run.end_to_end = {
        "serve_tok_s": (c["prompt_tokens"] + c["generated"]) / run.window_s,
        "itl_p95_ms": common.stat(s["itl_ms"], "p95"),
        "ttft_iqm_ms": common.stat(s["ttft_ms"], "iqm"),
        "setup_s": run.setup_s}
    run.facts.update(pool_blocks=engine_cfg["num_blocks"] + 1,
                     max_batch=max_batch)
    c["max_batch"] = max_batch
    run.attempted, run.failed = c["ended"], len(bad)

    # correct, outside the window: two finished requests against the
    # float32 reference: the shortest kept one, and the shortest whose
    # prompt passed the window by a chunk (its chunks and its decode
    # steps both crossed the window's edge). The reference is given the
    # program's own choice of experts (the configuration's
    # ``reference_tolerance_why``). In a traced run also: the engine
    # drains and the pool holds no block afterwards
    kept.sort(key=lambda rr: len(rr[0].prompt) + rr[0].max_new_tokens)
    past = (window or 0) + chunk
    picks = kept[:1] + [rr for rr in kept[1:]
                        if len(rr[0].prompt) > past][:1]
    ok = len(picks) == 2 and not bad
    if len(picks) < 2:
        run.notes.append(f"no kept request with a prompt over {past}: "
                         f"{len(kept)} kept")
    arch = arch_of(cfg)
    limits = dict(ulps=config["reference_tolerance_ulps"],
                  band=config["reference_choice_band"],
                  slack=config["reference_excused_margin"],
                  pad_to=engine_cfg["min_seq_bucket"],
                  dtype_eps=float(jnp.finfo(cfg.dtype).eps))
    excused = rows = 0
    for req, res in picks:
        toks, _ = reference_trinity.teacher_forced(
            req.prompt, res.tokens, limits["pad_to"])
        out = reference_trinity.check_served(
            params, arch, req.prompt, res.tokens,
            choice=program_choice(model, cfg, params, toks), **limits)
        ok = ok and out["ok"]
        excused += out["excused"]
        rows += out["rows"]
        run.notes.append(
            f"request {req.id}: prompt {len(req.prompt)}, {out['rows']} "
            f"tokens served, {out['exact']} of them the reference's argmax; "
            f"the reference followed the program's choice of experts in "
            f"{out['followed']} row(s) of the sequence (the worst misfit "
            f"{out['worst_misfit']:.5f}, band {limits['band']}) and refused "
            f"it in {out['refused']}; the worst row held to the reference "
            f"trails its best logit by {out['worst_ulps']:.3f} bf16 ulp(s) "
            f"of it (allowed {limits['ulps']}; row {out['worst_row']}, the "
            f"program's margin "
            f"{out['program_margin'][out['worst_row']]:.5f}); "
            f"{out['excused']} row(s) trail by more and are excused (the "
            f"worst by {out['worst_excused_ulps']:.2f}), of the "
            f"{out['may_differ']} whose margin by the program's own scores "
            f"is under {limits['slack']}; {out['held_pairs']} of "
            f"{out['pairs']} routed pairs landed on held experts "
            f"({100.0 * out['held_pairs'] / max(out['pairs'], 1):.1f}%)")
    if rows and excused > config["reference_excused_share_max"] * rows:
        ok = False
        run.notes.append(
            f"{excused} of {rows} checked rows excused: over the share "
            f"{config['reference_excused_share_max']} the cell allows")
    if run.trace:
        engine.take_queued()
        state, _ = deal(engine, state, None, lambda i: engine.idle())
        ok = ok and cache.blocks_in_use == 0
        run.notes.append(f"drained: {cache.blocks_in_use} block(s) held")
    run.correct = ok
    run.notes.extend(bad[:5])
    ms = np.asarray(s["step_ms"])
    stalled = ms > 2 * np.median(ms)
    run.notes.append(
        f"serve: the median step took {np.median(ms):.1f} ms, the slowest "
        + ", ".join(f"{ms[i]:.0f} ms (step {i})"
                    for i in np.argsort(ms)[::-1][:3])
        + f"; {int(stalled.sum())} step(s) took over twice the median "
        f"(chunk steps among them)")
    run.notes.append(
        f"serve: {c['steps']} steps, {c['prompt_tokens']} prompt + "
        f"{c['generated']} generated tokens, {c['ended']} requests ended, "
        f"{len(s['ttft_ms'])} TTFT and {len(s['itl_ms'])} gap samples; "
        f"gathered a window layer {c.get('gathered_window')} and a full "
        f"layer {c.get('gathered_full')} positions")
