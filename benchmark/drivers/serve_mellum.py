"""Closed-loop serving of a fixed deck by ``mellum2-12b-a2.5b``: the
pattern decoder (``apex_tpu/models/decoder.py``) under another block
than ``serve_pattern``'s ``afmoe``.

``serve_pattern.py`` reads ``afmoe``'s keys from its configuration file
and judges by ``reference_trinity``; this driver reads ``mellum``'s
(``rope_parameters`` by kind of layer, ``mlp_layer_types``,
``norm_topk_prob``) and judges by ``benchmark/reference_mellum.py``.
Everything that takes the model as an argument is imported, not
copied: the recorder whose keys carry both table widths, the dry deal,
the warm-up, the weights drawn leaf by leaf (``serve_pattern``), the
deck's clients, the engine, the deal and its clock (``serve_closed``).
What is repeated is the body of ``run``: ``serve_pattern.run``'s
counting, which builds its own model inside and so cannot be called
(``PERF.md`` section 7 asks a ``benchmark`` PR to fold the two). Beside
``serve_pattern``'s counters it reads ``ContinuousBatcher.held``: the
block-layers the live sequences hold at each step's end, and those of
them behind a window layer's window.

``correct`` checks, of the requests that ended inside the window among
the deck's first, the shortest and the shortest whose prompt is over
the traffic file's ``check_prompt_over`` (4096: a "repository" prompt,
prefilled in five chunks or more, past the window by thousands and
with its answer past where YaRN's scaled and unscaled frequencies
part), each against the float32 reference's full pass given the
program's choice of experts, within the limits the configuration file
states with their readings.

The one rule holds: the sequence of programs and shapes is a function
of the cell's files alone.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List

import numpy as np

from benchmark import common, reference_mellum
from benchmark.drivers.serve_closed import Clients, deal, make_engine
from benchmark.drivers.serve_pattern import (KINDS, init_params,
                                             reachable_programs,
                                             warm_programs)


def rotary_of(group: dict):
    """``rope_parameters``' group of one kind of layer as the program's
    ``Rotary``."""
    from apex_tpu.models.decoder import Rotary

    if group["rope_type"] == "default":
        return Rotary(float(group["rope_theta"]))
    if group["rope_type"] != "yarn":
        raise ValueError(f"rope_type {group['rope_type']!r} is not served")
    return Rotary(
        float(group["rope_theta"]), factor=float(group["factor"]),
        original_max_position=group["original_max_position_embeddings"],
        beta_fast=float(group["beta_fast"]),
        beta_slow=float(group["beta_slow"]),
        attention_factor=float(group["attention_factor"]))


def decoder_config(config: dict):
    """The program's ``DecoderConfig`` for a configuration file: the
    source's widths under the source's own keys; the block as
    ``assumed`` states it; the experts held and the router's width from
    ``deployment``."""
    import jax.numpy as jnp

    from apex_tpu.models.decoder import DecoderConfig

    assumed, deployment = config["assumed"], config["deployment"]
    layers = tuple((KINDS[kind], "experts")
                   for kind in config["layer_types"])
    held = tuple(deployment["held_experts"])
    if (len(layers) != config["num_hidden_layers"]
            or set(config["mlp_layer_types"]) != {"sparse"}
            or len(config["mlp_layer_types"]) != len(layers)
            or not config["norm_topk_prob"] or config["attention_bias"]
            or deployment["router_width"] != config["num_experts"]):
        raise ValueError(
            "the configuration disagrees with itself or with the block "
            "this driver builds: layer_types and mlp_layer_types (all "
            "sparse) against num_hidden_layers, a renormalised top-k, no "
            "attention bias, the router's width against num_experts")
    return DecoderConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        max_seq_len=config["engine"]["max_context"], layers=layers,
        ffn_hidden_size=config["intermediate_size"],
        attention_window=config["sliding_window"],
        expert_ffn_size=config["moe_intermediate_size"],
        num_experts=config["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        held_experts=None if held == (0, config["num_experts"]) else held,
        rms_eps=config["rms_norm_eps"], norms="pre", embedding_scale=False,
        output_gate=False, router="softmax",
        rotary=tuple(
            (KINDS[kind], rotary_of(config["rope_parameters"][kind]))
            for kind in dict.fromkeys(config["layer_types"])),
        dtype=jnp.dtype(assumed["dtype"]),
        param_dtype=jnp.dtype(assumed["param_dtype"]))


def arch_of(config: dict, cfg) -> "reference_mellum.Arch":
    """The reference's numbers, from the configuration file's own keys
    (not from the program's ``Rotary``)."""
    rope = config["rope_parameters"]
    full = rope["full_attention"]
    return reference_mellum.Arch(
        num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
        head_dim=cfg.head_dim, layers=cfg.layers,
        window=cfg.attention_window, top_k=cfg.experts_per_token,
        theta=float(rope["sliding_attention"]["rope_theta"]),
        yarn=reference_mellum.Yarn(
            theta=float(full["rope_theta"]), factor=float(full["factor"]),
            original=full["original_max_position_embeddings"],
            beta_fast=float(full["beta_fast"]),
            beta_slow=float(full["beta_slow"]),
            attention_factor=float(full["attention_factor"])),
        held=cfg.held_experts, eps=cfg.rms_eps)


def program_choice(model, cfg, params, toks):
    """What the program's own routers chose over one sequence, outside
    the window: one pass of the model without the cache, applied with
    ``mutable=["routing"]``. For each layer, in order, ``(ids (n, k),
    probabilities (n, experts))``."""
    import jax

    @jax.jit
    def routed(params, toks):
        _, sown = model.apply(params, toks[None], mutable=["routing"])
        return sown["routing"]

    sown = jax.device_get(routed(params, toks))
    return [(sown[f"layer_{i}"]["mlp"]["ids"][0],
             sown[f"layer_{i}"]["mlp"]["probs"][0])
            for i in range(cfg.num_layers)]


def mark(run, label: str) -> None:
    """``run.mark``, said at once: a run that stalls in set-up shows
    where (``run.py`` prints the phases when the run has ended)."""
    run.mark(label)
    print(f"# set-up so far: {run.phases[-1]}", flush=True)


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

    keys, _, first_wave_ended = reachable_programs(
        model, cfg, engine_cfg, deck, run.seed, vocab,
        lead + traffic["horizon_steps"])
    mark(run, "dry deal")
    params = jax.block_until_ready(
        init_params(cfg, run.seed, config["assumed"]))
    mark(run, "weights")
    engine, cache = make_engine(model, params, cfg, engine_cfg)
    state = warm_programs(engine.step_fn, params, cache.init_state(), keys,
                          True)
    mark(run, "warm-up of the programs")
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
    mark(run, "lead-in")
    compiles0 = run.compiles.n
    gathered0 = dict(engine.gathered)    # a layer of each kind, so far
    held0 = dict(engine.held)            # the live sequences' blocks
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
    for what, n in engine.held.items():
        c[f"held_{what}"] = n - held0[what]
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
    # prompt is over ``check_prompt_over`` (module docstring). The
    # reference is given the program's own choice of experts (the
    # configuration's ``reference_tolerance_why``). In a traced run
    # also: the engine drains and the pool holds no block afterwards
    kept.sort(key=lambda rr: len(rr[0].prompt) + rr[0].max_new_tokens)
    past = traffic["check_prompt_over"]
    picks = kept[:1] + [rr for rr in kept[1:]
                        if len(rr[0].prompt) > past][:1]
    ok = len(picks) == 2 and not bad
    if len(picks) < 2:
        run.notes.append(f"no kept request with a prompt over {past}: "
                         f"{len(kept)} kept")
    arch = arch_of(config, cfg)
    limits = dict(ulps=config["reference_tolerance_ulps"],
                  band=config["reference_choice_band"],
                  slack=config["reference_excused_margin"],
                  pad_to=engine_cfg["min_seq_bucket"],
                  dtype_eps=float(jnp.finfo(cfg.dtype).eps))
    excused = rows = 0
    for req, res in picks:
        toks, _ = reference_mellum.teacher_forced(
            req.prompt, res.tokens, limits["pad_to"])
        out = reference_mellum.check_served(
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
            f"{out['worst_misfit']:.5f} of log probability, band "
            f"{limits['band']}) and refused "
            f"it in {out['refused']}; the worst row held to the reference "
            f"trails its best logit by {out['worst_ulps']:.3f} bf16 ulp(s) "
            f"of it (allowed {limits['ulps']}; row {out['worst_row']}, the "
            f"program's margin "
            f"{out['program_margin'][out['worst_row']]:.5f}); "
            f"{out['excused']} row(s) trail by more and are excused (the "
            f"worst by {out['worst_excused_ulps']:.2f}), of the "
            f"{out['may_differ']} whose margin by the program's own scores "
            f"is under {limits['slack']}; {out['held_pairs']} of "
            f"{out['pairs']} routed pairs landed on held experts")
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
        f"layer {c.get('gathered_full')} positions; at the steps' ends the "
        f"live sequences held {c['held_block_layers']} block-layers, "
        f"{c['held_behind_window']} of them behind a window layer's window")
