"""Closed-loop serving of a fixed deck by ``ai21-jamba2-3b``: the
pattern decoder (``apex_tpu/models/decoder.py``) with thirteen Mamba-1
layers (``mamba1``: a decay for every channel and state) and one
attention layer of one KV head a period, whose sessions hold a
recurrent state of fixed size beside their paged K/V.

This driver reads ``jamba``'s keys (``attn_layer_period`` /
``attn_layer_offset``, the ``mamba_*`` sizes, ``num_experts`` 1) and
judges by ``benchmark/reference_jamba.py``. What takes the model as an
argument is imported, not copied: the deck's clients, the deal and the
dry deal's recorder (``serve_closed``); the engine with its state
slots, the recorder of a model whose dispatches name their slots, the
choice of the two checked requests, :class:`serve_granite.Keeping`
(the lanes' logits kept from the timed dispatches, found by the state
slot the engine named) and the warm-up (``serve_granite``); ``set-up so
far`` (``serve_mellum``). This driver's own: the configuration's
reading (:func:`decoder_config`), Mamba-1's published initialisation
(:func:`init_params`) and the body of ``run``, which is
``serve_granite.run``'s counting over a model that routes nothing
(that body builds its own model inside and so cannot be called;
``PERF.md`` section 7 asks a ``benchmark`` PR to fold the drivers).

``correct`` checks two requests that ended inside the window among the
deck's first (:func:`serve_granite.checked`): a *turn* whose whole
prompt was one padded whole-prompt prefill, served in a state slot an
earlier request had left, and a prompt over ``check_prompt_over``
(1024: two chunks, the state handed on between them), each then
decoded beside 255 other lanes. Their logits, kept from the dispatches
that served them, are held to the float32 reference's full pass (the
recurrence a scan over tokens), and their tokens to its argmax. After
the window the engine is drained, in every run, and must then hold no
block and no slot.

The one rule holds: the sequence of programs and shapes is a function
of the cell's files alone.
"""

from __future__ import annotations

import gc
import time
from typing import Any, Dict, List

import numpy as np

from benchmark import common, reference_jamba
from benchmark.drivers.serve_closed import Clients, deal
from benchmark.drivers.serve_granite import (Keeping, checked, make_engine,
                                             reachable_programs, warm)
from benchmark.drivers.serve_mellum import mark


def layer_kinds(config: dict):
    """``mamba`` or ``attention`` for each layer: attention where ``i %
    attn_layer_period == attn_layer_offset``."""
    return tuple("attention" if i % config["attn_layer_period"]
                 == config["attn_layer_offset"] else "mamba"
                 for i in range(config["num_hidden_layers"]))


def decoder_config(config: dict):
    """The program's ``DecoderConfig`` for a configuration file: the
    source's widths under the source's own keys; the block as
    ``assumed`` states it."""
    import jax.numpy as jnp

    from apex_tpu.models.decoder import DecoderConfig, SelectiveSSMConfig

    hidden, heads = config["hidden_size"], config["num_attention_heads"]
    if (config["num_experts"] != 1 or config["mamba_proj_bias"]
            or not config["mamba_conv_bias"]
            or config["sliding_window"] is not None
            or not config["tie_word_embeddings"] or hidden % heads
            or config["hidden_act"] != "silu"):
        raise ValueError(
            "the configuration disagrees with the block this driver builds: "
            "one expert (a dense MLP on every layer), no projection bias and "
            "a convolution bias, attention over every key, a tied head, "
            "head_dim = hidden_size / num_attention_heads, SiLU")
    layers = tuple(("full" if kind == "attention" else "mamba1", "dense")
                   for kind in layer_kinds(config))
    assumed = config["assumed"]
    return DecoderConfig(
        vocab_size=config["vocab_size"], hidden_size=hidden,
        num_heads=heads, num_kv_heads=config["num_key_value_heads"],
        head_dim=hidden // heads,
        max_seq_len=config["engine"]["max_context"], layers=layers,
        ffn_hidden_size=config["intermediate_size"],
        rms_eps=config["rms_norm_eps"], norms="pre", output_gate=False,
        qk_norm=False, embedding_scale=False, tied_head=True,
        selective=SelectiveSSMConfig(
            inner=config["mamba_expand"] * hidden,
            state_size=config["mamba_d_state"],
            dt_rank=config["mamba_dt_rank"],
            conv_width=config["mamba_d_conv"]),
        dtype=jnp.dtype(assumed["dtype"]),
        param_dtype=jnp.dtype(assumed["param_dtype"]))


def arch_of(config: dict, cfg) -> "reference_jamba.Arch":
    """The reference's numbers, from the configuration file's own
    keys."""
    return reference_jamba.Arch(
        num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
        head_dim=cfg.head_dim, layers=layer_kinds(config),
        inner=config["mamba_expand"] * config["hidden_size"],
        state=config["mamba_d_state"], dt_rank=config["mamba_dt_rank"],
        eps=config["rms_norm_eps"])


def init_params(cfg, seed: int, assumed: dict):
    """The model's parameters, made on the device from the seed, leaf
    by leaf from the tree's shapes (no forward pass runs): matrices
    N(0, init_std^2), norm gains and ``D`` 1, and the Mamba layers'
    own by Mamba's published initialisation (``assumed``'s
    ``init_why``)."""
    import functools

    import jax
    import jax.numpy as jnp

    from apex_tpu.models import ssm
    from apex_tpu.models.decoder import PatternDecoder

    shapes = jax.eval_shape(
        lambda key: PatternDecoder(cfg).init(
            key, jnp.zeros((1, 8), jnp.int32)), jax.random.PRNGKey(0))
    leaves, tree = jax.tree_util.tree_flatten_with_path(shapes)
    rank = cfg.selective.dt_rank

    @functools.partial(jax.jit, static_argnames=("shape", "dtype", "how"))
    def draw(key, *, shape, dtype, how):
        if how == "A_log":
            return ssm.a_log_range_init(key, shape, dtype)
        if how == "dt_bias":
            return ssm.dt_bias_init(key, shape, dtype)
        if how == "conv":
            return ssm.symmetric_uniform(0.5)(key, shape, dtype)
        if how == "dt_proj":
            return ssm.symmetric_uniform(rank ** -0.5)(key, shape, dtype)
        return (assumed["init_std"]
                * jax.random.normal(key, shape, jnp.float32)).astype(dtype)

    key, out = common.seed_key(seed), []
    for i, (path, leaf) in enumerate(leaves):
        name = str(getattr(path[-1], "key", path[-1]))
        if name in ("scale", "D", "dt_norm", "B_norm", "C_norm"):
            out.append(jnp.ones(leaf.shape, leaf.dtype))
            continue
        how = {"A_log": "A_log", "dt_bias": "dt_bias", "conv_w": "conv",
               "conv_b": "conv", "dt_proj": "dt_proj"}.get(name, "normal")
        out.append(draw(jax.random.fold_in(key, i), shape=leaf.shape,
                        dtype=leaf.dtype, how=how))
    return tree.unflatten(out)


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

    past = traffic["check_prompt_over"]
    keys, _, ended = reachable_programs(
        model, cfg, engine_cfg, deck, run.seed, vocab,
        lead + traffic["horizon_steps"])
    # whom to follow: the window's own choice below, made beforehand
    follow = checked([req for at, req in ended if at > lead]
                     [:traffic["check_among_first"]], chunk, past)
    mark(run, "dry deal")
    params = jax.block_until_ready(
        init_params(cfg, run.seed, config["assumed"]))
    mark(run, "weights")
    engine, cache = make_engine(model, params, cfg, engine_cfg)
    engine.step_fn = kept_rows = Keeping(engine.step_fn, {
        int(req.prompt[0]): (req.id, req.max_new_tokens) for req in follow})
    state = warm(engine.step_fn, params, cache.init_state(), keys,
                 cache.state_slots)
    mark(run, "warm-up of the programs")
    run.notes.append(f"serve: {len(keys)} programs warmed: {keys}")
    run.notes.append(
        f"serve: the lead-in is {lead} steps; followed for their logits: "
        + ", ".join(f"{req.id} (prompt {len(req.prompt)}, ends by step "
                    f"{next(at for at, r in ended if r is req)})"
                    for req in follow))

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
    step_at: List[float] = []            # when each step of the window began

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
        step_at.append(t_submit)
        if report["decoded"]:
            c["decode_steps"] += 1
            c["decoded"] += len(report["decoded"])
            if not report["admitted"] and not report["prefilled"]:
                s["step_ms.decode_only"].append(ms)

    state, _ = deal(engine, state, clients, lambda i: i >= lead, observe)
    mark(run, "lead-in")
    compiles0 = run.compiles.n
    gathered0 = dict(engine.gathered)    # a layer of each kind, so far
    held0 = dict(engine.held)            # what the live sequences hold
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
    # for the readers: the pools' leading sizes, trash block and trash
    # slot counted (a state pool's shape starts with state_slots)
    run.facts.update(pool_blocks=engine_cfg["num_blocks"] + 1,
                     max_batch=max_batch,
                     state_slots=engine_cfg["state_slots"] + 1)
    c["max_batch"] = max_batch
    run.attempted, run.failed = c["ended"], len(bad)

    # correct, after the window (module docstring). First the engine
    # drains: what is left must be nothing
    engine.take_queued()
    state, _ = deal(engine, state, None, lambda i: engine.idle())
    ok = not bad and cache.blocks_in_use == 0 and cache.slots_in_use == 0
    run.notes.append(f"drained: {cache.blocks_in_use} block(s) and "
                     f"{cache.slots_in_use} state slot(s) held")
    del state
    served = {req.id: res for req, res in kept}
    picks = checked([req for req, _ in kept], chunk, past)
    if len(picks) < 2 or [r.id for r in picks] != [r.id for r in follow]:
        ok = False
        run.notes.append(
            f"of {len(kept)} kept requests, no turn of {chunk} or fewer in a "
            f"reused slot, or no prompt over {past}, or not the requests "
            f"followed ({[r.id for r in picks]} for "
            f"{[r.id for r in follow]}: the window ended before theirs)")
        picks = [r for r in picks if r.id in kept_rows.rows]
    arch = arch_of(config, cfg)
    limits = dict(ulps=config["reference_tolerance_ulps"],
                  logit_limit=config["reference_logit_error_max"],
                  rms_limit=config["reference_logit_rms_max"],
                  pad_to=engine_cfg["min_seq_bucket"],
                  dtype_eps=float(jnp.finfo(cfg.dtype).eps))
    for req in picks:
        res = served[req.id]
        logits = jnp.stack(kept_rows.rows.pop(req.id))
        if len(logits) != len(res.tokens):
            ok = False
            run.notes.append(
                f"request {req.id}: {len(logits)} rows of logits kept for "
                f"{len(res.tokens)} served tokens")
            continue
        out = reference_jamba.check_served(
            params, arch, req.prompt, res.tokens, program_logits=logits,
            **limits)
        del logits
        ok = ok and out["ok"]
        run.notes.append(
            f"request {req.id}: prompt {len(req.prompt)}, {out['rows']} "
            f"tokens served, {out['exact']} of them the reference's argmax; "
            f"the worst trails the reference's best logit by "
            f"{out['worst_ulps']:.3f} bf16 ulp(s) of it (allowed "
            f"{limits['ulps']}; row {out['worst_row']}); kept from the "
            f"dispatches that served them, the logits leave the reference's "
            f"by {out['logit_error']:.3g} of its largest at the worst row "
            f"(allowed {limits['logit_limit']}; row "
            f"{out['logit_worst_row']}), {out['logit_rms']:.4g} in the root "
            f"mean square over the rows (allowed {limits['rms_limit']})")
    run.correct = ok
    run.notes.extend(bad[:5])
    ms = np.asarray(s["step_ms"])
    stalled = ms > 2 * np.median(ms)
    run.notes.append(
        f"serve: the median step took {np.median(ms):.1f} ms, the slowest "
        + ", ".join(f"{ms[i]:.0f} ms (step {i}, {step_at[i] - t0:.2f} s in)"
                    for i in np.argsort(ms)[::-1][:3])
        + f"; {int(stalled.sum())} step(s) took over twice the median "
        f"(chunk steps among them)")
    run.notes.append(
        f"serve: {c['steps']} steps, {c['prompt_tokens']} prompt + "
        f"{c['generated']} generated tokens, {c['ended']} requests ended, "
        f"{len(s['ttft_ms'])} TTFT and {len(s['itl_ms'])} gap samples; the "
        f"attention layers gathered {c.get('gathered_full')} positions; at "
        f"the steps' ends the live sessions held {c['held_state_slots']} "
        f"state slots ({c['held_state_bytes']} bytes) and "
        f"{c['held_kv_bytes']} bytes of K/V blocks")
    gc.collect()
