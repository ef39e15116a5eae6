"""Closed-loop serving of a fixed deck by ``glm-4.7-flash``: the
pattern decoder (``apex_tpu/models/decoder.py``) with ``latent`` layers
(DeepSeek-V2/V3's multi-head latent attention) over a latent paged
pool, one leading dense layer, then the sigmoid router over 64 experts
of which 8 are held, and a shared expert.

``serve_pattern.py``, ``serve_mellum.py`` and ``serve_granite.py`` read
their own models' keys and judge by their own references; this driver
reads ``glm4_moe_lite``'s (``q_lora_rank`` / ``kv_lora_rank`` / the
three head sizes, ``first_k_dense_replace``, ``n_routed_experts``,
``routed_scaling_factor``) and judges by
``benchmark/reference_glm.py``. What takes the model as an argument is
imported, not copied: the deck's clients, the deal, its clock and the
warm-up (``serve_closed``), the weights drawn leaf by leaf and the
program's own choice of experts (``serve_pattern``), ``set-up so far``
(``serve_mellum``), the engine with the cell's bound on a prefill
call's lanes and the dry deal that also says who ends when
(``serve_granite``: no state slots here, ``engine.state_slots`` 0). What is
repeated is the body of ``run``: ``serve_granite.run``'s counting and
its way of following two requests, whose ``Keeping`` finds a lane by
its state slot, which this model has none of (``PERF.md`` section 7
asks a ``benchmark`` PR to fold the four).

``correct`` checks two requests that ended inside the window among the
deck's first, each against the float32 reference's full pass given the
program's choice of experts: the shortest (prompt and answer), and the
shortest whose prompt is over ``check_prompt_over`` (a "repository":
sixteen chunks or more of expanded attention over the latent pool, then
the absorbed form's decode over as many rows). Which two is known
before the run, from the dry deal, so the timed path itself is listened
to: :class:`Keeping` stands between the engine and its ``DecodeStep``
and keeps, on the device and without a sync, the LOGITS row of a
followed request's lane out of every dispatch that serves it, the
32-lane decode calls with their 31 other live sequences among them. It
finds the lane by the first block of its table, which no other live
sequence holds. The tokens are judged by the reference's logits
(``check_served``'s ulps) and the logits by the reference's
(``logit_error``). In a traced run the engine is then drained and must
hold no block. The pool is let go before the reference runs, so that
the reference has the memory it needs; each stage after the window says
when it ends (``said``), so a stall shows where.

The one rule holds: the sequence of programs and shapes is a function
of the cell's files alone.
"""

from __future__ import annotations

import gc
import time
from typing import Any, Dict, List

import numpy as np

from benchmark import common, reference_glm
from benchmark.drivers.serve_closed import Clients, deal, warm
from benchmark.drivers.serve_granite import make_engine, reachable_programs
from benchmark.drivers.serve_mellum import mark
from benchmark.drivers.serve_pattern import init_params, program_choice


def decoder_config(config: dict):
    """The program's ``DecoderConfig`` for a configuration file: the
    source's widths under the source's own keys; the block as
    ``assumed`` states it; the experts held and the router's width from
    ``deployment``."""
    import jax.numpy as jnp

    from apex_tpu.models.decoder import DecoderConfig, MLAConfig

    assumed, deployment = config["assumed"], config["deployment"]
    dense = config["first_k_dense_replace"]
    n = config["num_hidden_layers"]
    layers = tuple(("latent", "dense" if i < dense else "experts")
                   for i in range(n))
    held = tuple(deployment["held_experts"])
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    if (held[1] != config["n_routed_experts"] or not config["norm_topk_prob"]
            or config["attention_bias"] or config["tie_word_embeddings"]
            or config["n_group"] != 1 or config["topk_group"] != 1
            or config["topk_method"] != "noaux_tc"
            or config["rope_scaling"] is not None
            or config["partial_rotary_factor"] != 1
            or config["num_nextn_predict_layers"] != 0
            or config["hidden_act"] != "silu"):
        raise ValueError(
            "the configuration disagrees with itself or with the block this "
            "driver builds: the experts held against n_routed_experts, a "
            "renormalised top-k by noaux_tc in one group, no attention "
            "bias, an untied head, no rope scaling, no MTP module served, "
            "SiLU")
    return DecoderConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"], head_dim=nope + rope,
        max_seq_len=config["engine"]["max_context"], layers=layers,
        ffn_hidden_size=config["intermediate_size"],
        expert_ffn_size=config["moe_intermediate_size"],
        num_experts=deployment["router_width"],
        experts_per_token=config["num_experts_per_tok"],
        held_experts=None if held == (0, deployment["router_width"])
        else held,
        shared_ffn_size=(config["n_shared_experts"]
                         * config["moe_intermediate_size"]),
        route_scale=config["routed_scaling_factor"],
        rms_eps=config["rms_norm_eps"], norms="pre", embedding_scale=False,
        output_gate=False, router="sigmoid",
        rope_theta=float(config["rope_theta"]),
        mla=MLAConfig(q_lora_rank=config["q_lora_rank"],
                      kv_lora_rank=config["kv_lora_rank"],
                      qk_nope_head_dim=nope, qk_rope_head_dim=rope,
                      v_head_dim=config["v_head_dim"]),
        dtype=jnp.dtype(assumed["dtype"]),
        param_dtype=jnp.dtype(assumed["param_dtype"]))


def arch_of(config: dict, cfg) -> "reference_glm.Arch":
    """The reference's numbers, from the configuration file's own
    keys."""
    return reference_glm.Arch(
        num_heads=config["num_attention_heads"],
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        layers=tuple(m for _, m in cfg.layers),
        top_k=config["num_experts_per_tok"],
        route_scale=config["routed_scaling_factor"],
        held=cfg.held_experts, eps=config["rms_norm_eps"],
        theta=float(config["rope_theta"]))


def checked(kept, past: int):
    """Of ``kept`` requests, the two held to the reference: the
    shortest (prompt and answer), and the shortest whose prompt is over
    ``past``. Fewer where ``kept`` holds none such."""
    kept = sorted(kept, key=lambda req: len(req.prompt) + req.max_new_tokens)
    return kept[:1] + [req for req in kept[1:] if len(req.prompt) > past][:1]


class Keeping:
    """The engine's ``DecodeStep``, listened to: every dispatch goes
    through as it came, and where it serves a followed request, that
    lane's row of the dispatch's logits is kept on the device (one
    small program after the dispatch; nothing is read back).

    ``follow`` maps a request's first prompt token (its own:
    ``serve_closed.Clients``) to ``(request id, tokens asked)``. A lane
    that starts a sequence with such a token is that request's, and the
    first block of its table is its own until it has been served its
    last token; until then the lane whose table starts with that block
    is followed, wherever among the lanes it stands. ``rows[id]`` ends
    as the rows that decided the served tokens: the last prefill-type
    dispatch's, then one a decode call."""

    def __init__(self, step_fn, follow):
        import jax

        self.step_fn, self.follow = step_fn, follow
        self.block = {}                  # first block -> followed request
        self.rows = {rid: [] for rid, _ in follow.values()}
        self.asked = dict(follow.values())
        self.row = jax.jit(lambda logits, lane: jax.lax.dynamic_index_in_dim(
            logits, lane, keepdims=False))

    def _keep(self, out, tables, decode: bool):
        for block, rid in list(self.block.items()):
            for lane in np.flatnonzero(tables[:, 0] == block):
                row = self.row(out.logits, np.int32(lane))
                self.rows[rid] = self.rows[rid] + [row] if decode else [row]
            if len(self.rows[rid]) == self.asked[rid]:
                del self.block[block]    # served: the block is another's next

    def _starts(self, tokens, starts, lengths, tables):
        for lane in np.flatnonzero((starts == 0) & (lengths > 0)):
            block = int(tables[lane, 0])
            self.block.pop(block, None)
            rid, _ = self.follow.get(int(tokens[lane, 0]), (None, 0))
            if rid is not None:
                self.block[block] = rid

    def prefill(self, params, state, tokens, lengths, tables, sampling=None):
        out = self.step_fn.prefill(params, state, tokens, lengths, tables,
                                   sampling=sampling)
        self._starts(tokens, np.zeros_like(lengths), lengths, tables)
        self._keep(out, tables, decode=False)
        return out

    def prefill_chunk(self, params, state, tokens, starts, lengths, tables,
                      sampling=None):
        out = self.step_fn.prefill_chunk(params, state, tokens, starts,
                                         lengths, tables, sampling=sampling)
        self._starts(tokens, starts, lengths, tables)
        self._keep(out, tables, decode=False)
        return out

    def decode(self, params, state, tokens, positions, tables, sampling=None):
        out = self.step_fn.decode(params, state, tokens, positions, tables,
                                  sampling=sampling)
        self._keep(out, tables, decode=True)
        return out


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
                     [:traffic["check_among_first"]], past)
    mark(run, "dry deal")
    params = jax.block_until_ready(
        init_params(cfg, run.seed, config["assumed"]))
    mark(run, "weights")
    engine, cache = make_engine(model, params, cfg, engine_cfg)
    engine.step_fn = kept_rows = Keeping(engine.step_fn, {
        int(req.prompt[0]): (req.id, req.max_new_tokens) for req in follow})
    state = warm(engine.step_fn, params, cache.init_state(), keys)
    for shape in {(1, vocab)} | {(k[1], vocab) for k in keys}:
        jax.block_until_ready(kept_rows.row(jnp.zeros(shape, jnp.float32),
                                            np.int32(0)))
    mark(run, "warm-up of the programs")
    run.notes.append(f"serve: {len(keys)} programs warmed: {keys}")
    first_wave = [at for at, req in ended if req.id[1] == 0]
    run.notes.append(
        f"serve: the deck's first {len(deck)} requests have ended by step "
        f"{max(first_wave) if len(first_wave) == len(deck) else None}; the "
        f"lead-in is {lead} steps; followed for their logits: "
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
    gathered0 = dict(engine.gathered)
    held0 = dict(engine.held)
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

    # correct, after the window (module docstring). In a traced run the
    # engine drains first: what is left must be nothing. Then the pool
    # goes
    t_after = time.perf_counter()

    def said(what):
        print(f"# after the window: {what} "
              f"({time.perf_counter() - t_after:.1f} s)", flush=True)

    ok = not bad
    if run.trace:
        engine.take_queued()
        state, n = deal(engine, state, None, lambda i: engine.idle())
        ok = ok and cache.blocks_in_use == 0
        run.notes.append(f"drained: {cache.blocks_in_use} block(s) held")
        said(f"drained in {n} steps")
    del state
    gc.collect()
    served = {req.id: res for req, res in kept}
    picks = checked([req for req, _ in kept], past)
    if len(picks) < 2 or [r.id for r in picks] != [r.id for r in follow]:
        ok = False
        run.notes.append(
            f"of {len(kept)} kept requests, none with a prompt over {past}, "
            f"or not the requests followed ({[r.id for r in picks]} for "
            f"{[r.id for r in follow]}: the window ended before theirs)")
        picks = [r for r in picks if r.id in kept_rows.rows]
    arch = arch_of(config, cfg)
    limits = dict(ulps=config["reference_tolerance_ulps"],
                  band=config["reference_choice_band"],
                  slack=config["reference_excused_margin"],
                  logit_limit=config["reference_logit_error_max"],
                  pad_to=engine_cfg["min_seq_bucket"],
                  dtype_eps=float(jnp.finfo(cfg.dtype).eps))
    excused = rows = 0
    for req in picks:
        res = served[req.id]
        toks = reference_glm.teacher_forced(req.prompt, res.tokens,
                                            limits["pad_to"])[0]
        logits = jnp.stack(kept_rows.rows.pop(req.id))
        if len(logits) != len(res.tokens):
            ok = False
            run.notes.append(
                f"request {req.id}: {len(logits)} rows of logits kept for "
                f"{len(res.tokens)} served tokens")
            continue
        said(f"request {req.id}: {len(toks)} tokens to the program's "
             "routers")
        choice = program_choice(model, cfg, params, toks)
        said(f"request {req.id}: to the reference")
        out = reference_glm.check_served(
            params, arch, req.prompt, res.tokens, program_logits=logits,
            choice=choice, **limits)
        said(f"request {req.id}: judged")
        del logits, choice
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
            f"of it (allowed {limits['ulps']}); "
            f"{out['excused'] - len(out['swapped'])} row(s) "
            f"trail by more and are excused (the worst by "
            f"{out['worst_excused_ulps']:.2f}), of the {out['may_differ']} "
            f"whose margin by the program's own scores is under "
            f"{limits['slack']}; {len(out['swapped'])} row(s) astray held "
            f"to the reference again under the nearest other choice of "
            f"experts and excused (row, layer, margin: "
            f"{[(r, j, round(m, 4)) for r, j, m in out['swapped']]}); kept "
            f"from the dispatches that served them, "
            f"the logits leave the reference's by {out['logit_error']:.3g} "
            f"of its largest at the worst row held to it (allowed "
            f"{limits['logit_limit']}; row {out['logit_worst_row']}, rms "
            f"{out['logit_rms']:.3g}); {out['held_pairs']} of "
            f"{out['pairs']} routed pairs landed on held experts")
    if rows and excused > config["reference_excused_share_max"] * rows:
        ok = False
        run.notes.append(
            f"{excused} of {rows} checked rows excused: over the share "
            f"{config['reference_excused_share_max']} the cell allows")
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
        f"{len(s['ttft_ms'])} TTFT and {len(s['itl_ms'])} gap samples; the "
        f"latent layers gathered {c.get('gathered_full')} positions, "
        f"{c.get('gathered_full_live')} of them in live blocks; at the "
        f"steps' ends the live sequences held {c['held_block_layers']} "
        f"block-layers")
