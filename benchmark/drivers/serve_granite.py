"""Closed-loop serving of a fixed deck by ``granite-4.0-h-small``: the
pattern decoder (``apex_tpu/models/decoder.py``) under a third block,
nine Mamba-2 layers and one attention layer a period, whose sessions
hold a recurrent state of fixed size beside their paged K/V.

``serve_pattern.py`` and ``serve_mellum.py`` read their own models' keys
and judge by their own references; this driver reads
``granitemoehybrid``'s (``layer_types`` of ``mamba`` / ``attention``,
the ``mamba_*`` sizes, the four multipliers, ``num_local_experts``) and
judges by ``benchmark/reference_granite.py``. What takes the model as
an argument is imported, not copied: the deck's clients, the deal, its
clock and the dry deal's recorder (``serve_closed``), the program's own
choice of experts and ``set-up so far`` (``serve_mellum``: the softmax
router leaves ``probs``, as Mellum2's does).
This driver's own, because the engine of such a model differs: the
cache is made with its state slots (:func:`make_engine`), every
dispatch names its lanes' slots (:class:`Recorder`, :func:`warm`), and
the weights of the state-space layers are drawn by Mamba-2's published
initialisation (:func:`init_params`). What is repeated is the body of
``run``: ``serve_pattern.run``'s counting, which builds its own model
inside and so cannot be called (``PERF.md`` section 7 asks a
``benchmark`` PR to fold the three).

``correct`` checks two requests that ended inside the window among the
deck's first, each against the float32 reference's full pass (the
recurrence a scan over tokens) given the program's choice of experts:
a *turn* whose whole prompt was one padded whole-prompt prefill, served
in a state slot that an earlier request had left, and a *document*
over ``check_prompt_over`` (4096: five chunks or more of state handed
on, the attention layer past 4096 keys). Which two is known before the
run, from the dry deal (:func:`checked`), so the timed path itself can
be listened to: :class:`Keeping` stands between the engine and its
``DecodeStep`` from the lead-in's first step on and keeps, on the
device and without a sync, the LOGITS row of a followed request's lane
out of every dispatch that serves it, the 64-lane decode calls with
their 63 other live sessions among them. It finds the lane by the state
slot the engine itself named for it. Twice over, then: the tokens the
timed path served are judged by the reference's logits
(``check_served``'s ulps), and the logits it computed for them are
compared with the reference's (``logit_error``: an error in the state
is carried for thousands of steps, and a token's argmax need not show
it; in this cell the tokens show nothing, ``PERF.md`` section 6). After
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

from benchmark import common, reference_granite
from benchmark.drivers import serve_closed
from benchmark.drivers.serve_closed import Clients, deal
from benchmark.drivers.serve_mellum import mark, program_choice

KINDS = {"mamba": "mamba", "attention": "full"}


def decoder_config(config: dict):
    """The program's ``DecoderConfig`` for a configuration file: the
    source's widths under the source's own keys; the block as
    ``assumed`` states it; the experts held and the router's width from
    ``deployment``."""
    import jax.numpy as jnp

    from apex_tpu.models.decoder import DecoderConfig, Mamba2Config

    assumed, deployment = config["assumed"], config["deployment"]
    layers = tuple((KINDS[kind], "experts") for kind in config["layer_types"])
    held = tuple(deployment["held_experts"])
    heads, hidden = config["mamba_n_heads"], config["hidden_size"]
    if (len(layers) != config["num_hidden_layers"]
            or held[1] != config["num_local_experts"]
            or heads * config["mamba_d_head"]
            != config["mamba_expand"] * hidden
            or config["mamba_n_groups"] != 1 or config["mamba_proj_bias"]
            or not config["mamba_conv_bias"] or config["attention_bias"]
            or config["position_embedding_type"] != "nope"
            or not config["tie_word_embeddings"]
            or hidden % config["num_attention_heads"]):
        raise ValueError(
            "the configuration disagrees with itself or with the block this "
            "driver builds: layer_types against num_hidden_layers, the "
            "experts held against num_local_experts, the Mamba-2 inner "
            "width against mamba_expand, one group, a convolution bias and "
            "no projection bias, no attention bias, no positional "
            "embedding, a tied head")
    return DecoderConfig(
        vocab_size=config["vocab_size"], hidden_size=hidden,
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=hidden // config["num_attention_heads"],
        max_seq_len=config["engine"]["max_context"], layers=layers,
        ffn_hidden_size=0, expert_ffn_size=config["intermediate_size"],
        num_experts=deployment["router_width"],
        experts_per_token=config["num_experts_per_tok"],
        held_experts=None if held == (0, deployment["router_width"])
        else held,
        shared_ffn_size=config["shared_intermediate_size"],
        rms_eps=config["rms_norm_eps"], norms="pre", output_gate=False,
        router="softmax", qk_norm=False,
        attention_scale=config["attention_multiplier"],
        embedding_scale=False,
        embedding_multiplier=float(config["embedding_multiplier"]),
        residual_multiplier=config["residual_multiplier"],
        logits_divisor=float(config["logits_scaling"]), tied_head=True,
        mamba=Mamba2Config(
            num_heads=heads, head_dim=config["mamba_d_head"],
            state_size=config["mamba_d_state"],
            conv_width=config["mamba_d_conv"],
            chunk=config["mamba_chunk_size"]),
        dtype=jnp.dtype(assumed["dtype"]),
        param_dtype=jnp.dtype(assumed["param_dtype"]))


def arch_of(config: dict, cfg) -> "reference_granite.Arch":
    """The reference's numbers, from the configuration file's own
    keys."""
    return reference_granite.Arch(
        num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
        head_dim=cfg.head_dim, layers=tuple(config["layer_types"]),
        mamba_heads=config["mamba_n_heads"],
        mamba_head_dim=config["mamba_d_head"],
        mamba_state=config["mamba_d_state"],
        top_k=config["num_experts_per_tok"],
        attention_multiplier=config["attention_multiplier"],
        embedding_multiplier=float(config["embedding_multiplier"]),
        residual_multiplier=config["residual_multiplier"],
        logits_scaling=float(config["logits_scaling"]),
        held=cfg.held_experts, eps=config["rms_norm_eps"])


def init_params(cfg, seed: int, assumed: dict):
    """The model's parameters, made on the device from the seed, leaf
    by leaf from the tree's shapes (no forward pass runs): matrices
    N(0, init_std^2), norm gains and ``D`` 1, and the state-space
    layers' own by Mamba-2's published initialisation (``assumed``'s
    ``init_why``): ``A_log``, ``dt_bias``, the convolution's kernel and
    bias U(-1/2, 1/2)."""
    import functools

    import jax
    import jax.numpy as jnp

    from apex_tpu.models import ssm
    from apex_tpu.models.decoder import PatternDecoder

    shapes = jax.eval_shape(
        lambda key: PatternDecoder(cfg).init(
            key, jnp.zeros((1, 8), jnp.int32)), jax.random.PRNGKey(0))
    leaves, tree = jax.tree_util.tree_flatten_with_path(shapes)

    @functools.partial(jax.jit, static_argnames=("shape", "dtype", "how"))
    def draw(key, *, shape, dtype, how):
        if how == "A_log":
            return ssm.a_log_init(key, shape, dtype)
        if how == "dt_bias":
            return ssm.dt_bias_init(key, shape, dtype)
        if how == "conv":
            return jax.random.uniform(key, shape, jnp.float32, -0.5,
                                      0.5).astype(dtype)
        return (assumed["init_std"]
                * jax.random.normal(key, shape, jnp.float32)).astype(dtype)

    key, out = common.seed_key(seed), []
    for i, (path, leaf) in enumerate(leaves):
        name = str(getattr(path[-1], "key", path[-1]))
        if name in ("scale", "norm", "D"):
            out.append(jnp.ones(leaf.shape, leaf.dtype))
            continue
        how = {"A_log": "A_log", "dt_bias": "dt_bias", "conv_w": "conv",
               "conv_b": "conv"}.get(name, "normal")
        out.append(draw(jax.random.fold_in(key, i), shape=leaf.shape,
                        dtype=leaf.dtype, how=how))
    return tree.unflatten(out)


class Recorder(serve_closed.Recorder):
    """``serve_closed.Recorder`` for a model whose dispatches name their
    lanes' state slots; the keys are ``serve_closed``'s."""

    def prefill(self, params, state, tokens, lengths, tables, sampling=None,
                slots=None):
        return super().prefill(params, state, tokens, lengths, tables)

    def prefill_chunk(self, params, state, tokens, starts, lengths, tables,
                      sampling=None, slots=None):
        return super().prefill_chunk(params, state, tokens, starts, lengths,
                                     tables)

    def decode(self, params, state, tokens, positions, tables,
               sampling=None, slots=None):
        return super().decode(params, state, tokens, positions, tables)


def make_engine(model, params, cfg, engine_cfg, step_fn=None):
    """``serve_closed.make_engine`` with the cache's state slots and the
    cell's bound on the lanes of a prefill call."""
    from apex_tpu import serving

    cache = serving.KVCache.for_config(
        cfg, num_blocks=engine_cfg["num_blocks"],
        block_size=engine_cfg["block_size"],
        state_slots=engine_cfg["state_slots"])
    engine = serving.ContinuousBatcher(
        model, params, cache, max_batch=engine_cfg["max_batch"],
        max_prefill_batch=engine_cfg["max_prefill_batch"],
        prefill_chunk=engine_cfg["prefill_chunk"],
        min_width_bucket=engine_cfg["min_width_bucket"],
        min_seq_bucket=engine_cfg["min_seq_bucket"],
        step_fn=(step_fn if step_fn is not None
                 else serving.make_decode_step(model, cache)))
    return engine, cache


def reachable_programs(model, cfg, engine_cfg, deck, seed, vocab, steps):
    """Every program key the deck reaches in ``steps`` engine steps, in
    the order first reached, the per-step key sequence, and the
    requests in the order they ended, each ``(the steps dealt by then,
    the request)``."""
    recorder = Recorder()
    engine, _ = make_engine(model, None, cfg, engine_cfg, recorder)
    clients = Clients(deck, seed, vocab)
    ended = []

    def observe(i, t_submit, t_end, submitted, report, results):
        ended.extend((i + 1, clients.open[res.id]) for res in results)

    deal(engine, None, clients, lambda i: i >= steps, observe)
    return list(dict.fromkeys(recorder.keys)), recorder.keys, ended


def checked(kept, chunk: int, past: int):
    """Of ``kept`` requests, the two held to the reference: the
    shortest (prompt and answer) *turn* whose prompt is one
    whole-prompt prefill and whose client has had a request before it
    (every slot is taken by the deck's first requests, so its slot is
    one that an earlier request left), and the shortest *document*
    over ``past``. Fewer where ``kept`` holds none such."""
    kept = sorted(kept, key=lambda req: len(req.prompt) + req.max_new_tokens)
    return ([req for req in kept
             if len(req.prompt) <= chunk and req.id[1] >= 1][:1]
            + [req for req in kept if len(req.prompt) > past][:1])


class Keeping:
    """The engine's ``DecodeStep``, listened to: every dispatch goes
    through as it came, and where it serves a followed request, that
    lane's row of the dispatch's logits is kept on the device (one
    small program after the dispatch, :func:`jax.jit` of a dynamic
    index; nothing is read back, so the engine's one sync a dispatch
    stays the only one).

    ``follow`` maps a request's first prompt token (its own:
    ``serve_closed.Clients``) to ``(request id, tokens asked)``. A lane
    that starts a sequence with such a token is that request's, and the
    state slot the engine names for it is its until it has been served
    its last token; until then the lane that names that slot is
    followed, wherever among the lanes it stands. ``rows[id]`` ends as
    the rows that decided the served tokens: the last prefill-type
    dispatch's, then one a decode call."""

    def __init__(self, step_fn, follow):
        import jax

        self.step_fn, self.follow = step_fn, follow
        self.slot = {}                   # state slot -> followed request id
        self.rows = {rid: [] for rid, _ in follow.values()}
        self.asked = dict(follow.values())
        self.row = jax.jit(lambda logits, lane: jax.lax.dynamic_index_in_dim(
            logits, lane, keepdims=False))

    def _keep(self, out, slots, decode: bool):
        for slot, rid in list(self.slot.items()):
            for lane in np.flatnonzero(slots == slot):
                row = self.row(out.logits, np.int32(lane))
                self.rows[rid] = self.rows[rid] + [row] if decode else [row]
            if len(self.rows[rid]) == self.asked[rid]:
                del self.slot[slot]      # served: the slot is another's next

    def _starts(self, tokens, starts, lengths, slots):
        for lane in np.flatnonzero((starts == 0) & (lengths > 0)):
            self.slot.pop(int(slots[lane]), None)
            rid, _ = self.follow.get(int(tokens[lane, 0]), (None, 0))
            if rid is not None:
                self.slot[int(slots[lane])] = rid

    def prefill(self, params, state, tokens, lengths, tables, sampling=None,
                slots=None):
        out = self.step_fn.prefill(params, state, tokens, lengths, tables,
                                   sampling=sampling, slots=slots)
        self._starts(tokens, np.zeros_like(lengths), lengths, slots)
        self._keep(out, slots, decode=False)
        return out

    def prefill_chunk(self, params, state, tokens, starts, lengths, tables,
                      sampling=None, slots=None):
        out = self.step_fn.prefill_chunk(params, state, tokens, starts,
                                         lengths, tables, sampling=sampling,
                                         slots=slots)
        self._starts(tokens, starts, lengths, slots)
        self._keep(out, slots, decode=False)
        return out

    def decode(self, params, state, tokens, positions, tables, sampling=None,
               slots=None):
        out = self.step_fn.decode(params, state, tokens, positions, tables,
                                  sampling=sampling, slots=slots)
        self._keep(out, slots, decode=True)
        return out


def warm(step_fn, params, state, keys, trash_slot: int):
    """Run each program once on zeros: every K/V write lands in the
    trash block and every state in the trash slot; and
    :class:`Keeping`'s row program once for each shape of logits."""
    import jax

    z = lambda *shape: np.zeros(shape, np.int32)  # noqa: E731
    out = None
    for fn, b, *rest in keys:
        slots = np.full((b,), trash_slot, np.int32)
        if fn == "decode_step":
            out = step_fn.decode(params, state, z(b), z(b), z(b, rest[0]),
                                 slots=slots)
        elif fn == "prefill_step":
            out = step_fn.prefill(params, state, z(b, rest[0]), z(b),
                                  z(b, rest[1]), slots=slots)
        else:
            out = step_fn.prefill_chunk(params, state, z(b, rest[0]), z(b),
                                        z(b), z(b, rest[1]), slots=slots)
        state = out.cache
        row = step_fn.row(out.logits, np.int32(0))
    if out is not None:
        jax.block_until_ready((out.next_token, row))
    return state


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
    first_wave = [at for at, req in ended if req.id[1] == 0]
    run.notes.append(
        f"serve: the deck's first {len(deck)} requests have ended by step "
        f"{max(first_wave) if len(first_wave) == len(deck) else None}; the "
        f"lead-in is {lead} steps; followed for their logits: "
        + ", ".join(f"{req.id} (ends by step "
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
    # (began, seconds) of the collector's runs over its oldest generation
    # inside the window: a step that stalls for a second is the
    # collector's or the host's (PERF.md section 7), and this tells them
    collections: List[tuple] = []

    def collecting(phase, info):
        if info["generation"] == 2:
            if phase == "start":
                collections.append((time.perf_counter(), None))
            else:
                began = collections.pop()[0]
                collections.append((began, time.perf_counter() - began))

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
    gc.callbacks.append(collecting)
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
    gc.callbacks.remove(collecting)
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
    served = {req.id: res for req, res in kept}
    picks = checked([req for req, _ in kept], chunk, past)
    if len(picks) < 2 or [r.id for r in picks] != [r.id for r in follow]:
        ok = False
        run.notes.append(
            f"of {len(kept)} kept requests, no turn of {chunk} or fewer in a "
            f"reused slot, or no document over {past}, or not the requests "
            f"followed ({[r.id for r in picks]} for "
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
        toks, _ = reference_granite.teacher_forced(
            req.prompt, res.tokens, limits["pad_to"])
        logits = jnp.stack(kept_rows.rows.pop(req.id))
        if len(logits) != len(res.tokens):
            ok = False
            run.notes.append(
                f"request {req.id}: {len(logits)} rows of logits kept for "
                f"{len(res.tokens)} served tokens")
            continue
        out = reference_granite.check_served(
            params, arch, req.prompt, res.tokens, program_logits=logits,
            choice=program_choice(model, cfg, params, toks), **limits)
        del logits
        ok = ok and out["ok"]
        excused += out["excused"]
        rows += out["rows"]
        run.notes.append(
            f"request {req.id}: prompt {len(req.prompt)}, {out['rows']} "
            f"tokens served, {out['exact']} of them the reference's argmax; "
            f"the reference followed the program's choice of experts in "
            f"{out['followed']} row(s) of the sequence (the worst misfit "
            f"{out['worst_misfit']:.5f} of log probability, band "
            f"{limits['band']}) and refused it in {out['refused']}; the "
            f"worst row held to the reference trails its best logit by "
            f"{out['worst_ulps']:.3f} bf16 ulp(s) of it (allowed "
            f"{limits['ulps']}; row {out['worst_row']}, the program's margin "
            f"{out['program_margin'][out['worst_row']]:.5f}); "
            f"{out['excused']} row(s) trail by more and are excused (the "
            f"worst by {out['worst_excused_ulps']:.2f}), of the "
            f"{out['may_differ']} whose margin by the program's own scores "
            f"is under {limits['slack']}; kept from the dispatches that "
            f"served them, the logits leave the reference's by "
            f"{out['logit_error']:.3g} of its largest at the worst row "
            f"held to it (allowed "
            f"{limits['logit_limit']}; row {out['logit_worst_row']}, rms "
            f"{out['logit_rms']:.3g}); {out['held_pairs']} of "
            f"{out['pairs']} routed pairs landed on held experts")
    if rows and excused > config["reference_excused_share_max"] * rows:
        ok = False
        run.notes.append(
            f"{excused} of {rows} checked rows excused: over the share "
            f"{config['reference_excused_share_max']} the cell allows")
    ok = ok and cache.blocks_in_use == 0 and cache.slots_in_use == 0
    run.correct = ok
    run.notes.extend(bad[:5])
    ms = np.asarray(s["step_ms"])
    stalled = ms > 2 * np.median(ms)
    run.notes.append(
        f"serve: the median step took {np.median(ms):.1f} ms, the slowest "
        + ", ".join(f"{ms[i]:.0f} ms (step {i}, {step_at[i] - t0:.2f} s in)"
                    for i in np.argsort(ms)[::-1][:3])
        + f"; {int(stalled.sum())} step(s) took over twice the median "
        f"(chunk steps among them); the collector ran its oldest generation "
        f"{len(collections)} time(s) inside the window"
        + "".join(f", {took * 1e3:.0f} ms at {at - t0:.2f} s"
                  for at, took in collections[:5]))
    run.notes.append(
        f"serve: {c['steps']} steps, {c['prompt_tokens']} prompt + "
        f"{c['generated']} generated tokens, {c['ended']} requests ended, "
        f"{len(s['ttft_ms'])} TTFT and {len(s['itl_ms'])} gap samples; the "
        f"attention layer gathered {c.get('gathered_full')} positions; at "
        f"the steps' ends the live sessions held {c['held_state_slots']} "
        f"state slots ({c['held_state_bytes']} bytes) and "
        f"{c['held_kv_bytes']} bytes of K/V blocks")
