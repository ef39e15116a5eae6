"""Closed-loop serving of a fixed deck, dealt at step boundaries.

The traffic file holds the deck: for each client a list of ``[prompt
length, tokens asked]``. A client submits its next request at the step
boundary after its last one ended, and starts its list again when it
ends. Greedy, no EOS: every request ends by length. So the sequence of
programs and shapes the engine runs is a function of the deck and the
engine's settings alone. ``--seed`` makes the weights and the prompts'
token ids (fresh ones for every request, each opening with a token of
its own, so the prefix cache never hits), and nothing that decides a
shape; the wall clock decides only
where the window ends.

Before anything touches the device the deck is dealt once to the
program's own scheduler over a recording stand-in for its compiled
steps: that gives exactly the programs the cell can reach, which are
then warmed, and no others.

The benchmark's clock is read at step boundaries: a request's tokens
are *delivered* at the end of the engine step that produced them.
Tokens are counted per step from the step's report (prompt tokens as
their chunk runs, one generated token per first token and per decode).
"""

from __future__ import annotations

import time
from typing import Any, Dict, List

import numpy as np

from benchmark import common, reference


class Recorder:
    """Stands in for the engine's ``DecodeStep`` in the dry deal:
    records each dispatch's program key and touches no device."""

    def __init__(self):
        self.keys: List[tuple] = []

    def _out(self, key, state):
        from apex_tpu.serving.decode import StepOut

        self.keys.append(key)
        return StepOut(None, np.zeros(key[1], np.int32), state, None)

    def prefill(self, params, state, tokens, lengths, tables, sampling=None):
        return self._out(("prefill_step", *tokens.shape, tables.shape[1]),
                         state)

    def prefill_chunk(self, params, state, tokens, starts, lengths, tables,
                      sampling=None):
        return self._out(("prefill_chunk", *tokens.shape, tables.shape[1]),
                         state)

    def decode(self, params, state, tokens, positions, tables,
               sampling=None):
        return self._out(("decode_step", tokens.shape[0], tables.shape[1]),
                         state)


class Clients:
    """The deck's clients: who is free, and each one's next request."""

    def __init__(self, deck, seed: int, vocab: int):
        self.deck, self.seed, self.vocab = deck, seed, vocab
        self.turn = [0] * len(deck)
        self.open: Dict[Any, Any] = {}        # request id -> Request
        self.free = list(range(len(deck)))

    def submit_due(self, engine) -> List[Any]:
        from apex_tpu.serving import Request

        out = []
        for c in self.free:
            k = self.turn[c]
            self.turn[c] += 1
            plen, new = self.deck[c][k % len(self.deck[c])]
            rng = np.random.default_rng([self.seed, 2, c, k])
            prompt = common.int_tokens(rng, plen, self.vocab)
            # a first token no other request of the run has: the prefix
            # cache matches rows of a block from the first on, and one
            # chance match would change the programs that run
            prompt[0] = (k * len(self.deck) + c) % self.vocab
            req = Request(id=(c, k), max_new_tokens=new, prompt=prompt)
            self.open[req.id] = req
            engine.submit(req)
            out.append(req)
        self.free = []
        return out

    def ended(self, request_id) -> Any:
        self.free.append(request_id[0])
        self.free.sort()
        return self.open.pop(request_id)


def make_engine(model, params, cfg, engine_cfg, step_fn=None):
    from apex_tpu import serving

    cache = serving.KVCache.for_config(
        cfg, num_blocks=engine_cfg["num_blocks"],
        block_size=engine_cfg["block_size"])
    engine = serving.ContinuousBatcher(
        model, params, cache, max_batch=engine_cfg["max_batch"],
        prefill_chunk=engine_cfg["prefill_chunk"],
        min_width_bucket=engine_cfg["min_width_bucket"],
        min_seq_bucket=engine_cfg["min_seq_bucket"],
        step_fn=(step_fn if step_fn is not None
                 else serving.make_decode_step(model, cache)))
    return engine, cache


def deal(engine, state, clients, stop, observe=None):
    """Step the engine until ``stop(i)``; at each boundary every free
    client submits its next request. ``observe(i, t_submit, t_end,
    submitted, report, results)`` sees each step."""
    i = 0
    while not stop(i):
        t_submit = time.perf_counter()
        with common.span("bench.serve.submit"):
            submitted = clients.submit_due(engine) if clients else []
        with common.span("bench.serve.engine_step"):
            state, report = engine.step(state)
        t_end = time.perf_counter()
        results = engine.drain() if report["finished"] else []
        if observe is not None:
            observe(i, t_submit, t_end, submitted, report, results)
        if clients:
            for res in results:
                clients.ended(res.id)
        i += 1
    return state, i


def reachable_programs(model, cfg, engine_cfg, deck, seed, vocab, steps):
    """Every program key the deck reaches in ``steps`` engine steps, in
    the order first reached, and the per-step key sequence."""
    recorder = Recorder()
    engine, _ = make_engine(model, None, cfg, engine_cfg, recorder)
    deal(engine, None, Clients(deck, seed, vocab), lambda i: i >= steps)
    return list(dict.fromkeys(recorder.keys)), recorder.keys


def warm(step_fn, params, state, keys):
    """Run each program once on zeros (every write lands in the trash
    block), as ``ContinuousBatcher.warmup`` does for its product."""
    import jax

    z = lambda *shape: np.zeros(shape, np.int32)  # noqa: E731
    out = None
    for fn, b, *rest in keys:
        if fn == "decode_step":
            out = step_fn.decode(params, state, z(b), z(b), z(b, rest[0]))
        elif fn == "prefill_step":
            out = step_fn.prefill(params, state, z(b, rest[0]), z(b),
                                  z(b, rest[1]))
        else:
            out = step_fn.prefill_chunk(params, state, z(b, rest[0]), z(b),
                                        z(b), z(b, rest[1]))
        state = out.cache
    if out is not None:
        jax.block_until_ready(out.next_token)
    return state


def check_against_reference(params, cfg, prompt, served, ulps):
    """Teacher-forced over the served sequence: each served token must
    be the float32 reference's argmax or trail that row's largest logit
    by at most ``ulps`` bf16 ulps of it. Returns (ok, exact, worst gap
    in ulps)."""
    import jax.numpy as jnp

    served = np.asarray(served)
    toks = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    first, n = len(prompt) - 1, len(served)
    toks = np.pad(toks, (0, -len(toks) % 128))
    rows = np.asarray(reference.logits_rows(
        params, toks, np.arange(first, first + n), heads=cfg.num_heads))
    gap = rows.max(-1) - rows[np.arange(n), served]
    ulp = float(jnp.finfo(cfg.dtype).eps) * np.abs(rows).max(-1)
    worst = float((gap / ulp).max())
    return worst <= ulps, int((gap == 0).sum()), worst


def run(run) -> None:
    import jax

    from apex_tpu.models.gpt import GPTModel

    config, traffic = run.config, run.traffic
    engine_cfg = config["engine"]
    cfg = common.gpt_config(config)
    model = GPTModel(cfg)
    deck, vocab = traffic["clients"], config["vocab_size"]
    lead, chunk = traffic["lead_in_steps"], engine_cfg["prefill_chunk"]
    max_batch = engine_cfg["max_batch"]

    keys, _ = reachable_programs(model, cfg, engine_cfg, deck, run.seed,
                                 vocab, lead + traffic["horizon_steps"])
    run.mark("dry deal")
    params = jax.block_until_ready(common.init_params(cfg, run.seed))
    run.mark("weights")
    engine, cache = make_engine(model, params, cfg, engine_cfg)
    state = warm(engine.step_fn, params, cache.init_state(), keys)
    run.mark("warm-up of the programs")
    run.notes.append(f"serve: {len(keys)} programs warmed: {keys}")

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
    t0 = time.perf_counter()
    run.setup_s = t0 - run.t_start
    done = lead

    def window(stop):
        nonlocal state, done
        state, n = deal(engine, state, clients, stop,
                        lambda i, *a: observe(i + done, *a))
        done += n

    if run.trace:
        with common.traced(run.trace_dir) as took:
            window(lambda i: i >= traffic["trace_steps"])
        t0 += took["overhead_s"]
        c["traced_steps"] = traffic["trace_steps"]
    window(lambda i: time.perf_counter() - t0 >= run.seconds)
    t1 = time.perf_counter()
    run.window_compilations = run.compiles.n - compiles0
    run.window_s = t1 - t0
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
    # float32 reference (the shortest kept one and the first prefilled
    # in chunks); in a traced run also: the engine drains and the pool
    # holds no block afterwards
    kept.sort(key=lambda rr: len(rr[0].prompt) + rr[0].max_new_tokens)
    picks = kept[:1] + [rr for rr in kept[1:]
                        if len(rr[0].prompt) > chunk][:1]
    ok = bool(picks) and not bad
    for req, res in picks:
        fine, exact, worst = check_against_reference(
            params, cfg, req.prompt, res.tokens,
            config["reference_tolerance_ulps"])
        ok = ok and fine
        run.notes.append(
            f"request {req.id}: prompt {len(req.prompt)}, {len(res.tokens)} "
            f"tokens served, {exact} the reference's argmax, the worst "
            f"trails its best logit by {worst:.3f} bf16 ulp(s) of it "
            f"(allowed {config['reference_tolerance_ulps']})")
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
        + f"; {int(stalled.sum())} step(s) took over twice the median, "
        f"{float((ms - np.median(ms))[stalled].sum()):.0f} ms over it in "
        f"all: stalls, which serve_tok_s carries")
    run.notes.append(
        f"serve: {c['steps']} steps, {c['prompt_tokens']} prompt + "
        f"{c['generated']} generated tokens, {c['ended']} requests ended, "
        f"{len(s['ttft_ms'])} TTFT and {len(s['itl_ms'])} gap samples")
