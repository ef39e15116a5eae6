"""Serving hot-path performance plane (docs/serving.md): chunked
prefill, prefix-sharing KV reuse, and fused in-program sampling.

Anchors:

- chunked-prefill parity: a prompt prefilled in N chunks produces the
  SAME token stream as the monolithic prefill, with last-token logits
  matching to fp32 tightness (~1e-7 — the attention reduction order
  differs across the gathered-context layout, so the logits contract
  is allclose; the greedy token stream is pinned exactly);
- COW fork isolation: a forked writer never mutates the shared source
  block (pinned bitwise), and a dirty shared block reaching refcount
  zero is scrubbed before reuse;
- prefix-cache hits produce the same tokens as a cold cache, pay
  fewer prefill tokens, and release only private blocks on a
  mid-``PREFILLING`` deadline reap;
- sampled streams are deterministic per (seed, token index), replay
  across snapshot -> resume token for token, and the temperature-0
  path is bitwise the greedy argmax;
- compile plane: chunking mints one program per (batch bucket, chunk
  bucket, width) at warmup and ZERO hot-loop recompiles;
- the ``prefill_chunk_exception`` clause quarantines the chunk batch
  and the engine keeps serving; ``io:prefill_chunk`` is absorbed;
- the host-device boundary of a dispatch is one array each way: the
  packed argument carries every field bit for bit, the programs give
  what they gave from separate arrays, and the engine reads
  ``StepOut.packed`` and nothing else.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from apex_tpu import serving, telemetry  # noqa: E402
from apex_tpu.models.gpt import GPTConfig, GPTModel  # noqa: E402
from apex_tpu.resilience import faults  # noqa: E402
from apex_tpu.resilience.guard import PreemptionHandler  # noqa: E402
from apex_tpu.serving import decode as sdecode  # noqa: E402
from apex_tpu.serving import resilience as sresil  # noqa: E402
from apex_tpu.serving.kv_cache import KVCache  # noqa: E402

VOCAB, SEQ, HID, LAYERS, HEADS, KV = 64, 64, 32, 2, 4, 2
BLOCKS, BS = 32, 4


def tiny_config(**kw):
    base = dict(vocab_size=VOCAB, max_seq_len=SEQ, hidden_size=HID,
                num_layers=LAYERS, num_heads=HEADS, num_kv_heads=KV,
                dtype=jnp.float32, param_dtype=jnp.float32)
    base.update(kw)
    return GPTConfig(**base)


def fresh_cache(num_blocks=BLOCKS, block_size=BS):
    return KVCache(LAYERS, KV, HID // HEADS, num_blocks=num_blocks,
                   block_size=block_size, dtype=jnp.float32)


@pytest.fixture(scope="module")
def model_and_params():
    model = GPTModel(tiny_config())
    rng = np.random.RandomState(0)
    toks = jnp.asarray(rng.randint(0, VOCAB, (1, 8)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), toks)
    return model, params


@pytest.fixture(scope="module")
def step_fn(model_and_params):
    model, _ = model_and_params
    return serving.make_decode_step(model, fresh_cache())


def make_batcher(model, params, step_fn, cache, **kw):
    reg = telemetry.MetricsRegistry()
    sink = telemetry.InMemorySink()
    reg.add_sink(sink)
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_prefill_batch", 4)
    kw.setdefault("min_seq_bucket", 8)
    b = serving.ContinuousBatcher(model, params, cache, step_fn=step_fn,
                                  registry=reg, **kw)
    return b, reg, sink


def run_to_completion(eng, cache, reqs):
    state = cache.init_state()
    state, results = serving.serve_loop(eng, state, reqs)
    return {r.id: r for r in results}


# ---------------------------------------------------------------------------
# chunked prefill
# ---------------------------------------------------------------------------


class TestChunkedPrefill:
    def test_chunk_program_parity_vs_monolithic(self, model_and_params,
                                                step_fn):
        """N back-to-back chunk dispatches == one monolithic prefill:
        identical greedy token, last-token logits within fp32
        tightness, and the written K/V gathers back equal."""
        model, params = model_and_params
        rng = np.random.RandomState(3)
        toks = rng.randint(0, VOCAB, (1, 20)).astype(np.int32)
        cache = fresh_cache()
        cache.allocate("mono", 24)
        tm = cache.table_array(["mono"], 6)
        out = step_fn.prefill(params, cache.init_state(), toks,
                              np.asarray([20], np.int32), tm)
        ref_logits = np.asarray(out.logits)
        ref_tok = int(out.next_token[0])

        cache2 = fresh_cache()
        cache2.allocate("chk", 24)
        tc = cache2.table_array(["chk"], 6)
        state = cache2.init_state()
        for c, cs in ((0, 8), (8, 8), (16, 4)):
            out2 = step_fn.prefill_chunk(
                params, state, toks[:, c:c + 8][:, :8],
                np.asarray([c], np.int32), np.asarray([cs], np.int32),
                tc)
            state = out2.cache
        np.testing.assert_allclose(np.asarray(out2.logits), ref_logits,
                                   atol=1e-5, rtol=1e-5)
        assert int(out2.next_token[0]) == ref_tok

    def test_chunked_engine_streams_match_monolithic(
            self, model_and_params, step_fn):
        model, params = model_and_params

        def mk():
            r = np.random.RandomState(5)
            out = []
            for i in range(8):
                plen = 22 if i % 3 == 0 else int(r.randint(3, 9))
                out.append(serving.Request(
                    id=i, prompt=r.randint(0, VOCAB, (plen,)),
                    max_new_tokens=int(r.randint(3, 6))))
            return out

        cache = fresh_cache()
        eng, _, _ = make_batcher(model, params, step_fn, cache)
        mono = run_to_completion(eng, cache, mk())
        cache2 = fresh_cache()
        eng2, reg, _ = make_batcher(model, params, step_fn, cache2,
                                    prefill_chunk=8)
        chk = run_to_completion(eng2, cache2, mk())
        assert {i: r.tokens for i, r in mono.items()} == \
               {i: r.tokens for i, r in chk.items()}
        # the long prompts really went through the chunk path
        assert reg.counter("serving_prefill_chunks").value() >= 3
        assert cache2.blocks_in_use == 0

    def test_long_prompt_does_not_stall_decode(self, model_and_params,
                                               step_fn):
        """The co-scheduling contract: while a long prompt chunks, the
        in-flight short request keeps decoding EVERY step."""
        model, params = model_and_params
        cache = fresh_cache()
        eng, _, _ = make_batcher(model, params, step_fn, cache,
                                 prefill_chunk=4, max_prefill_batch=1)
        state = cache.init_state()
        eng.submit(serving.Request(id="short", prompt=[3] * 4,
                                   max_new_tokens=12))
        state, rep = eng.step(state)
        assert rep["decoded"] == ["short"]
        eng.submit(serving.Request(id="long", prompt=[5] * 20,
                                   max_new_tokens=4))
        for _ in range(4):      # 20 tokens / chunk 4 = 5 chunk steps
            state, rep = eng.step(state)
            assert "long" in rep.get("prefilled", [])
            assert "short" in rep["decoded"]       # never stalled
            assert "long" not in rep["decoded"]    # still PREFILLING
        state, rep = eng.step(state)               # final chunk
        assert "long" in rep["prefilled"]
        while not eng.idle():
            state, rep = eng.step(state)
        res = {r.id: r for r in eng.drain()}
        assert res["short"].finish_reason == "length"
        assert res["long"].finish_reason == "length"
        assert len(res["long"].tokens) == 4

    def test_staged_reservation_admits_before_full_span_fits(
            self, model_and_params, step_fn):
        """A long prompt admits with only its first chunk's blocks —
        the pre-chunking engine would defer until the FULL span fit."""
        model, params = model_and_params
        # full span = 20 prompt + 4 new = 24 tokens = 6 blocks; pool
        # of 4 can never hold it all at once while chunking staged
        # reservation admits and progresses as blocks free
        cache = fresh_cache(num_blocks=6)
        eng, _, _ = make_batcher(model, params, step_fn, cache,
                                 prefill_chunk=4)
        state = cache.init_state()
        eng.submit(serving.Request(id=0, prompt=[2] * 20,
                                   max_new_tokens=4))
        state, rep = eng.step(state)
        assert rep["admitted"] == [0]
        while not eng.idle():
            state, _ = eng.step(state)
        out = eng.drain()[0]
        assert out.finish_reason == "length" and len(out.tokens) == 4
        assert cache.blocks_in_use == 0

    def test_prefill_stall_requeues_instead_of_deadlocking(
            self, model_and_params, step_fn):
        """Two long prompts whose staged reservations collide on a
        pool that fits only one full span: the engine must requeue one
        (breaking the deadlock) and still finish both."""
        model, params = model_and_params
        # each request spans 12 + 12 = 24 tokens = 6 blocks == pool
        cache = fresh_cache(num_blocks=6)
        eng, reg, _ = make_batcher(model, params, step_fn, cache,
                                   prefill_chunk=4)
        reqs = [serving.Request(id=i, prompt=[2 + i] * 12,
                                max_new_tokens=12) for i in range(2)]
        res = run_to_completion(eng, cache, reqs)
        assert all(r.finish_reason == "length" for r in res.values())
        assert all(len(r.tokens) == 12 for r in res.values())
        assert reg.counter("serving_prefill_stalled").value() >= 1
        assert reg.counter("serving_prefill_requeued").value() >= 1
        assert cache.blocks_in_use == 0


# ---------------------------------------------------------------------------
# prefix sharing + COW fork
# ---------------------------------------------------------------------------


class TestPrefixSharing:
    def test_hit_skips_prefill_and_matches_cold_tokens(
            self, model_and_params, step_fn):
        model, params = model_and_params
        sysp = list(np.random.RandomState(9).randint(0, VOCAB, (12,)))

        def req(i, tail):
            return serving.Request(id=i, prompt=sysp + tail,
                                   max_new_tokens=4)

        cache = fresh_cache()
        eng, reg, _ = make_batcher(model, params, step_fn, cache)
        state = cache.init_state()
        state, res_a = serving.serve_loop(eng, state, [req("a", [3, 4])])
        saved0 = cache.prefix_stats()["tokens_saved"]
        state, res_b = serving.serve_loop(eng, state, [req("b", [3, 4])])
        stats = cache.prefix_stats()
        assert stats["hits"] == 1
        assert stats["tokens_saved"] - saved0 >= 12
        assert reg.counter("serving_prefix_cache_hits").value(
            outcome="hit") == 1
        # cold-cache reference: identical tokens
        cache2 = fresh_cache()
        eng2, _, _ = make_batcher(model, params, step_fn, cache2)
        cold = run_to_completion(eng2, cache2, [req("b", [3, 4])])
        assert res_b[0].tokens == cold["b"].tokens == res_a[0].tokens

    def test_concurrent_sharing_block_refcounts(self, model_and_params,
                                                step_fn):
        model, params = model_and_params
        sysp = list(np.random.RandomState(11).randint(0, VOCAB, (8,)))
        cache = fresh_cache()
        eng, _, _ = make_batcher(model, params, step_fn, cache)
        state = cache.init_state()
        eng.submit(serving.Request(id="a", prompt=sysp + [1],
                                   max_new_tokens=8))
        state, _ = eng.step(state)       # a prefilled + published
        eng.submit(serving.Request(id="b", prompt=sysp + [2],
                                   max_new_tokens=8))
        state, rep = eng.step(state)
        assert rep["admitted"] == ["b"]
        # both alive: the 2 full prefix blocks are shared (ref == 2)
        assert cache.prefix_stats()["shared_blocks"] == 2
        ta = cache.table(eng.running[0].seq_id)
        tb = cache.table(eng.running[1].seq_id)
        assert ta[:2] == tb[:2]          # same physical blocks
        assert ta[2:] != tb[2:]          # private tails differ
        while not eng.idle():
            state, _ = eng.step(state)
        assert cache.blocks_in_use == 0
        assert cache.prefix_stats()["cached_blocks"] >= 2

    def test_cow_fork_writer_never_mutates_shared_block(
            self, model_and_params, step_fn):
        """B forks A's divergence block: the copied rows land in B's
        private block, and A's published source block stays bitwise
        untouched through B's whole lifetime."""
        model, params = model_and_params
        rng = np.random.RandomState(13)
        base = list(rng.randint(0, VOCAB, (8,)))
        cache = fresh_cache()
        eng, _, _ = make_batcher(model, params, step_fn, cache)
        state = cache.init_state()
        # A: 8-token prompt = 2 full published blocks
        eng.submit(serving.Request(id="a", prompt=base,
                                   max_new_tokens=2))
        while not eng.idle():
            state, _ = eng.step(state)
        eng.drain()
        # B matches block 0 fully, diverges inside block 1 (2 of 4
        # rows common) -> COW fork
        bp = base[:6] + [int(base[6]) ^ 1, 5, 7]
        eng.submit(serving.Request(id="b", prompt=bp, max_new_tokens=3))
        state, rep = eng.step(state)
        assert rep["admitted"] == ["b"]
        fb = next(f for f in eng.running + eng.prefilling)
        assert fb.prefilled >= 6 or fb.prefilled == 0  # fork matched 6
        stats = cache.prefix_stats()
        assert stats["hits"] == 1 and stats["tokens_saved"] >= 6
        # A's source block (the cold cache still holds it) is bitwise
        # untouched: re-admit A's exact prompt and check its stream
        while not eng.idle():
            state, _ = eng.step(state)
        res_b = eng.drain()[0]
        eng.submit(serving.Request(id="a2", prompt=base,
                                   max_new_tokens=2))
        while not eng.idle():
            state, _ = eng.step(state)
        res_a2 = eng.drain()[0]
        # reference: both prompts on a cold cache
        cache2 = fresh_cache()
        eng2, _, _ = make_batcher(model, params, step_fn, cache2)
        cold = run_to_completion(eng2, cache2, [
            serving.Request(id="a2", prompt=base, max_new_tokens=2),
            serving.Request(id="b", prompt=bp, max_new_tokens=3)])
        assert res_b.tokens == cold["b"].tokens
        assert res_a2.tokens == cold["a2"].tokens

    def test_dirty_shared_block_scrubbed_at_refcount_zero(
            self, model_and_params, step_fn, tmp_path, monkeypatch):
        """The PR-9 NaN-scrub rule on refcounted blocks: quarantining
        one tenant of a shared block marks it dirty (unpublished at
        once); when the LAST tenant frees it, it parks on the
        pending-scrub list and is zeroed before reuse."""
        from apex_tpu import records

        monkeypatch.setattr(records, "RECORDS_DIR", str(tmp_path))
        model, params = model_and_params
        sysp = list(np.random.RandomState(17).randint(0, VOCAB, (8,)))
        cache = fresh_cache()
        eng, reg, _ = make_batcher(model, params, step_fn, cache)
        state = cache.init_state()
        eng.submit(serving.Request(id="a", prompt=sysp + [1],
                                   max_new_tokens=10))
        state, _ = eng.step(state)
        eng.submit(serving.Request(id="b", prompt=sysp + [2],
                                   max_new_tokens=10))
        state, _ = eng.step(state)
        shared = cache.table(eng.running[0].seq_id)[:2]
        assert cache.block_ref(shared[0]) == 2
        # quarantine b (lane 1) via the nonfinite drill
        with faults.inject(decode_nonfinite_steps=frozenset({2}),
                           decode_nonfinite_lane=1):
            state, rep = eng.step(state)
        assert rep["quarantined"] == ["b"]
        # the shared blocks are dirty: unpublished, still ref'd by a
        stats = cache.prefix_stats()
        assert stats["published_blocks"] == 0
        assert cache.block_ref(shared[0]) == 1
        # a finishes -> refcount zero -> pending scrub, NOT free
        while not eng.idle():
            state, _ = eng.step(state)
        assert cache.prefix_stats()["pending_scrub"] == 2
        assert cache.blocks_in_use == 0
        # the next step scrubs and returns them to the free list
        state, _ = eng.step(state)
        assert cache.prefix_stats()["pending_scrub"] == 0
        assert cache.free_blocks == BLOCKS
        assert reg.counter("serving_blocks_scrubbed").value() == 2

    def test_deadline_reap_mid_prefilling_releases_private_only(
            self, model_and_params, step_fn):
        """The satellite fix: a request dying mid-PREFILLING frees its
        private blocks and only DECREMENTS the shared prefix refs."""
        model, params = model_and_params
        sysp = list(np.random.RandomState(19).randint(0, VOCAB, (8,)))
        cache = fresh_cache()
        t = [0.0]
        eng, reg, _ = make_batcher(model, params, step_fn, cache,
                                   clock=lambda: t[0], prefill_chunk=4)
        state = cache.init_state()
        eng.submit(serving.Request(id="a", prompt=sysp + [1],
                                   max_new_tokens=12))
        while not eng.running:           # a prefills (chunked) and
            state, _ = eng.step(state)   # publishes its prefix blocks
        # long prompt sharing the prefix: stays PREFILLING for a while
        eng.submit(serving.Request(
            id="victim", prompt=sysp + [2] * 14, max_new_tokens=4,
            deadline_ms=100.0))
        state, rep = eng.step(state)
        assert rep["admitted"] == ["victim"]
        victim = next(f for f in eng.prefilling
                      if f.req.id == "victim")
        shared = cache.table(victim.seq_id)[:2]
        assert cache.block_ref(shared[0]) == 2
        t[0] = 0.5                       # TTL long gone
        state, rep = eng.step(state)
        assert rep["expired"] == ["victim"]
        res = [r for r in eng.drain() if r.id == "victim"]
        assert res[0].finish_reason == "deadline_exceeded"
        assert reg.counter("serving_deadline_exceeded").value(
            where="prefilling") == 1
        # shared blocks survive with a's reference; privates are free
        assert cache.block_ref(shared[0]) == 1
        assert cache.prefix_stats()["published_blocks"] == 2
        while not eng.idle():
            state, _ = eng.step(state)
        assert cache.blocks_in_use == 0


# ---------------------------------------------------------------------------
# fused sampling
# ---------------------------------------------------------------------------


class TestFusedSampling:
    def test_temperature_zero_is_bitwise_greedy(self, model_and_params,
                                                step_fn):
        model, params = model_and_params
        rng = np.random.RandomState(23)
        reqs = [serving.Request(
            id=i, prompt=rng.randint(0, VOCAB, (int(rng.randint(2, 9)),)),
            max_new_tokens=4) for i in range(4)]
        cache = fresh_cache()
        eng, _, _ = make_batcher(model, params, step_fn, cache)
        res = run_to_completion(eng, cache, reqs)
        for i, r in res.items():
            assert r.finish_reason == "length"
        # explicit greedy-sampling request (temp 0) matches default
        cache2 = fresh_cache()
        eng2, _, _ = make_batcher(model, params, step_fn, cache2)
        rng = np.random.RandomState(23)
        reqs2 = [serving.Request(
            id=i, prompt=rng.randint(0, VOCAB, (int(rng.randint(2, 9)),)),
            max_new_tokens=4, temperature=0.0, seed=99) for i in range(4)]
        res2 = run_to_completion(eng2, cache2, reqs2)
        assert {i: r.tokens for i, r in res.items()} == \
               {i: r.tokens for i, r in res2.items()}

    def test_sampled_stream_deterministic_and_seed_sensitive(
            self, model_and_params, step_fn):
        model, params = model_and_params

        def run(seed):
            cache = fresh_cache()
            eng, _, _ = make_batcher(model, params, step_fn, cache)
            res = run_to_completion(eng, cache, [serving.Request(
                id=0, prompt=[7] * 6, max_new_tokens=12,
                temperature=0.9, top_k=16, seed=seed)])
            return res[0].tokens

        a, b, c = run(1), run(1), run(2)
        assert a == b                     # same seed: same stream
        assert a != c                     # different seed: different

    def test_top_k_one_equals_greedy(self, model_and_params, step_fn):
        model, params = model_and_params
        cache = fresh_cache()
        eng, _, _ = make_batcher(model, params, step_fn, cache)
        greedy = run_to_completion(eng, cache, [serving.Request(
            id=0, prompt=[9] * 5, max_new_tokens=8)])
        cache2 = fresh_cache()
        eng2, _, _ = make_batcher(model, params, step_fn, cache2)
        k1 = run_to_completion(eng2, cache2, [serving.Request(
            id=0, prompt=[9] * 5, max_new_tokens=8, temperature=1.0,
            top_k=1, seed=5)])
        assert greedy[0].tokens == k1[0].tokens

    def test_mixed_greedy_and_sampled_batch(self, model_and_params,
                                            step_fn):
        """Sampling is per-lane: a greedy request in a batch with a
        sampled one still produces its greedy stream exactly."""
        model, params = model_and_params
        cache = fresh_cache()
        eng, _, _ = make_batcher(model, params, step_fn, cache)
        solo = run_to_completion(eng, cache, [serving.Request(
            id="g", prompt=[4] * 6, max_new_tokens=6)])
        cache2 = fresh_cache()
        eng2, _, _ = make_batcher(model, params, step_fn, cache2)
        mixed = run_to_completion(eng2, cache2, [
            serving.Request(id="g", prompt=[4] * 6, max_new_tokens=6),
            serving.Request(id="s", prompt=[8] * 6, max_new_tokens=6,
                            temperature=1.2, top_p=0.9, seed=3)])
        assert mixed["g"].tokens == solo["g"].tokens
        assert mixed["s"].finish_reason == "length"

    def test_sampled_resume_replays_token_for_token(
            self, model_and_params, step_fn, tmp_path):
        """The RNG-state-in-snapshot contract: a sampled stream cut by
        a drain snapshot resumes exactly where it left off."""
        model, params = model_and_params
        reqs = [serving.Request(id=i, prompt=[3 + i] * 5,
                                max_new_tokens=8, temperature=0.8,
                                top_k=24, seed=40 + i)
                for i in range(3)]
        cache = fresh_cache()
        eng, _, _ = make_batcher(model, params, step_fn, cache)
        clean = run_to_completion(eng, cache, reqs)

        handler = PreemptionHandler()        # not installed: flag only
        cache2 = fresh_cache()
        eng2, _, _ = make_batcher(
            model, params, step_fn, cache2, preemption=handler,
            snapshot_dir=str(tmp_path))
        state = cache2.init_state()
        for r in reqs:
            eng2.submit(r)
        state, _ = eng2.step(state)
        state, _ = eng2.step(state)          # a few sampled tokens
        handler.requested = True
        state, rep = eng2.step(state)
        assert rep["snapshot"] is not None
        phase1 = eng2.drain()
        snap = sresil.load_snapshot(rep["snapshot"])
        assert all("seed" in e for e in snap["requests"])
        resumed, prior = sresil.resume_requests(snap)
        cache3 = fresh_cache()
        eng3, _, _ = make_batcher(model, params, step_fn, cache3)
        _, results = serving.serve_loop(eng3, cache3.init_state(),
                                        resumed)
        merged = sresil.merge_results(results, prior)
        got = {r.id: r.tokens for r in merged}
        got.update({r.id: r.tokens for r in phase1})
        assert got == {i: r.tokens for i, r in clean.items()}


# ---------------------------------------------------------------------------
# compile plane
# ---------------------------------------------------------------------------


class TestChunkCompilePlane:
    def test_chunking_mints_bounded_programs_zero_hot_recompiles(
            self, model_and_params):
        from apex_tpu.telemetry import compiled as _compiled

        model, params = model_and_params
        cache = fresh_cache()
        step = serving.make_decode_step(model, cache)
        reg = telemetry.MetricsRegistry()
        sink = telemetry.InMemorySink()
        reg.add_sink(sink)
        tracker = _compiled.enable(registry=reg, storm_threshold=1000)
        try:
            eng = serving.ContinuousBatcher(
                model, params, cache, step_fn=step, max_batch=4,
                max_prefill_batch=2, prefill_chunk=8,
                min_seq_bucket=8, registry=reg)
            # long prompts reserve wide tables: warm both width
            # buckets (the operator contract — warm what you serve)
            state = eng.warmup(cache.init_state(),
                               width_buckets=[4, 8])
            keys = step.compile_keys()
            # chunk programs: batch buckets {1, 2} x chunk buckets
            # {8} x width buckets {4, 8} — bounded by the bucket grid
            assert keys["prefill_chunk"] == 4
            assert keys["decode_step"] == 2
            n_warm = [e["event"] for e in sink.events].count("recompile")
            rng = np.random.RandomState(29)
            reqs = []
            for i in range(10):
                plen = 22 if i % 3 == 0 else int(rng.randint(2, 9))
                reqs.append(serving.Request(
                    id=i, prompt=rng.randint(0, VOCAB, (plen,)),
                    max_new_tokens=int(rng.randint(1, 5))))
            state, results = serving.serve_loop(eng, state, reqs)
            assert len(results) == 10
            hot = [e["event"] for e in sink.events].count("recompile")
            assert hot == n_warm, "chunking recompiled in the hot loop"
            assert step.compile_keys() == keys
        finally:
            _compiled.disable()


# ---------------------------------------------------------------------------
# one host array in, one host array out
# ---------------------------------------------------------------------------

LAYOUTS = [("decode_step", 1, None), ("decode_step", 1, 3),
           ("prefill_step", 8, None), ("prefill_chunk", 8, None),
           ("prefill_chunk", 8, 3)]


# the program keys boundary_requests() reaches at commit 24b2cc4,
# before the packed argument
PARENT_KEYS = [("decode_step", 4, 4), ("decode_step", 4, 8),
               ("prefill_chunk", 1, 8, 4), ("prefill_chunk", 1, 8, 8),
               ("prefill_step", 2, 8, 4)]


def host_unpack(packed, layout):
    """The packed argument taken apart on the host, with numpy views:
    what the program's static slices and bit casts must give."""
    fields, at = {}, 0
    for name, shape in layout:
        n = int(np.prod(shape))
        dtype = {"temps": np.float32, "top_ps": np.float32,
                 "seeds": np.uint32}.get(name, np.int32)
        fields[name] = packed[at:at + n].view(dtype).reshape(shape)
        at += n
    assert at == len(packed)
    return fields


def sampled_lanes(b):
    """Per-lane sampling arrays with lane 0 greedy, floats that are
    not round numbers and seeds above 2^31."""
    temps = np.array([0.0, 0.8, 1.3, 0.0][:b], np.float32)
    top_ks = np.array([0, 5, 0, 0][:b], np.int32)
    top_ps = np.array([1.0, 0.9, 1.0, 1.0][:b], np.float32)
    seeds = np.array([0, 3_000_000_000, 2 ** 32 - 1, 0][:b], np.uint32)
    return temps, top_ks, top_ps, seeds


class _Unreadable:
    def __array__(self, *a, **kw):
        raise AssertionError("the engine read more than StepOut.packed")


class OnlyPacked:
    """A step_fn whose results can be read through ``packed`` alone;
    counts the dispatches."""

    def __init__(self, inner):
        self.inner, self.calls = inner, 0

    def _strip(self, out):
        self.calls += 1
        return out._replace(logits=_Unreadable(), next_token=_Unreadable(),
                            finite=_Unreadable())

    def prefill(self, *a, **kw):
        return self._strip(self.inner.prefill(*a, **kw))

    def prefill_chunk(self, *a, **kw):
        return self._strip(self.inner.prefill_chunk(*a, **kw))

    def decode(self, *a, **kw):
        return self._strip(self.inner.decode(*a, **kw))


class WithoutPacked(OnlyPacked):
    """The benchmark's ``Recorder`` shape, ``StepOut(None, ids, state,
    None)``, or an older step_fn's, with the finite flags."""

    def __init__(self, inner, flags):
        super().__init__(inner)
        self.flags = flags

    def _strip(self, out):
        return sdecode.StepOut(None, out.next_token, out.cache,
                               out.finite if self.flags else None)


def boundary_requests():
    """Prompts prefilled whole (5, 3) and in chunks of 8 (20), greedy
    and sampled lanes."""
    rng = np.random.RandomState(31)
    return [serving.Request(
        id=i, prompt=rng.randint(0, VOCAB, (n,)), max_new_tokens=5, **kw)
        for i, (n, kw) in enumerate([
            (5, {}), (20, dict(temperature=0.8, top_k=5, top_p=0.9,
                               seed=3_000_000_000)), (3, {})])]


def serve_boundary(model, params, step_fn):
    cache = fresh_cache()
    eng, reg, _ = make_batcher(model, params, step_fn, cache,
                               prefill_chunk=8)
    res = run_to_completion(eng, cache, boundary_requests())
    assert cache.blocks_in_use == 0
    return {i: (r.finish_reason, r.tokens) for i, r in res.items()}, reg


@pytest.fixture(scope="module")
def plain(model_and_params):
    """What the engine serves through a bare ``DecodeStep``."""
    model, params = model_and_params
    return serve_boundary(
        model, params, serving.make_decode_step(model, fresh_cache()))[0]


@pytest.fixture(scope="module", params=[None, 8], ids=["full", "window"])
def windowed(request):
    """A model without and with window layers: the second carries the
    window tables through the packed argument."""
    model = GPTModel(tiny_config(attention_window=request.param))
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    return model, params, request.param


class TestOneArrayBoundary:
    @pytest.mark.parametrize("fn,seq,window_width", LAYOUTS)
    def test_packed_layout_round_trips_every_field(self, fn, seq,
                                                   window_width):
        b, width = 4, 6
        layout = sdecode.packed_layout(fn, b, width, seq, window_width)
        rng = np.random.default_rng(5)
        fields = {}
        for name, shape in layout:
            if name in ("temps", "top_ps"):
                fields[name] = np.array([0.7, -0.0, 1e-40, np.inf],
                                        np.float32)
            elif name == "seeds":
                fields[name] = np.array([0, 2 ** 31, 3_000_000_000,
                                         2 ** 32 - 1], np.uint32)
            else:
                fields[name] = rng.integers(-5, 2 ** 31 - 1, shape,
                                            dtype=np.int64).astype(np.int32)
        packed = sdecode.pack(layout, fields)
        assert packed.dtype == np.int32
        assert packed.shape == (sdecode.packed_size(layout),)
        on_device = jax.jit(
            lambda p: sdecode.unpack(p, layout))(packed)
        on_host = host_unpack(packed, layout)
        assert sorted(on_device) == sorted(name for name, _ in layout)
        for name, shape in layout:
            got = np.asarray(on_device[name])
            assert got.dtype == fields[name].dtype, name
            assert got.shape == shape
            for other in (on_host[name], fields[name]):
                assert got.tobytes() == other.tobytes(), name
        # the layout is the key's alone: sampling given or not, window
        # tables given or not
        assert (window_width is not None) == any(
            name == "window_tables" for name, _ in layout)
        with pytest.raises(ValueError, match=layout[1][0]):
            sdecode.pack(layout, {**fields, layout[1][0]: np.zeros(b + 1)})

    @pytest.mark.parametrize("mode", ["greedy", "sampled"])
    @pytest.mark.parametrize("fn", ["prefill_step", "prefill_chunk",
                                    "decode_step"])
    def test_programs_match_a_reference_unpacked_on_the_host(
            self, windowed, fn, mode):
        """Each program over the packed argument against its body
        jitted over separate arrays, taken out of the same buffer on
        the host: the same token ids, finite flags, logits and cache,
        bit for bit; ``packed`` is the ids over the flags."""
        model, params, window = windowed
        cache = fresh_cache()
        step = serving.make_decode_step(model, cache)
        b, width, lens = 4, 8, (5, 9, 12)
        seqs = list(range(len(lens)))
        for sid in seqs:
            cache.allocate(sid, 24)
        tables = cache.table_array(seqs, width, batch=b)
        rng = np.random.RandomState(3)
        prompt = np.zeros((b, 16), np.int32)
        for i, n in enumerate(lens):
            prompt[i, :n] = rng.randint(0, VOCAB, (n,))
        at = np.array([*lens, 0], np.int32)          # lane 3 is a dummy

        def tail(positions):
            if window is None:
                return None
            return cache.window_table_array(
                seqs, positions, window, cache.window_width(window, width),
                batch=b)

        def prefilled():
            return step.prefill(params, cache.init_state(), prompt, at,
                                tables).cache

        sampling = sampled_lanes(b) if mode == "sampled" else None
        if fn == "prefill_step":
            states = cache.init_state(), cache.init_state()
            args = (prompt, at, tables)
            kw = dict(sampling=sampling)
        elif fn == "prefill_chunk":
            states = prefilled(), prefilled()
            chunk = rng.randint(0, VOCAB, (b, 8)).astype(np.int32)
            args = (chunk, at, np.array([3, 8, 1, 0], np.int32), tables)
            kw = dict(sampling=sampling, window=tail(at))
        else:
            states = prefilled(), prefilled()
            args = (rng.randint(0, VOCAB, (b,)).astype(np.int32), at, tables)
            kw = dict(sampling=sampling, window=tail(at))
        if window is None or fn == "prefill_step":
            kw.pop("window", None)
        method = {"prefill_step": step.prefill,
                  "prefill_chunk": step.prefill_chunk,
                  "decode_step": step.decode}[fn]
        before = dict(step.transfers)
        got = method(params, states[0], *args, **kw)
        assert {k: step.transfers[k] - before[k] for k in before} == {
            "dispatches": 1, "host_arrays_in": 1, "host_arrays_out": 1}

        (key,) = [k for k in step._compiled if k[0] == fn]
        layout, _ = step._compiled[key]
        fields = {"tokens": args[0], "tables": tables}
        names = {"prefill_step": ("lengths",),
                 "prefill_chunk": ("starts", "lengths"),
                 "decode_step": ("positions",)}[fn]
        fields.update(zip(names, args[1:]))
        if kw.get("window") is not None:
            fields["window_tables"], fields["window_first"] = kw["window"]
        fields.update(zip(("temps", "top_ks", "top_ps", "seeds"),
                          sampling or sdecode.greedy_sampling(b)))
        apart = host_unpack(sdecode.pack(layout, fields), layout)
        want = jax.jit(step._bodies[fn], donate_argnums=(1,))(
            params, states[1], **apart)

        for name in ("logits", "next_token", "finite", "packed"):
            np.testing.assert_array_equal(np.asarray(getattr(got, name)),
                                          np.asarray(getattr(want, name)))
        for g, w in zip(got.cache.pools, want.cache.pools, strict=True):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        np.testing.assert_array_equal(
            np.asarray(got.packed),
            np.stack([np.asarray(got.next_token),
                      np.asarray(got.finite).astype(np.int32)]))
        assert np.asarray(got.packed).dtype == np.int32
        np.testing.assert_array_equal(sdecode.host_tokens(got),
                                      np.asarray(got.packed))

    def test_engine_moves_one_array_each_way_a_dispatch(
            self, model_and_params, plain):
        """A short run with whole prefills, chunks and decodes, greedy
        and sampled: one host array in and one out a dispatch, the
        engine reads ``packed`` alone, and serves what it served."""
        model, params = model_and_params
        step = serving.make_decode_step(model, fresh_cache())
        only = OnlyPacked(step)
        served, _ = serve_boundary(model, params, only)
        assert served == plain
        assert all(reason == "length" for reason, _ in served.values())
        assert only.calls > 8
        assert step.transfers == {"dispatches": only.calls,
                                  "host_arrays_in": only.calls,
                                  "host_arrays_out": only.calls}

    @pytest.mark.parametrize("flags", [False, True],
                             ids=["recorder", "older"])
    def test_step_fn_without_the_packed_field_still_serves(
            self, model_and_params, plain, flags):
        model, params = model_and_params
        served, _ = serve_boundary(model, params, WithoutPacked(
            serving.make_decode_step(model, fresh_cache()), flags))
        assert served == plain

    @pytest.mark.parametrize("stand_in", ["packed", "older"])
    def test_nonfinite_lane_quarantined_from_the_flags_read(
            self, model_and_params, plain, stand_in):
        model, params = model_and_params
        step = serving.make_decode_step(model, fresh_cache())
        step_fn = (OnlyPacked(step) if stand_in == "packed"
                   else WithoutPacked(step, True))
        with faults.inject(decode_nonfinite_steps=frozenset({2}),
                           decode_nonfinite_lane=1):
            served, reg = serve_boundary(model, params, step_fn)
        bad = [i for i, (reason, _) in served.items() if reason == "error"]
        assert len(bad) == 1
        assert reg.counter("serving_quarantined").value(
            reason="nonfinite") == 1
        for i, (reason, toks) in served.items():
            if i in bad:
                assert toks == plain[i][1][:len(toks)]
            else:
                assert (reason, toks) == plain[i]

    def test_compile_keys_over_a_fixed_deck(self, model_and_params):
        """The programs a fixed deck reaches, by kind: the counts of
        the engine before the packed argument (read at its commit)."""
        model, params = model_and_params
        step = serving.make_decode_step(model, fresh_cache())
        serve_boundary(model, params, step)
        assert sorted(step._compiled) == PARENT_KEYS
        assert step.compile_keys() == {
            "prefill_step": 1, "prefill_chunk": 2, "decode_step": 2}


# ---------------------------------------------------------------------------
# fault drills
# ---------------------------------------------------------------------------


class TestChunkFaultDrills:
    def test_prefill_chunk_exception_quarantines_batch(
            self, model_and_params, step_fn, tmp_path, monkeypatch):
        from apex_tpu import records

        monkeypatch.setattr(records, "RECORDS_DIR", str(tmp_path))
        model, params = model_and_params
        cache = fresh_cache()
        eng, reg, sink = make_batcher(model, params, step_fn, cache,
                                      prefill_chunk=4)
        state = cache.init_state()
        with faults.inject(
                prefill_chunk_exception_indices=frozenset({0})):
            eng.submit(serving.Request(id="dead", prompt=[1] * 12,
                                       max_new_tokens=4))
            state, rep = eng.step(state)
            assert rep["quarantined"] == ["dead"]
            assert rep["finished"] == ["dead"]
        res = eng.drain()
        assert res[0].finish_reason == "error"
        assert "prefill-chunk exception" in res[0].error
        assert reg.counter("serving_quarantined").value(
            reason="exception") == 1
        assert cache.blocks_in_use == 0
        # engine keeps serving after the fault window
        eng.submit(serving.Request(id="alive", prompt=[2] * 12,
                                   max_new_tokens=2))
        while not eng.idle():
            state, _ = eng.step(state)
        assert eng.drain()[0].finish_reason == "length"

    def test_transient_io_prefill_chunk_absorbed(self, model_and_params,
                                                 step_fn):
        model, params = model_and_params
        reqs = [serving.Request(id=i, prompt=[2 + i] * 12,
                                max_new_tokens=3) for i in range(2)]
        cache0 = fresh_cache()
        eng0, _, _ = make_batcher(model, params, step_fn, cache0,
                                  prefill_chunk=4)
        clean = run_to_completion(eng0, cache0, reqs)
        cache = fresh_cache()
        eng, reg, _ = make_batcher(model, params, step_fn, cache,
                                   prefill_chunk=4)
        state = cache.init_state()
        with faults.inject(io_errors={"prefill_chunk": frozenset({1})}):
            for r in reqs:
                eng.submit(r)
            while not eng.idle():
                state, _ = eng.step(state)
        res = {r.id: r for r in eng.drain()}
        assert {r.finish_reason for r in res.values()} == {"length"}
        assert res[0].tokens == clean[0].tokens
        assert res[1].tokens == clean[1].tokens
        assert reg.counter("serving_quarantined").value() == 0

    def test_env_knob_grammar(self):
        inj = faults.FaultInjector.from_env(
            "prefill_chunk_exception=1,3;io:prefill_chunk=0")
        with pytest.raises(faults.FaultError):
            inj.maybe_prefill_chunk_exception(1)
        with pytest.raises(faults.FaultError):
            inj.maybe_prefill_chunk_exception(3)
        inj.maybe_prefill_chunk_exception(0)   # off-plan: no-op
        with pytest.raises(faults.FaultError):
            inj.check("prefill_chunk")
        inj.check("prefill_chunk")             # index 1: clean


# ---------------------------------------------------------------------------
# request validation
# ---------------------------------------------------------------------------


class TestSamplingValidation:
    def test_bad_knobs_rejected(self):
        with pytest.raises(ValueError, match="temperature"):
            serving.Request(id=0, prompt=[1], temperature=-0.1)
        with pytest.raises(ValueError, match="top_k"):
            serving.Request(id=0, prompt=[1], top_k=-1)
        with pytest.raises(ValueError, match="top_p"):
            serving.Request(id=0, prompt=[1], top_p=0.0)
        with pytest.raises(ValueError, match="top_p"):
            serving.Request(id=0, prompt=[1], top_p=1.5)
        serving.Request(id=0, prompt=[1], temperature=0.7, top_k=5,
                        top_p=0.9, seed=11)
