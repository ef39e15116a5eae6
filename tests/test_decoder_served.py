"""The pattern decoder (and GPT, for the gather) served through the
paged cache by ``serving.ContinuousBatcher``, against the plain float32
references: ``tests/test_decoder.py``'s blocks, weights and helpers,
in a file of their own because ``--dist loadfile`` gives a file one
worker and the two files together are the suite's longest stretch."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import serving
from apex_tpu.models.decoder import PatternDecoder
from test_decoder import (BF16_EPS, BLOCK, BLOCKS, VOCAB, WINDOW,  # noqa: F401
                          _engine, _serve, all_blocks, built, config,
                          seeded, tokens)


@all_blocks
@pytest.mark.parametrize("chunk", [None, 8, 5])
def test_served_through_the_cache_matches_the_reference(built, block, chunk):
    """Prefill (whole, or in chunks that cross the window's edge at 8)
    then decode, several lengths in one batch, short of the window and
    past it: every served token is the reference's argmax of a full
    pass, and the pool is empty after the drain."""
    model, params = built(block)
    config, arch, ref, _ = BLOCKS[block]
    engine, cache = _engine(model, params, config(), prefill_chunk=chunk)
    requests = [serving.Request(id=i, prompt=tokens(n, 10 + i),
                                max_new_tokens=m)
                for i, (n, m) in enumerate([(5, 6), (13, 9), (30, 12),
                                            (21, 5), (7, 10)])]
    done = _serve(engine, cache, requests)
    for r in requests:
        out = ref.check_served(params, arch(), r.prompt, done[r.id].tokens,
                               ulps=0.01, dtype_eps=BF16_EPS)
        assert out["ok"] and out["exact"] == r.max_new_tokens, (r.id, out)
    assert cache.blocks_in_use == 0
    keys = sorted(engine.step_fn._compiled)
    if block == "granite":
        # no window layer, no second table; every slot came back, and
        # the pool has the ONE layer that has keys
        assert engine.gathered["window"] == 0 < engine.gathered["full"]
        assert all(len(k) == (3 if k[0] == "decode_step" else 4)
                   for k in keys), keys
        assert cache.slots_in_use == 0 and cache.num_layers == 1
        return
    # window layers gathered less than the full layer did
    assert 0 < engine.gathered["window"] < engine.gathered["full"]
    assert all(len(k) == (4 if k[0] == "decode_step" else 5)
               for k in keys if k[0] != "prefill_step"), keys


def _dispatches(engine):
    """Every dispatch the engine makes from here on, as (program,
    logits, token ids, finite flags) on the host."""
    log = []
    for name in ("prefill", "prefill_chunk", "decode"):
        def recorded(*args, _real=getattr(engine.step_fn, name), _name=name,
                     **kw):
            out = _real(*args, **kw)
            log.append((_name, *(np.asarray(x) for x in (
                out.logits, out.next_token, out.finite))))
            return out
        setattr(engine.step_fn, name, recorded)
    return log


def _kernel_model(kind):
    """A model whose gather is the kernel (interpreted): GPT, its layers
    scanned or unrolled, or a pattern decoder with window and full
    layers."""
    if kind == "pattern":
        cfg = dataclasses.replace(
            config(layers=(("window", "dense"), ("full", "dense"),
                           ("window", "dense"), ("full", "dense"))),
            softmax_impl="interpret")
        model = PatternDecoder(cfg)
    else:
        from apex_tpu.models.gpt import GPTConfig, GPTModel

        cfg = GPTConfig(vocab_size=VOCAB, max_seq_len=64, hidden_size=64,
                        num_layers=3, num_heads=4, num_kv_heads=2,
                        scan_layers=kind == "gpt-scan", dtype=jnp.float32,
                        softmax_impl="interpret")
        model = GPTModel(cfg)
    shapes = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32)),
        jax.random.PRNGKey(0))
    return model, seeded(shapes), cfg


@pytest.mark.parametrize("kind", ["gpt-scan", "gpt-unrolled", "pattern"])
def test_gather_to_each_lanes_length_serves_the_whole_tables_answers(
        kind, monkeypatch):
    """A deck with a lane at a block's edge (8), a lane past the window
    (21, in chunks of 8) and, three requests on four lanes, a dummy
    lane: every dispatch's logits, tokens and finite flags are bitwise
    those of the same deck with every gather forced through the whole
    table."""
    from apex_tpu.ops import kv_gather as gather_module

    model, params, cfg = _kernel_model(kind)

    def served():
        engine, cache = _engine(model, params, cfg, prefill_chunk=8)
        log = _dispatches(engine)
        done = _serve(engine, cache, [
            serving.Request(id=i, prompt=tokens(n, 30 + i), max_new_tokens=m)
            for i, (n, m) in enumerate([(8, 6), (21, 7), (5, 4)])])
        assert cache.blocks_in_use == 0
        return log, done, dict(engine.gathered)

    got, done, gathered = served()
    real = gather_module.kv_gather
    monkeypatch.setattr(
        gather_module, "kv_gather",
        lambda pools, layer, tables, lens, into=(), **kw: real(
            pools, layer, tables,
            jnp.full_like(lens, tables.shape[1] * pools[0].shape[2]), into,
            **kw))
    want, whole, _ = served()
    assert len(got) == len(want) > 10
    assert {name for name, *_ in got} == {"prefill", "prefill_chunk",
                                          "decode"}
    for (name, *outs), (name_w, *outs_w) in zip(got, want):
        assert name == name_w
        for a, b in zip(outs, outs_w):
            np.testing.assert_array_equal(a, b)
    assert all(flags.all() for *_, flags in got)
    assert {i: r.tokens for i, r in done.items()} \
        == {i: r.tokens for i, r in whole.items()}
    assert 0 < gathered["full_live"] < gathered["full"]
    assert (0 < gathered["window_live"] < gathered["window"]) \
        == (kind == "pattern")


def test_gathered_counts_the_live_blocks():
    """``ContinuousBatcher.gathered``, one request on one lane: a lane
    that fills its table has every addressed position live, and past
    the window a window layer's live positions stop growing while the
    full layer's go on."""
    model, params, cfg = _kernel_model("pattern")

    def deltas(prompt, new):
        cache = serving.KVCache.for_config(cfg, num_blocks=96,
                                           block_size=BLOCK)
        engine = serving.ContinuousBatcher(
            model, params, cache, max_batch=1, min_width_bucket=2,
            min_seq_bucket=4)
        state = cache.init_state()
        engine.submit(serving.Request(id=0, prompt=tokens(prompt),
                                      max_new_tokens=new))
        seen = [dict(engine.gathered)]
        while not engine.idle():
            state, _ = engine.step(state)
            seen.append(dict(engine.gathered))
        return [{k: b[k] - a[k] for k in a} for a, b in zip(seen, seen[1:])
                if b != a]

    # 6 + 2 tokens reserve two blocks, the width's least bucket: the
    # one decode dispatch, at position 6, finds both of them live
    (only,) = deltas(6, 2)
    assert only["full_live"] == only["full"] == 2 * BLOCK
    assert only["window_live"] == only["window"] == 2 * BLOCK
    steps = deltas(30, 12)
    assert len(steps) == 11
    for a, b in zip(steps, steps[1:]):
        assert b["full_live"] >= a["full_live"] >= 28
        assert BLOCK * (WINDOW // BLOCK) <= b["window_live"] <= WINDOW + BLOCK
        assert b["full_live"] <= b["full"] and b["window_live"] <= b["window"]
    assert steps[-1]["full_live"] > steps[0]["full_live"]
