"""Latent attention (``glm4_moe_lite``'s block through
``models/decoder.py``) against its plain float32 reference
(``models/decoder_reference_glm.py``), at a small size on the CPU with
seeded random weights: hidden 64, 4 heads, a query latent of 48,
``c_kv`` of 32, heads of 24 + 16 rotary, values of 32 (the cache holds
one 128-wide row a token in one pool), a dense layer, then 8 experts
top-2 with 4 held beside a shared expert under the sigmoid router
scaled by 1.8; prefill and chunks take the expanded form, decode the
absorbed one (``ops/mla_decode.py``).

Kept apart from ``tests/test_decoder.py``, whose blocks are the other
pattern models': that file alone is the longest stretch of the suite
under one worker.
"""

import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import serving
from apex_tpu.models import decoder_reference as ref
from apex_tpu.models import decoder_reference_glm as lref
from apex_tpu.models.decoder import DecoderConfig, MLAConfig, PatternDecoder
from apex_tpu.moe.held import HeldMoEConfig, HeldMoEMLP

VOCAB, THETA = 64, 500000.0
BF16_EPS = float(jnp.finfo(jnp.bfloat16).eps)
LAYERS = (("latent", "dense"), ("latent", "experts"), ("latent", "experts"))
MLA = MLAConfig(q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=24,
                qk_rope_head_dim=16, v_head_dim=32)
HELD = (2, 4)


def glm_config():
    return DecoderConfig(
        vocab_size=VOCAB, hidden_size=64, num_heads=4, num_kv_heads=4,
        head_dim=40, max_seq_len=128, layers=LAYERS, ffn_hidden_size=128,
        expert_ffn_size=48, num_experts=8, experts_per_token=2,
        held_experts=HELD, shared_ffn_size=48, route_scale=1.8,
        rope_theta=THETA, dtype=jnp.float32, param_dtype=jnp.float32,
        norms="pre", embedding_scale=False, output_gate=False, mla=MLA)


def glm_arch():
    return lref.Arch(4, 48, 32, 24, 16, 32, tuple(m for _, m in LAYERS),
                     top_k=2, route_scale=1.8, held=HELD, theta=THETA)


def seeded(shapes, seed=0):
    """Weights that make every part matter: gains off 1, a selection
    bias that changes choices, a router that spreads its scores."""
    def leaf(path, x):
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        key = jax.random.fold_in(jax.random.PRNGKey(seed),
                                 zlib.crc32(name.encode()) % 2**31)
        draw = jax.random.normal(key, x.shape, jnp.float32)
        if name.endswith("scale"):
            return 1.0 + 0.1 * draw
        if name.endswith("select_bias"):
            return 0.3 * draw
        return (0.4 if name.endswith("router") else 0.06) * draw
    return jax.tree_util.tree_map_with_path(leaf, shapes)


@pytest.fixture(scope="module")
def built():
    model = PatternDecoder(glm_config())
    shapes = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32)),
        jax.random.PRNGKey(0))
    return model, seeded(shapes)


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, VOCAB, n).astype(np.int32)


def test_the_expanded_form_matches_the_reference(built):
    """A whole sequence through the expanded form, no cache: float32
    against float32, what is left is the order of the sums; the cut
    leaves experts out and keeps some."""
    model, params = built
    toks = tokens(40)
    got = model.apply(params, toks[None])[:, 0]
    want, routing = lref.forward(params, toks, np.arange(40), glm_arch(),
                                 row_block=16)
    assert float(jnp.abs(got - want).max()) < 2e-5 * float(
        jnp.abs(want).max())
    assert len(routing) == 2 and all(
        0 < f["held_pairs"] < f["pairs"] for f in routing)


def test_latent_rows_through_the_pool_and_a_handoff(built):
    """Through ``DecodeStep`` over the latent pool: a whole prompt, a
    chunk that starts and ends inside blocks (the expanded form over the
    gathered rows), then decode (the absorbed form), then the
    sequence's rows exported and installed into another engine's pool
    (a disaggregated handoff) where it decodes on. Every row of logits
    is the reference's full pass's. The pool is one 128-wide row a
    token a layer in one pool; the handoff carries that one pool's
    payload; a scrub zeroes the rows and a quarantine's poison lands in
    them."""
    from apex_tpu.serving.resilience import poison_lane_kv

    model, params = built
    cfg = glm_config()
    toks = tokens(40, 3)
    want, _ = lref.forward(params, toks, np.arange(40), glm_arch(),
                           row_block=16)
    got = []
    caches = [serving.KVCache.for_config(cfg, num_blocks=16, block_size=8)
              for _ in range(2)]
    step = [serving.make_decode_step(model, c) for c in caches]
    a, b = caches
    assert a.row_bytes() == 3 * 128 * 4 and a.block_bytes() == 8 * 1536
    state = a.init_state()
    assert [p.shape for p in state.pools] == [(3, 17, 8, 1, 128)]
    a.allocate(0, 40)

    def table(cache, sid):
        t = cache.table(sid)
        return np.array([t + [0] * (8 - len(t))], np.int32)

    out = step[0].prefill(params, state, toks[None, :16], np.array([12]),
                          table(a, 0))
    got.append(out.logits[0])
    out = step[0].prefill_chunk(params, out.cache, toks[None, 12:28],
                                np.array([12]), np.array([13]), table(a, 0))
    got.append(out.logits[0])
    state = out.cache
    for p in range(25, 31):
        out = step[0].decode(params, state, toks[p:p + 1], np.array([p]),
                             table(a, 0))
        state = out.cache
        got.append(out.logits[0])
    blocks, payloads = a.export_blocks(state, 0, length=31)
    assert [p.shape for p in payloads] == [(3, 4, 8, 1, 128)]
    b.allocate("other", 9)              # the rows land in other blocks
    b.allocate(7, 40)
    assert b.table(7)[:4] != blocks
    other = b.import_blocks(b.init_state(), 7, payloads)
    for p in range(31, 39):
        out = step[1].decode(params, other, toks[p:p + 1], np.array([p]),
                             table(b, 7))
        other = out.cache
        got.append(out.logits[0])
    rows = [11, 24] + list(range(25, 39))
    np.testing.assert_allclose(np.asarray(jnp.stack(got)),
                               np.asarray(want[np.asarray(rows)]),
                               atol=2e-5 * float(jnp.abs(want).max()))
    poisoned = poison_lane_kv(other, b, 7, 33)
    (pool,) = poisoned.pools
    assert bool(jnp.isnan(pool[:, b.table(7)[4], 1]).all())
    (pool,) = serving.kv_cache.scrub_blocks(state, blocks).pools
    assert not bool(pool[:, blocks].any())


def test_the_references_experts_take_their_rows_256_a_call(built):
    """The reference's held experts, 256 rows a call (one program
    whatever an expert's count), add up to what one call an expert
    gives (``decoder_reference._experts``): 600 rows, the program's
    choice followed, two held experts given 301 (two calls) and 299 of
    them, the other two none."""
    _, params = built
    mlp = params["params"]["layer_1"]["mlp"]
    m = jax.random.normal(jax.random.PRNGKey(3), (600, 64), jnp.float32)
    theirs = np.stack([np.where(np.arange(600) <= 300, 3, 2),
                       np.full(600, 7)], axis=1)
    arch = glm_arch()
    got, facts = lref._experts(m, mlp, arch, None, theirs, band=10.0)
    want, their_facts = ref._experts(m, mlp, arch, None, (), theirs,
                                     band=10.0)
    assert facts["held_pairs"] == their_facts["held_pairs"] == 600
    assert int((facts["chosen"] == 3).sum()) == 301
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("length", [256, 2048])
def test_mla_decode_kernel_is_the_plain_form(length):
    """``ops/mla_decode.py`` interpreted against its plain ``jnp`` form
    at two widths, ragged lengths (one key, a third, all of them, a
    length inside a block), 20 heads over 640-wide rows: the kernel's
    online softmax over blocks of rows and its bf16 ``p`` agree with
    the float32 softmax to bf16's rounding."""
    from apex_tpu.ops.mla_decode import mla_decode

    k1, k2 = jax.random.split(jax.random.PRNGKey(length))
    q = jax.random.normal(k1, (4, 20, 640), jnp.float32).astype(jnp.bfloat16)
    rows = jax.random.normal(k2, (4, 1, length, 640),
                             jnp.float32).astype(jnp.bfloat16)
    lens = jnp.array([1, length // 3, length, length - 7], jnp.int32)
    kw = dict(value_dim=512, scale=256 ** -0.5)
    want = mla_decode(q, rows, lens, impl="xla", **kw)
    got = mla_decode(q, rows, lens, impl="interpret", **kw)
    assert got.shape == (4, 20, 512) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-2 * float(jnp.abs(want).max()))
    # one key: its value, exactly
    np.testing.assert_allclose(
        np.asarray(got[0]),
        np.broadcast_to(np.asarray(rows[0, 0, 0, :512], np.float32),
                        (20, 512)), rtol=1e-6)


def test_eight_shares_of_one_expert_add_up_to_the_uncut_layer():
    """GLM's expert layer in eight shares of one expert each (8-way
    expert parallelism over 8 routed experts, top-2, scaled by 1.8): the
    routed parts every share computes, plus the shared expert once,
    equal the uncut layer of the reference."""
    full = HeldMoEConfig(hidden_size=64, expert_ffn_size=48, num_experts=8,
                         top_k=2, route_scale=1.8, shared_ffn_size=48,
                         dtype=jnp.float32)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(3, 11, 64)),
                    jnp.float32)
    whole = seeded(jax.eval_shape(
        lambda k: HeldMoEMLP(full).init(k, x), jax.random.PRNGKey(0)), 7)
    want, facts = ref._experts(
        x.reshape(-1, 64), whole["params"],
        ref.Arch(4, 2, 32, (), None, top_k=2, route_scale=1.8), None, ())
    assert facts["held_pairs"] == facts["pairs"]
    total = 0.0
    for first in range(8):
        share = dict(whole["params"])
        for name in ("w_gate", "w_up", "w_down"):
            share[name] = whole["params"][name][first:first + 1]
        if first:                       # the shared expert counted once
            for name in ("shared_gate", "shared_up", "shared_down"):
                share[name] = jnp.zeros_like(share[name])
        cfg = HeldMoEConfig(**{**full.__dict__, "held": (first, 1)})
        total = total + HeldMoEMLP(cfg).apply({"params": share}, x)
    np.testing.assert_allclose(np.asarray(total).reshape(-1, 64),
                               np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("row_served", ["under the other choice", "off"])
def test_a_row_astray_is_judged_again_under_the_nearest_flip(built,
                                                             row_served):
    """A served row whose logits leave the reference's, at a margin by
    the program's pass over the slack, is judged again with the choice at
    that row changed to the nearest held flip of one expert layer at a
    time: where the program's logits there are the reference's under
    that choice the row is swapped and excused; where they are the
    reference's moved by noise no flip explains it and the request
    fails."""
    from benchmark.drivers.serve_pattern import program_choice

    model, params = built
    arch = glm_arch()
    prompt, served = tokens(12, seed=5), tokens(6, seed=6)
    toks, rows = lref.teacher_forced(prompt, served, 8)
    choice = program_choice(model, glm_config(), params, jnp.asarray(toks))
    limits = dict(ulps=1e9, dtype_eps=BF16_EPS, band=10.0, slack=0.0,
                  pad_to=8, logit_limit=1e-4)
    ids = [np.asarray(i) for i, _ in choice]
    base = lref.forward(params, toks, rows, arch, band=10.0, choice=ids)[0]
    r, j = 3, len(choice) - 1
    flip, _ = lref.nearest_flip(ids[j][rows[r]], choice[j][1][rows[r]],
                                arch.top_k, arch.held)
    tried = [i.copy() for i in ids]
    tried[j][rows[r]] = flip
    moved = lref.forward(params, toks, rows, arch, band=10.0,
                         choice=tried)[0][r]
    off = float(jnp.abs(moved - base[r]).max() / jnp.abs(base[r]).max())
    assert off > 100 * limits["logit_limit"]
    if row_served == "off":
        noise = np.random.default_rng(1).normal(size=moved.shape)
        moved = base[r] + off * float(jnp.abs(base[r]).max()) * noise
    program = np.asarray(base).copy()
    program[r] = np.asarray(moved)
    out = lref.check_served(params, arch, prompt, served, choice=choice,
                            program_logits=program, **limits)
    if row_served == "off":
        assert not out["ok"] and out["swapped"] == [] and out["excused"] == 0
        assert out["logit_worst_row"] == r
    else:
        assert out["ok"] and out["excused"] == 1, out["logit_error"]
        assert [s[:2] for s in out["swapped"]] == [(r, j)]
        assert out["logit_error"] <= limits["logit_limit"]
