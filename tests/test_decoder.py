"""The pattern decoder (``models/decoder.py``), the held-experts layer
(``moe/held.py``) and window layers over the paged cache, against the
plain float32 references, at a small size on the CPU with seeded
random weights: hidden 64, 4 heads / 2 KV heads of 32, window 8, block
4, vocabulary 64. Two blocks go through the one ``PatternDecoder``
(``BLOCKS``), and a test that is the same test for both is
parametrised over them:

- ``afmoe`` (``models/decoder_reference.py``): 16 experts top-2 with 4
  held, pattern dense + window, then window, window, full;
- ``mellum`` (``models/decoder_reference_mellum.py``): pre-norm, no
  gate, no embedding scale, 16 experts top-4 all held under the
  softmax router, window, window, window, full; plain rotary on the
  window layers and YaRN on the full one, trained (so the toy says) at
  16 positions, so that the scaled and the unscaled frequencies are
  both in play within 40 tokens;
- ``granite`` (``models/decoder_reference_granite.py``): pre-norm,
  mamba, mamba, full, mamba (8 heads of 16 with a state of 16, blocks
  of 8 rows in the chunked form), no QK-norm, no rotary, scores times
  1/32, multipliers 12 / 0.22 / 16, a tied head, 16 experts top-4 with
  8 held beside a shared MLP under the softmax router; beside its K/V
  (ONE layer of the pool) a sequence holds a state slot.
"""

import dataclasses
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import serving
from apex_tpu.models import decoder_reference as ref
from apex_tpu.models import decoder_reference_granite as gref
from apex_tpu.models import decoder_reference_mellum as mref
from apex_tpu.models import ssm
from apex_tpu.models.decoder import (DecoderConfig, Mamba2Config,
                                     PatternDecoder, Rotary)
from apex_tpu.moe import held as held_module
from apex_tpu.moe.held import (EXPERT_FORMS, HeldMoEConfig, HeldMoEMLP,
                               dense_experts, expert_form, held_experts,
                               sigmoid_router, softmax_router)

LAYERS = (("window", "dense"), ("window", "experts"), ("window", "experts"),
          ("window", "experts"), ("full", "experts"))
WINDOW, BLOCK, VOCAB, EXPERTS, HELD = 8, 4, 64, 16, (4, 4)
BF16_EPS = float(jnp.finfo(jnp.bfloat16).eps)


def config(dtype=jnp.float32, held=HELD, layers=LAYERS):
    return DecoderConfig(
        vocab_size=VOCAB, hidden_size=64, num_heads=4, num_kv_heads=2,
        head_dim=32, max_seq_len=128, layers=layers, ffn_hidden_size=128,
        attention_window=WINDOW, expert_ffn_size=48, num_experts=EXPERTS,
        experts_per_token=2, held_experts=held, shared_ffn_size=48,
        route_scale=2.448, dtype=dtype, param_dtype=jnp.float32)


def arch(held=HELD, layers=LAYERS):
    return ref.Arch(4, 2, 32, layers, WINDOW, top_k=2, route_scale=2.448,
                    held=held)


MELLUM_LAYERS = (("window", "experts"),) * 3 + (("full", "experts"),)
THETA = 500000.0
YARN = mref.Yarn(theta=THETA, factor=16.0, original=16, beta_fast=32.0,
                 beta_slow=1.0, attention_factor=1.2772588722239782)


def mellum_config(dtype=jnp.float32, held=None, layers=MELLUM_LAYERS,
                  full=Rotary(THETA, YARN.factor, YARN.original,
                              YARN.beta_fast, YARN.beta_slow,
                              YARN.attention_factor)):
    return DecoderConfig(
        vocab_size=VOCAB, hidden_size=64, num_heads=4, num_kv_heads=2,
        head_dim=32, max_seq_len=128, layers=layers, ffn_hidden_size=0,
        attention_window=WINDOW, expert_ffn_size=48, num_experts=EXPERTS,
        experts_per_token=4, held_experts=held, rms_eps=1e-6, dtype=dtype,
        param_dtype=jnp.float32, norms="pre", embedding_scale=False,
        output_gate=False, router="softmax",
        rotary=(("window", Rotary(THETA)), ("full", full)))


def mellum_arch(held=None, layers=MELLUM_LAYERS):
    return mref.Arch(4, 2, 32, layers, WINDOW, top_k=4, theta=THETA,
                     yarn=YARN, held=held)


GRANITE_LAYERS = (("mamba", "experts"), ("mamba", "experts"),
                  ("full", "experts"), ("mamba", "experts"))
GRANITE_HELD = (4, 8)


def granite_config(dtype=jnp.float32, held=GRANITE_HELD,
                   layers=GRANITE_LAYERS):
    return DecoderConfig(
        vocab_size=VOCAB, hidden_size=64, num_heads=4, num_kv_heads=2,
        head_dim=32, max_seq_len=128, layers=layers, ffn_hidden_size=0,
        expert_ffn_size=48, num_experts=EXPERTS, experts_per_token=4,
        held_experts=held, shared_ffn_size=48, dtype=dtype,
        param_dtype=jnp.float32, norms="pre", output_gate=False,
        router="softmax", qk_norm=False, attention_scale=1 / 32,
        embedding_scale=False, embedding_multiplier=12.0,
        residual_multiplier=0.22, logits_divisor=16.0, tied_head=True,
        mamba=Mamba2Config(num_heads=8, head_dim=16, state_size=16,
                           chunk=8))


def granite_arch(held=GRANITE_HELD, layers=GRANITE_LAYERS):
    return gref.Arch(
        4, 2, 32, tuple("mamba" if a == "mamba" else "attention"
                        for a, _ in layers),
        mamba_heads=8, mamba_head_dim=16, mamba_state=16, top_k=4,
        attention_multiplier=1 / 32, embedding_multiplier=12.0,
        residual_multiplier=0.22, logits_scaling=16.0, held=held)


# block -> (the program's configuration, the reference's, the
# reference, the name the layer leaves its scores under)
BLOCKS = {"afmoe": (config, arch, ref, "biased"),
          "mellum": (mellum_config, mellum_arch, mref, "probs"),
          "granite": (granite_config, granite_arch, gref, "probs")}
both_blocks = pytest.mark.parametrize("block", ["afmoe", "mellum"])
all_blocks = pytest.mark.parametrize("block", list(BLOCKS))


def seeded(shapes, seed=0):
    """Weights that make every part matter: gains off 1, a selection
    bias that changes choices, a router that spreads its scores."""
    def leaf(path, x):
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        key = jax.random.fold_in(jax.random.PRNGKey(seed),
                                 zlib.crc32(name.encode()) % 2**31)
        draw = jax.random.normal(key, x.shape, jnp.float32)
        if name.endswith(("scale", "mixer/norm", "mixer/D")):
            return 1.0 + 0.1 * draw
        if name.endswith("select_bias"):
            return 0.3 * draw
        # the state-space layers' own: Mamba-2's published draws (a
        # state that neither dies in a few tokens nor never forgets),
        # a convolution whose kernel and bias both matter
        if name.endswith("A_log"):
            return ssm.a_log_init(key, x.shape)
        if name.endswith("dt_bias"):
            return ssm.dt_bias_init(key, x.shape)
        if name.endswith(("conv_w", "conv_b")):
            return 0.3 * draw
        return (0.4 if name.endswith("router") else 0.06) * draw
    return jax.tree_util.tree_map_with_path(leaf, shapes)


@pytest.fixture(scope="module")
def built():
    """``block -> (model, params)``, each made once."""
    made = {}

    def get(block):
        if block not in made:
            model = PatternDecoder(BLOCKS[block][0]())
            shapes = jax.eval_shape(
                lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32)),
                jax.random.PRNGKey(0))
            made[block] = model, seeded(shapes)
        return made[block]

    return get


@pytest.fixture(scope="module")
def model_and_params(built):
    return built("afmoe")


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, VOCAB, n).astype(np.int32)


@all_blocks
def test_full_forward_matches_the_reference(built, block):
    """Float32 against float32: what is left is the order of the sums
    (2e-5 of the largest logit; 1e-6 is what it reads)."""
    model, params = built(block)
    _, make_arch, reference, _ = BLOCKS[block]
    toks = tokens(40)
    got = model.apply(params, toks[None])[:, 0]
    want, routing = reference.forward(params, toks, np.arange(40),
                                      make_arch(), row_block=16)
    assert float(jnp.abs(got - want).max()) < 2e-5 * float(
        jnp.abs(want).max())
    if block in ("afmoe", "granite"):
        # the cut really leaves experts out, and really keeps some
        assert all(0 < f["held_pairs"] < f["pairs"] for f in routing)
    else:
        # every expert is here: no pair is left out
        assert len(routing) == 4 and all(
            f["held_pairs"] == f["pairs"] == 40 * 4 for f in routing)


def _engine(model, params, cfg, **kw):
    cache = serving.KVCache.for_config(
        cfg, num_blocks=96, block_size=BLOCK,
        state_slots=4 if getattr(cfg, "mamba", None) is not None else 0)
    engine = serving.ContinuousBatcher(
        model, params, cache, max_batch=4, min_width_bucket=2,
        min_seq_bucket=4, **kw)
    return engine, cache


def _serve(engine, cache, requests):
    state = cache.init_state()
    for r in requests:
        engine.submit(r)
    done = {}
    while not engine.idle():
        state, _ = engine.step(state)
        for res in engine.drain():
            done[res.id] = res
    return done


@pytest.mark.parametrize("start,length", [(0, 8), (4, 8), (7, 3), (8, 8),
                                          (9, 8), (13, 6), (26, 8)])
@both_blocks
def test_chunk_logits_across_the_window_edge(built, block, start, length):
    """``prefill_chunk`` at every kind of start around the window's
    edge (8) and a block's edge (4): the last row's logits against the
    reference's full pass (logits, not tokens; float32 both, 2e-5 of
    the largest logit for the order of the sums)."""
    model, params = built(block)
    config, arch, ref, _ = BLOCKS[block]
    cfg = config()
    cache = serving.KVCache.for_config(cfg, num_blocks=32, block_size=BLOCK)
    step = serving.make_decode_step(model, cache)
    state = cache.init_state()
    toks = tokens(start + length, 5)
    cache.allocate("s", start + length)
    width = 16
    table = cache.table_array(["s"], width)

    def tail(position):
        ww = cache.window_width(WINDOW, width)
        return cache.window_table_array(["s"], [position], WINDOW, ww)

    if start:
        out = step.prefill_chunk(
            params, state, toks[None, :start], np.zeros(1, np.int32),
            np.array([start], np.int32), table, window=tail(0))
        state = out.cache
    pad = -length % 8
    chunk = np.pad(toks[start:], (0, pad))[None]
    out = step.prefill_chunk(
        params, state, chunk, np.array([start], np.int32),
        np.array([length], np.int32), table, window=tail(start))
    want, _ = ref.forward(params, toks, [start + length - 1], arch())
    assert float(jnp.abs(out.logits[0] - want[0]).max()) < 2e-5 * float(
        jnp.abs(want).max())


@pytest.mark.parametrize("position", [0, 3, 4, 7, 8, 9, 11, 12, 13, 30])
def test_tail_tables(position):
    """The window layers' table holds the blocks of the last ``window``
    positions at every position around a block's edge and the window's
    edge, and the trash block elsewhere."""
    cache = serving.KVCache(1, 2, 32, num_blocks=32, block_size=BLOCK)
    cache.allocate("s", 40)
    own = cache.table("s")
    width = cache.window_width(WINDOW, 16)
    assert width == 3                       # 8 positions + the edge's block
    tables, first = cache.window_table_array(
        ["s"], [position], WINDOW, width, batch=2)
    lo = max(0, position - WINDOW + 1)
    assert first[0] == lo // BLOCK
    want = own[lo // BLOCK:lo // BLOCK + width]
    assert tables[0, :len(want)].tolist() == want
    assert position // BLOCK - first[0] < width      # the token's own block
    assert (tables[0, len(want):] == 0).all() and (tables[1] == 0).all()
    assert first[1] == 0
    cache.free("s")
    assert cache.blocks_in_use == 0


def test_window_width_never_passes_the_full_table():
    cache = serving.KVCache(1, 8, 128, num_blocks=8, block_size=16)
    assert cache.window_width(4096, 1024) == 320     # 5120 positions
    assert cache.window_width(4096, 512) == 320
    assert cache.window_width(4096, 256) == 256


def test_router_chooses_by_biased_scores_and_weighs_by_plain_ones():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(6, 8)), jnp.float32)
    gate = jnp.asarray(np.random.default_rng(1).normal(size=(8, 5)),
                       jnp.float32)
    bias = jnp.asarray([0.0, 2.0, 0.0, -2.0, 0.0])
    w, ids, s = sigmoid_router(x, gate, bias, 2, route_scale=2.448)
    s, ids = np.asarray(s), np.asarray(ids)
    np.testing.assert_allclose(s, 1 / (1 + np.exp(-np.asarray(x @ gate))),
                               rtol=1e-5)
    assert (ids[:, 0] == 1).all() and (ids != 3).all()     # the bias decides
    chosen = np.take_along_axis(s, ids, 1)
    np.testing.assert_allclose(
        np.asarray(w), chosen / chosen.sum(1, keepdims=True) * 2.448,
        rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w).sum(1), 2.448, rtol=1e-5)


def test_softmax_router_is_softmax_then_top_k_then_renormalised():
    """Against one written by hand in numpy: the softmax over ALL the
    experts, the top-k of it, the chosen renormalised to 1; and it is
    not the unrenormalised thing."""
    x = np.random.default_rng(0).normal(size=(6, 8)).astype(np.float32)
    gate = np.random.default_rng(1).normal(size=(8, 12)).astype(np.float32)
    w, ids, p = (np.asarray(t) for t in softmax_router(
        jnp.asarray(x), jnp.asarray(gate), 3))
    logits = x.astype(np.float64) @ gate
    want = np.exp(logits - logits.max(1, keepdims=True))
    want /= want.sum(1, keepdims=True)
    np.testing.assert_allclose(p, want, rtol=1e-5)      # float32 rounding
    np.testing.assert_allclose(p.sum(1), 1.0, rtol=1e-6)
    order = np.argsort(-want, 1)[:, :3]
    np.testing.assert_array_equal(ids, order)
    chosen = np.take_along_axis(want, order, 1)
    np.testing.assert_allclose(w, chosen / chosen.sum(1, keepdims=True),
                               rtol=1e-5)
    assert (chosen.sum(1) < 0.95).all()    # renormalising changed them


def _spy_on_group_sizes(monkeypatch):
    """The group sizes of every grouped product (``moe_grouped``) the
    layer makes from here on."""
    sizes = []
    real = held_module.moe_grouped
    monkeypatch.setattr(
        held_module, "moe_grouped",
        lambda x, w, group_sizes: (sizes.append(np.asarray(group_sizes)),
                                   real(x, w, group_sizes))[1])
    return sizes


def _experts_by_hand(x, p, w, ids, first=0, count=EXPERTS):
    """The sum over a row's chosen experts in ``[first, first +
    count)`` of weight times expert, a pair at a time in float64."""
    x, w, ids = (np.asarray(t) for t in (x, w, ids))
    p = {k: np.asarray(v, np.float64) for k, v in p.items()}
    want = np.zeros(x.shape, np.float64)
    for row in range(len(x)):
        for weight, e in zip(w[row], ids[row] - first):
            if 0 <= e < count:
                h = x[row] @ p["w_gate"][e]
                h = h / (1 + np.exp(-h)) * (x[row] @ p["w_up"][e])
                want[row] += weight * (h @ p["w_down"][e])
    return want


# rows on each side of the rule's second edge (held.DENSE_MAX_ROWS)
both_forms = pytest.mark.parametrize("rows,form", [(40, "dense"),
                                                   (136, "grouped")])


@both_forms
def test_every_expert_held_drops_no_pair(monkeypatch, rows, form):
    """``held=None``, in both forms of the three products (the form is
    the rule's, from the rows): the grouped products' group sizes count
    every routed pair, ``tokens x top_k`` rows over all the experts,
    the dense products sort nothing, and either way the layer is the
    sum over the chosen of weight times expert, written out by hand."""
    cfg = HeldMoEConfig(hidden_size=64, expert_ffn_size=48,
                        num_experts=EXPERTS, top_k=4, router="softmax",
                        dtype=jnp.float32)
    assert expert_form(rows, 4, EXPERTS) == form
    x = jnp.asarray(np.random.default_rng(4).normal(size=(rows, 64)),
                    jnp.float32)
    params = seeded(jax.eval_shape(
        lambda k: HeldMoEMLP(cfg).init(k, x), jax.random.PRNGKey(0)), 1)
    assert "select_bias" not in params["params"]
    assert params["params"]["w_gate"].shape == (EXPERTS, 64, 48)
    sizes = _spy_on_group_sizes(monkeypatch)
    out, (w, ids, _) = HeldMoEMLP(cfg).apply(params, x, return_routing=True)
    if form == "grouped":
        assert len(sizes) == 3 and all(
            g.shape == (EXPERTS,) and g.sum() == rows * 4 for g in sizes)
    else:
        assert not sizes
    np.testing.assert_allclose(
        np.asarray(out), _experts_by_hand(x, params["params"], w, ids),
        atol=2e-5)


def test_the_softmax_routers_shares_add_up_to_the_uncut_layer():
    """64 experts top-8 in four shares of 16 (a training deployment's
    cut): the parts the shares compute add up to the layer whole, as
    the program computes it with ``held=None`` and as the reference
    does."""
    full = HeldMoEConfig(hidden_size=64, expert_ffn_size=48, num_experts=64,
                         top_k=8, router="softmax", dtype=jnp.float32)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(3, 11, 64)),
                    jnp.float32)
    whole = seeded(jax.eval_shape(
        lambda k: HeldMoEMLP(full).init(k, x), jax.random.PRNGKey(0)), 7)
    a = mref.Arch(4, 2, 32, (), WINDOW, top_k=8, theta=THETA, yarn=YARN)
    want, facts = mref._experts(x.reshape(-1, 64), whole["params"], a,
                                None, ())
    assert facts["held_pairs"] == facts["pairs"] == 33 * 8
    total = 0.0
    for first in range(0, 64, 16):
        share = dict(whole["params"])
        for name in ("w_gate", "w_up", "w_down"):
            share[name] = whole["params"][name][first:first + 16]
        cfg = HeldMoEConfig(**{**full.__dict__, "held": (first, 16)})
        part = HeldMoEMLP(cfg).apply({"params": share}, x)
        assert float(jnp.abs(part).max()) > 0
        total = total + part
    np.testing.assert_allclose(np.asarray(total).reshape(-1, 64),
                               np.asarray(want), atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(HeldMoEMLP(full).apply(whole, x)).reshape(-1, 64),
        np.asarray(want), atol=2e-5)


def test_the_shares_add_up_to_the_uncut_layer():
    """The routed parts that all ``16 / 4`` shares compute, plus the
    shared expert once, equal the uncut layer of the reference."""
    full = HeldMoEConfig(hidden_size=64, expert_ffn_size=48,
                         num_experts=EXPERTS, top_k=2, route_scale=2.448,
                         shared_ffn_size=48, dtype=jnp.float32)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(3, 11, 64)),
                    jnp.float32)
    whole = seeded(jax.eval_shape(
        lambda k: HeldMoEMLP(full).init(k, x), jax.random.PRNGKey(0)), 7)
    want, facts = ref._experts(
        x.reshape(-1, 64), whole["params"],
        ref.Arch(4, 2, 32, (), None, top_k=2, route_scale=2.448), None, ())
    assert facts["held_pairs"] == facts["pairs"]
    shared_only = dict(whole["params"])
    total = 0.0
    for first in range(0, EXPERTS, 4):
        share = dict(whole["params"])
        for name in ("w_gate", "w_up", "w_down"):
            share[name] = whole["params"][name][first:first + 4]
        for name in ("shared_gate", "shared_up", "shared_down"):
            share[name] = jnp.zeros_like(share[name])       # counted once
        cfg = HeldMoEConfig(**{**full.__dict__, "held": (first, 4)})
        total = total + HeldMoEMLP(cfg).apply({"params": share}, x)
    for name in ("w_gate", "w_up", "w_down"):
        shared_only[name] = jnp.zeros_like(shared_only[name][:1])
    cfg = HeldMoEConfig(**{**full.__dict__, "held": (0, 1)})
    total = total + HeldMoEMLP(cfg).apply({"params": shared_only}, x)
    np.testing.assert_allclose(np.asarray(total).reshape(-1, 64),
                               np.asarray(want), atol=2e-5)


@both_forms
def test_absent_pairs_are_dropped_before_the_grouped_products(
        monkeypatch, rows, form):
    """A share of the experts held, in both forms: the grouped
    products' group sizes count the held pairs only, and either form
    is the by-hand sum over the chosen experts that are held."""
    cfg = HeldMoEConfig(hidden_size=64, expert_ffn_size=48,
                        num_experts=EXPERTS, top_k=2, held=HELD,
                        dtype=jnp.float32)
    assert expert_form(rows, 2, EXPERTS) == form
    x = jnp.asarray(np.random.default_rng(4).normal(size=(rows, 64)),
                    jnp.float32)
    params = seeded(jax.eval_shape(
        lambda k: HeldMoEMLP(cfg).init(k, x), jax.random.PRNGKey(0)), 1)
    sizes = _spy_on_group_sizes(monkeypatch)
    out, (w, ids, _) = HeldMoEMLP(cfg).apply(params, x, return_routing=True)
    held = (np.asarray(ids) >= 4) & (np.asarray(ids) < 8)
    assert 0 < held.sum() < held.size
    if form == "grouped":
        assert len(sizes) == 3 and all(
            g.shape == (4,) and g.sum() == held.sum() for g in sizes)
    else:
        assert not sizes
    p = params["params"]
    np.testing.assert_allclose(
        np.asarray(out), _experts_by_hand(x, p, w, ids, *HELD), atol=2e-5)
    # an expert outside the held range changes nothing: zero its pairs'
    # weights by hand and the result is the same
    again = held_experts(x, jnp.where(held, w, 0.0), ids, p["w_gate"],
                         p["w_up"], p["w_down"], HELD, jnp.float32,
                         num_experts=EXPERTS)
    np.testing.assert_allclose(np.asarray(out), np.asarray(again), atol=1e-6)


def _routed(rows, held, dtype, seed=5, top_k=4):
    """Rows, a softmax router's choice over ``EXPERTS`` and the held
    experts' seeded weights, as ``held_experts`` takes them."""
    first, count = held
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(rows, 64)), dtype)
    w, ids, _ = softmax_router(
        x, jnp.asarray(rng.normal(size=(64, EXPERTS)), jnp.float32), top_k)
    mats = [jnp.asarray(rng.normal(size=shape) * 0.3, dtype) for shape in
            [(count, 64, 48), (count, 64, 48), (count, 48, 64)]]
    return x, w, ids, mats


@pytest.mark.parametrize("held", [(0, EXPERTS), HELD])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_two_forms_agree(held, dtype):
    """The same rows, choice and weights through both bodies: the same
    sum to float32 rounding in float32. In bfloat16 (operands rounded,
    sums in float32) the dense form, which rounds the activation and
    the result and nothing else, stays within one bf16 ulp of the
    row's largest of the by-hand sum; the grouped one rounds each
    product's result too and reads up to 1.2 there, so the two lie
    within two of each other, and the dense one is the nearer."""
    x, w, ids, mats = _routed(24, held, dtype)
    dense, grouped = (np.asarray(EXPERT_FORMS[form](
        x, w, ids, *mats, held, dtype).astype(jnp.float32))
        for form in ("dense", "grouped"))
    assert np.abs(grouped).max() > 0.1
    if dtype == jnp.float32:
        np.testing.assert_allclose(dense, grouped, atol=2e-5)
        return
    want = _experts_by_hand(
        x.astype(jnp.float32),
        dict(zip(("w_gate", "w_up", "w_down"), mats)), w, ids, *held)
    ulp = BF16_EPS * np.abs(want).max(-1, keepdims=True)
    assert (np.abs(dense - want) <= ulp).all()
    assert (np.abs(dense - grouped) <= 2 * ulp).all()
    assert np.abs(dense - want).max() < np.abs(grouped - want).max()


@pytest.mark.parametrize("poison", [np.nan, np.inf])
@pytest.mark.parametrize("leaf", [0, 1, 2])
def test_an_unchosen_experts_weights_do_not_reach_the_dense_sum(leaf,
                                                                  poison):
    """Every held expert multiplies every row, the unchosen with them:
    a NaN or an Inf in the weights of an expert that NO row chose is
    dropped by the select on the chosen mask (a zero weight would turn
    it into a NaN, and the sampler's finite flag would quarantine the
    lane); in the weights of a chosen one it is seen."""
    x, w, ids, mats = _routed(6, (0, EXPERTS), jnp.float32, top_k=2)
    chosen = np.unique(np.asarray(ids))
    unchosen = sorted(set(range(EXPERTS)) - set(chosen.tolist()))
    assert unchosen
    clean = dense_experts(x, w, ids, *mats, (0, EXPERTS), jnp.float32)

    def planted(e):
        bad = list(mats)
        bad[leaf] = bad[leaf].at[e].set(poison)
        return np.asarray(dense_experts(
            x, w, ids, *bad, (0, EXPERTS), jnp.float32))

    np.testing.assert_array_equal(planted(unchosen[0]), np.asarray(clean))
    assert not np.isfinite(planted(int(chosen[0]))).all()


@pytest.mark.parametrize("cell,rows,top_k,experts,form", [
    ("mellum2 decode", 16, 8, 64, "dense"),
    ("mellum2 least prefill", 512, 8, 64, "grouped"),
    ("granite decode", 64, 10, 72, "dense"),
    ("granite least prefill", 512, 10, 72, "grouped"),
    ("trinity decode", 16, 4, 256, "grouped"),
    ("trinity least prefill", 512, 4, 256, "grouped"),
    ("a pair an expert", 8, 8, 64, "dense"),
    ("under a pair an expert", 7, 8, 64, "grouped"),
    ("the most rows", 128, 8, 64, "dense"),
    ("past the most rows", 129, 8, 64, "grouped"),
])
def test_expert_form_at_the_cells_shapes(cell, rows, top_k, experts, form):
    """The rule at the benchmark's three cells' decode calls and least
    prefill-type calls, and on each side of its two edges."""
    assert expert_form(rows, top_k, experts) == form


@pytest.mark.parametrize("window", [None, 8])
def test_gpt_config_with_a_window_decodes(window):
    """``GPTConfig(attention_window=...)`` is served: the cached paths
    share ``models/cached_attention.py``."""
    from apex_tpu.models.gpt import GPTConfig, GPTModel

    cfg = GPTConfig(vocab_size=VOCAB, max_seq_len=64, hidden_size=64,
                    num_layers=2, num_heads=4, num_kv_heads=2,
                    attention_window=window, dtype=jnp.float32)
    model = GPTModel(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    engine, cache = _engine(model, params, cfg, prefill_chunk=8)
    requests = [serving.Request(id=i, prompt=tokens(n, 20 + i),
                                max_new_tokens=m)
                for i, (n, m) in enumerate([(5, 6), (19, 9), (30, 7)])]
    done = _serve(engine, cache, requests)
    for r in requests:
        seq = np.concatenate([r.prompt, done[r.id].tokens])
        logits = model.apply(params, seq[None, :-1])[:, 0]
        rows = np.asarray(logits[len(r.prompt) - 1:])
        gap = rows.max(-1) - rows[np.arange(len(rows)), done[r.id].tokens]
        assert float(gap.max()) < 1e-4, (r.id, gap)
    assert cache.blocks_in_use == 0
    assert (engine.gathered["window"] > 0) == (window is not None)


# -- scope tables: which part of the model a compiled instruction is ----

SCOPED_KINDS = {
    # kind -> the parts its programs hold (telemetry.compiled.PARTS)
    "gpt": {"embed", "attention", "cache", "mlp", "head"},
    "gpt-window": {"embed", "attention", "cache", "mlp", "head"},
    "afmoe": {"embed", "attention", "cache", "experts", "mlp", "head"},
    "mellum": {"embed", "attention", "cache", "experts", "head"},
    "granite": {"embed", "attention", "cache", "mixer", "experts", "mlp",
                "head"},
}
PROGRAMS = {"prefill_step": "jit_prefill_fn",
            "prefill_chunk": "jit_prefill_chunk_fn",
            "decode_step": "jit_decode_fn"}


def _scoped_model(kind, built):
    if kind in BLOCKS:
        (model, params), cfg = built(kind), BLOCKS[kind][0]()
        return model, params, cfg
    from apex_tpu.models.gpt import GPTConfig, GPTModel

    cfg = GPTConfig(vocab_size=VOCAB, max_seq_len=64, hidden_size=64,
                    num_layers=2, num_heads=4, num_kv_heads=2,
                    attention_window=8 if kind == "gpt-window" else None,
                    dtype=jnp.float32)
    model = GPTModel(cfg)
    shapes = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32)),
        jax.random.PRNGKey(0))
    return model, seeded(shapes), cfg


@pytest.fixture(scope="module")
def scoped(built):
    """``kind -> (engine, tables by program, compiles fired by the
    pull)``: a toy deck served once a kind (whole prompts, chunks of 8,
    decodes), then every registered program's scope table pulled."""
    import gc

    from apex_tpu.telemetry import compiled

    made = {}

    def get(kind):
        if kind in made:
            return made[kind]
        gc.collect()
        compiled.forget_programs()
        model, params, cfg = _scoped_model(kind, built)
        engine, cache = _engine(model, params, cfg, prefill_chunk=8)
        _serve(engine, cache, [
            serving.Request(id=i, prompt=tokens(n, 30 + i), max_new_tokens=m)
            for i, (n, m) in enumerate([(8, 6), (21, 7), (5, 4)])])
        keys = engine.step_fn.compile_keys()
        fired = []

        def listen(name, secs, **kw):
            if name == compiled.BACKEND_COMPILE_EVENT:
                fired.append(name)

        jax.monitoring.register_event_duration_secs_listener(listen)
        try:
            tables = compiled.scope_tables()
        finally:
            jax.monitoring.unregister_event_duration_listener(listen)
        by_fn = {}
        for table in tables:
            by_fn.setdefault(table["signature"]["fn"], []).append(table)
        made[kind] = engine, by_fn, len(fired), keys
        return made[kind]

    return get


@pytest.mark.parametrize("program", list(PROGRAMS))
@pytest.mark.parametrize("kind", list(SCOPED_KINDS))
def test_every_instruction_knows_its_part(scoped, kind, program):
    """After a dispatch of each of a ``DecodeStep``'s programs
    ``compiled.scope_tables()`` holds one table a program, under the
    name a trace prints; at least 95% of the instructions that do work
    and that the source named lie under a part (what is left is the
    layer scan's own counter), and 85% with the compiler's unnamed ones
    counted (the CPU's copies of a loop's carry, which nothing but the
    ``while`` consumes); every part is one of the list, and the kind's
    parts are all there (nothing the source opens is missing)."""
    from apex_tpu.telemetry import compiled

    engine, by_fn, _, keys = scoped(kind)
    tables = by_fn[program]
    assert len(tables) == keys[program] > 0
    for table in tables:
        assert table["name"] == PROGRAMS[program]
        assert table["missing_parts"] == []
        work = [name for name, opcode in table["opcodes"].items()
                if opcode not in compiled.PLUMBING]
        parts = [table["parts"].get(name) for name in work]
        assert len(work) > 50
        left = [(n, table["opcodes"][n], table["ops"][n]) for n in work
                if n not in table["parts"]]
        named = [n for n in work if table["ops"][n]]
        assert len([u for u in left if u[2]]) <= 0.05 * len(named), left
        assert len(left) <= 0.15 * len(work), left
        assert set(parts) - {None} == SCOPED_KINDS[kind]
        # one part an instruction: its own op_name's innermost, and the
        # paths of one part never read as another's
        for name, op_name in table["ops"].items():
            own = compiled.part_of(op_name)
            if own is not None:
                assert table["parts"][name] == own
        scoped_ops = [op for op in table["ops"].values()
                      if compiled.part_of(op)]
        assert all(compiled.part_of(op) == "cache" for op in scoped_ops
                   if "/kv_gather" in op or "/zero_context" in op)
        assert all(compiled.part_of(op) == "experts" for op in scoped_ops
                   if "/moe_router/" in op or "/moe_experts/" in op)
        assert all(compiled.part_of(op) == "mixer" for op in scoped_ops
                   if "/mamba_mixer/" in op)


@pytest.mark.parametrize("kind", list(SCOPED_KINDS))
def test_pulling_the_tables_compiles_nothing(scoped, kind):
    """Lowering and compiling a program that has run from its
    registered shapes is an in-memory hit: no backend compile fires,
    and the step's own compile cache is as it was."""
    engine, by_fn, fired, keys = scoped(kind)
    assert fired == 0
    assert engine.step_fn.compile_keys() == keys
    assert sum(len(t) for t in by_fn.values()) == sum(keys.values())


@pytest.mark.parametrize("block", ["mellum", "granite"])
def test_the_compiled_program_holds_the_form_the_rule_gives(built, block):
    """``expert_form`` in the compiled text, not a host tally: a decode
    call of four lanes holds ``moe_experts/dense`` and no grouped
    product, a whole-prompt call of 144 rows ``moe_experts/grouped``
    and no dense one."""
    import gc

    from apex_tpu.telemetry import compiled

    gc.collect()
    compiled.forget_programs()
    (model, params), cfg = built(block), BLOCKS[block][0]()
    engine, cache = _engine(model, params, cfg)
    step, state = engine.step_fn, cache.init_state()
    b, s = 2, 72
    assert expert_form(b * s, cfg.experts_per_token,
                       cfg.num_experts) == "grouped"
    assert expert_form(4, cfg.experts_per_token, cfg.num_experts) == "dense"
    recurrent = getattr(cfg, "mamba", None) is not None
    slots = {"slots": np.zeros(b, np.int32)} if recurrent else {}
    out = step.prefill(params, state, np.zeros((b, s), np.int32),
                       np.full(b, s, np.int32), np.zeros((b, 18), np.int32),
                       **slots)
    window = {} if recurrent else {
        "window": (np.zeros((4, 3), np.int32), np.zeros(4, np.int32))}
    slots = {"slots": np.zeros(4, np.int32)} if recurrent else {}
    step.decode(params, out.cache, np.zeros(4, np.int32),
                np.zeros(4, np.int32), np.zeros((4, 2), np.int32),
                **window, **slots)
    forms = {}
    for table in compiled.scope_tables():
        text = " ".join(table["ops"].values())
        forms[table["name"]] = {form for form in EXPERT_FORMS
                                if f"/moe_experts/{form}/" in text}
    assert forms == {"jit_prefill_fn": {"grouped"},
                     "jit_decode_fn": {"dense"}}


def test_a_dropped_decode_step_leaves_its_tables_until_the_bound(
        built, monkeypatch):
    """Whoever asks for the tables asks after the fact: a step that was
    dropped (as a benchmark's driver drops its engine before any reader
    runs) still has its programs' tables, the registry holds no more
    than ``MAX_PROGRAMS`` of them, oldest out first, and
    ``forget_programs`` empties it."""
    import gc

    from apex_tpu.telemetry import compiled

    compiled.forget_programs()
    (model, params), cfg = built("afmoe"), BLOCKS["afmoe"][0]()
    engine, cache = _engine(model, params, cfg)
    _serve(engine, cache, [serving.Request(id=0, prompt=tokens(5, 1),
                                           max_new_tokens=2)])
    n = sum(engine.step_fn.compile_keys().values())
    del engine, cache
    gc.collect()
    tables = compiled.scope_tables()
    assert len(tables) == n >= 2
    assert all(t["parts"] and not t["missing_parts"] for t in tables)
    monkeypatch.setattr(compiled, "MAX_PROGRAMS", n)
    newest = jax.jit(lambda x: x + 1)
    compiled.register_program("jit_newest", {"fn": "newest"}, newest,
                              (jnp.ones(3),))
    assert [t["name"] for t in compiled.scope_tables()] == [
        *(t["name"] for t in tables[1:]), "jit_newest"]
    compiled.forget_programs()
    assert compiled.scope_tables() == []


@both_blocks
def test_held_counts_the_blocks_behind_the_window(built, block):
    """``ContinuousBatcher.held``, one request on one lane (prompt 30,
    12 new: 11 blocks of 4 reserved at admission): at each step's end
    the live sequence's blocks times the pool's layers, and of them the
    blocks that end at or before ``t - window + 1`` (``t`` the next
    query's position) times the window layers; nothing once it ends."""
    model, params = built(block)
    cfg = BLOCKS[block][0]()
    cache = serving.KVCache.for_config(cfg, num_blocks=96, block_size=BLOCK)
    engine = serving.ContinuousBatcher(
        model, params, cache, max_batch=1, min_width_bucket=2,
        min_seq_bucket=4)
    state = cache.init_state()
    engine.submit(serving.Request(id=0, prompt=tokens(30),
                                  max_new_tokens=12))
    layers = len(cfg.layers)
    window_layers = sum(a == "window" for a, _ in cfg.layers)
    assert (layers, window_layers) == ((5, 4) if block == "afmoe"
                                       else (4, 3))
    seen = [dict(engine.held)]
    while not engine.idle():
        state, _ = engine.step(state)
        seen.append(dict(engine.held))
    steps = [{k: b[k] - a[k] for k in a} for a, b in zip(seen, seen[1:])]
    assert steps[-1] == {"block_layers": 0, "behind_window": 0}  # it ended
    live = steps[:-1]
    assert len(live) == 10 and all(
        s["block_layers"] == 11 * layers for s in live)
    # the first step prefills and decodes once: the next query then
    # stands at 31, a step later at 32, ...
    assert [s["behind_window"] for s in live] == [
        window_layers * ((t - WINDOW + 1) // BLOCK) for t in range(31, 41)]
    assert cache.blocks_in_use == 0


@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_flash_attention_takes_a_lanes_own_positions(impl):
    from apex_tpu.ops.attention import flash_attention

    rng = np.random.default_rng(0)
    b, h, hk, sq, sk, d = 3, 4, 2, 8, 24, 32
    q, k, v = (jnp.asarray(rng.normal(size=s), jnp.float32)
               for s in ((b, h, sq, d), (b, hk, sk, d), (b, hk, sk, d)))
    q_pos = jnp.asarray([[10 + i for i in range(sq)],
                         [3 + i for i in range(sq)],
                         [40 + i for i in range(sq)]], jnp.int32)
    base = jnp.asarray([2, 0, 30], jnp.int32)
    k_pos = base[:, None] + jnp.arange(sk, dtype=jnp.int32)[None]
    got = flash_attention(q, k, v, causal=True, window_size=6,
                          q_positions=q_pos, kv_positions=k_pos, impl=impl,
                          block_q=8, block_k=8)
    see = (k_pos[:, None, :] <= q_pos[:, :, None]) & (
        k_pos[:, None, :] > q_pos[:, :, None] - 6)
    s = jnp.einsum("bkgqd,bkcd->bkgqc", q.reshape(b, hk, 2, sq, d), k) \
        * d ** -0.5
    p = jax.nn.softmax(jnp.where(see[:, None, None], s, -jnp.inf), -1)
    want = jnp.einsum("bkgqc,bkcd->bkgqd", p, v).reshape(b, h, sq, d)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def _choice(model, params, toks, scores="biased"):
    """The program's own routing over ``toks``, as the benchmark's
    drivers read it: ``(ids, what they were chosen from)`` an expert
    layer."""
    _, sown = model.apply(params, toks[None], mutable=["routing"])
    return [(np.asarray(sown["routing"][f"layer_{i}"]["mlp"]["ids"][0]),
             np.asarray(sown["routing"][f"layer_{i}"]["mlp"][scores][0]))
            for i, (_, mlp) in enumerate(model.config.layers)
            if mlp == "experts"]


@pytest.fixture(scope="module")
def served(built):
    """``block ->`` four requests served through the cache, and the
    program's own routing over each one's teacher-forced tokens."""
    made = {}

    def get(block):
        if block not in made:
            model, params = built(block)
            engine, cache = _engine(model, params, BLOCKS[block][0](),
                                    prefill_chunk=8)
            requests = [serving.Request(id=i, prompt=tokens(n, 40 + i),
                                        max_new_tokens=90)
                        for i, n in enumerate([6, 11, 19, 30])]
            done = _serve(engine, cache, requests)
            made[block] = [
                (r.prompt, done[r.id].tokens, _choice(
                    model, params,
                    ref.teacher_forced(r.prompt, done[r.id].tokens, 1)[0],
                    BLOCKS[block][3]))
                for r in requests]
        return made[block]

    return get


# the program here is float32, so its choice may differ from the
# reference's by float32 rounding and no more: a band of 1e-5, nothing
# excused. (The cell serves bf16 and allows what bf16 moves.)
LIMITS = dict(ulps=4.0, band=1e-5, slack=0.0, pad_to=1, dtype_eps=BF16_EPS)


@all_blocks
def test_the_program_leaves_its_choice_only_when_asked(built, block):
    model, params = built(block)
    scores, k = BLOCKS[block][3], model.config.experts_per_token
    toks = tokens(24)
    plain = model.apply(params, toks[None])
    logits, sown = model.apply(params, toks[None], mutable=["routing"])
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(logits))
    got = sown["routing"]
    assert sorted(got) == [
        f"layer_{i}" for i, (_, mlp) in enumerate(model.config.layers)
        if mlp == "experts"]
    assert sorted(got["layer_1"]["mlp"]) == sorted(["ids", scores])
    ids, chosen_from = (got["layer_1"]["mlp"][name][0]
                        for name in ("ids", scores))
    assert ids.shape == (24, k) and chosen_from.shape == (24, EXPERTS)
    np.testing.assert_array_equal(
        np.sort(np.asarray(ids), -1),
        np.sort(np.argsort(-np.asarray(chosen_from), -1)[:, :k], -1))
    assert "routing" not in model.init(jax.random.PRNGKey(0), toks[None])


@pytest.mark.parametrize("gap,band,followed,refused", [
    (0.003, 0.005, True, False),     # a swap at the cut, within the band
    (0.003, 0.001, False, True),     # the same swap, outside it
    (0.0, 0.005, False, False)])     # the reference's own choice
def test_the_reference_follows_a_choice_within_the_band(gap, band, followed,
                                                        refused):
    """Scores 0.9, 0.7, 0.5, 0.5 - gap, 0.1, ...: top-3. A program
    that took the 4th for the 3rd is followed if the two lie within
    the band, and refused (the reference keeps its own) if not; a
    program that took the last expert is refused at any band here."""
    scores = np.full((2, 8), 0.1, np.float32)
    scores[:, :4] = [0.9, 0.7, 0.5, 0.5 - gap]
    logits = np.log(scores / (1 - scores))           # sigmoid's inverse
    a = ref.Arch(1, 1, 8, (), None, top_k=3, route_scale=1.0)
    theirs = np.array([[0, 1, 3 if gap else 2], [0, 1, 7]], np.int32)
    w, chosen, misfit = ref._route(
        jnp.asarray(logits), np.eye(8, dtype=np.float32),
        np.zeros(8, np.float32), jnp.asarray(theirs), arch=a, band=band,
        round_to=None, faults=())
    assert float(misfit[0]) == pytest.approx(gap, abs=1e-6)
    assert (0 < misfit[0] <= band, misfit[0] > band) == (followed, refused)
    assert sorted(np.asarray(chosen[0])) == (
        [0, 1, 3] if followed else [0, 1, 2])
    assert float(misfit[1]) == pytest.approx(0.4, abs=1e-6)
    assert sorted(np.asarray(chosen[1])) == [0, 1, 2]
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, rtol=1e-6)


def test_held_margin_watches_the_held_experts_only():
    biased = np.array([[0.9, 0.8, 0.5, 0.49, 0.3, 0.1]], np.float32)
    # top-3; expert 3 (held) would come in at 0.01; expert 1 (held)
    # would drop out at 0.8 - 0.49
    assert ref.held_margin(biased, 3, (3, 1)) == pytest.approx(0.01)
    assert ref.held_margin(biased, 3, (1, 1)) == pytest.approx(0.31)
    assert ref.held_margin(biased, 3, (4, 2)) == pytest.approx(0.2)
    assert ref.held_margin(biased, 3, (0, 6)) == pytest.approx(0.01)


@all_blocks
def test_what_was_served_passes_given_the_programs_choice(served, built,
                                                          block):
    _, params = built(block)
    _, arch, ref, _ = BLOCKS[block]
    for prompt, toks, choice in served(block):
        out = ref.check_served(params, arch(), prompt, toks, choice=choice,
                               **LIMITS)
        assert out["ok"] and out["exact"] == out["rows"] == 90, out
        assert out["refused"] == out["excused"] == out["may_differ"] == 0, out


@pytest.mark.parametrize("margins,ok,excused", [
    ([0.1, 0.1, 0.1, 0.1], False, 0),     # a row trails, nothing explains it
    ([0.1, 0.1, 1e-4, 0.1], True, 1),     # the program's own cut is close
    ([1e-4, 1e-4, 0.1, 1e-4], False, 0)])  # close elsewhere: no excuse
def test_a_row_is_excused_only_if_it_trails_and_may_differ(margins, ok,
                                                           excused):
    logits = np.zeros((4, 8), np.float32)
    logits[:, 0] = 1.0
    logits[2, 1] = 1.05                   # row 2 serves token 0, 0.05 behind
    out = ref.judge(logits, np.zeros(4, int), np.asarray(margins) < 1e-3,
                    ulps=4.0, dtype_eps=BF16_EPS)
    assert (out["ok"], out["excused"]) == (ok, excused)
    assert out["exact"] == 3 and out["rows"] == 4


@pytest.mark.parametrize("block,fault", [
    ("afmoe", f) for f in (
        "window_edge", "rope_on_full", "weight_by_biased", "bf16_router",
        "choice_without_bias", "fp8_everywhere")] + [
    ("mellum", f) for f in (
        "plain_rope_on_full", "yarn_on_window", "no_attention_factor",
        "no_renormalisation", "window_edge", "bf16_router",
        "fp8_everywhere")])
def test_the_comparison_refuses_a_wrong_model(built, served, block, fault):
    """A reference that is wrong in one way against what the true
    program served, at the cells' 4 bf16 ulps and given the program's
    choice: every request is refused. ``afmoe``: a window one key too
    wide, rotary positions on the full layer, weights from ``s + b``,
    the choice made without ``b``, the router's product on bf16 inputs,
    everything in fp8. ``mellum``: the two rotary schemes each on the
    other kind of layer (plain on the full layer, YaRN on the window
    layers), YaRN without its ``attention_factor``, a top-4 that is
    not renormalised, and the window, the bf16 router and fp8 as
    above. (The router's product on bf16 inputs moves a score by 1e-3
    or so, which at 16 experts and 64 wide changes a choice in a row
    or two of a hundred: the four requests are refused as the one run
    they are, by those of them that meet such a row.)"""
    _, params = built(block)
    _, arch, ref, _ = BLOCKS[block]
    wrong = dict(round_to=jnp.float8_e4m3fn) if fault == "fp8_everywhere" \
        else dict(faults=(fault,))
    verdicts = [ref.check_served(params, arch(), prompt, toks, choice=choice,
                                 **LIMITS, **wrong)
                for prompt, toks, choice in served(block)]
    passed = [v["ok"] for v in verdicts]
    assert not (all(passed) if fault == "bf16_router" else any(passed)), [
        (v["worst_ulps"], v["refused"]) for v in verdicts]


# -- the state-space layers: a recurrent state beside the paged K/V --------

def _random_scan_inputs(b=2, s=24, H=4, P=8, N=8, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731,E501
    dt = jax.nn.softplus(f(b, s, H) - 2.0)
    return dict(S=0.5 * f(b, H, P, N), x=f(b, s, H, P), dt=dt,
                A=-jnp.exp(f(H)), B=f(b, s, N), C=f(b, s, N),
                D=1.0 + 0.1 * f(H))


@pytest.mark.parametrize("chunk", [4, 8, 32])
def test_the_chunked_form_is_the_step_form_is_the_scan(chunk):
    """One recurrence three ways, from a state that is not zero: the
    chunked form (blocks of 4 and 8 rows of 24, and one block wider
    than the rows), the one-step form a token at a time, and a plain
    scan over tokens written out here. Float32 all: 1e-5 is the order
    of the sums (the chunked form adds a block's products at once)."""
    t = _random_scan_inputs()
    y_chunked, S_chunked = ssm.ssm_scan(
        t["S"], t["x"], t["dt"], t["A"], t["B"], t["C"], t["D"],
        chunk=chunk, dtype=jnp.float32)
    S, ys = t["S"], []
    for i in range(t["x"].shape[1]):
        y, S = ssm.ssm_step(S, t["x"][:, i], t["dt"][:, i], t["A"],
                            t["B"][:, i], t["C"][:, i], t["D"])
        ys.append(y)

    def token(S, r):
        x, dt, B, C = r
        S = (jnp.exp(dt * t["A"])[:, :, None, None] * S
             + jnp.einsum("bh,bhp,bn->bhpn", dt, x, B))
        return S, jnp.einsum("bhpn,bn->bhp", S, C) + t["D"][:, None] * x

    S_plain, y_plain = jax.lax.scan(token, t["S"], tuple(
        jnp.moveaxis(t[k], 1, 0) for k in ("x", "dt", "B", "C")))
    for got in (y_chunked, jnp.stack(ys, 1)):
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(jnp.moveaxis(y_plain, 0, 1)),
                                   atol=1e-5, rtol=1e-5)
    for got in (S_chunked, S):
        np.testing.assert_allclose(np.asarray(got), np.asarray(S_plain),
                                   atol=1e-5, rtol=1e-5)


def test_pad_rows_stand_still_and_the_convolution_keeps_real_rows():
    """A chunk of 16 rows of which a lane has 5 and 11 real (``d_t`` 0
    on the pads): the state after it is the state after the real rows
    alone, and the rows the convolution carries on are the last three
    REAL rows, whatever the pads held."""
    t = _random_scan_inputs(s=16)
    lengths = jnp.array([5, 11])
    real = jnp.arange(16)[None, :] < lengths[:, None]
    dt = jnp.where(real[..., None], t["dt"], 0.0)
    _, S_padded = ssm.ssm_scan(t["S"], t["x"], dt, t["A"], t["B"], t["C"],
                               t["D"], chunk=8, dtype=jnp.float32)
    for lane, n in enumerate([5, 11]):
        _, S_real = ssm.ssm_scan(*(
            t[k][lane:lane + 1, :n] if k in ("x", "dt", "B", "C")
            else (t[k][lane:lane + 1] if k == "S" else t[k])
            for k in ("S", "x", "dt", "A", "B", "C", "D")),
            chunk=8, dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(S_padded[lane]),
                                   np.asarray(S_real[0]), atol=1e-6)
    rng = np.random.default_rng(1)
    rows = jnp.asarray(rng.normal(size=(2, 3, 6)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(2, 16, 6)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(6, 4)), jnp.float32)
    out, carried = ssm.conv_rows(rows, c, w, jnp.zeros(6), lengths)
    np.testing.assert_array_equal(np.asarray(carried[0]),
                                  np.asarray(c[0, 2:5]))
    np.testing.assert_array_equal(np.asarray(carried[1]),
                                  np.asarray(c[1, 8:11]))
    # a lane with no real row (a dummy) keeps what it carried
    _, kept = ssm.conv_rows(rows, c, w, jnp.zeros(6), jnp.array([0, 2]))
    np.testing.assert_array_equal(np.asarray(kept[0]), np.asarray(rows[0]))
    np.testing.assert_array_equal(
        np.asarray(kept[1]),
        np.asarray(jnp.concatenate([rows[1, 2:], c[1, :2]])))
    # and the one-step form is the chunked form's row
    step, _ = ssm.conv_step(rows, c[:, 0], w, jnp.zeros(6))
    np.testing.assert_allclose(np.asarray(step), np.asarray(out[:, 0]),
                               rtol=1e-6, atol=1e-6)


class _WrongProgram:
    """The model with its lanes' state handed over wrongly: every row
    of a chunk taken for real (``pads_advance``), or no lane ever
    restarted from zeros (``slot_not_restarted``)."""

    def __init__(self, model, how):
        self.model, self.how, self.config = model, how, model.config

    def apply(self, params, tokens, **kw):
        pools, slots, lengths, fresh = kw["state_ctx"]
        if self.how == "pads_advance":
            lengths = None
        if self.how == "slot_not_restarted":
            fresh = jnp.zeros_like(fresh)
        return self.model.apply(params, tokens, **{
            **kw, "state_ctx": (pools, slots, lengths, fresh)})


def _through_the_slots(model, params, cfg, sequences, *, wrong=None):
    """Two sequences through ``DecodeStep`` as the engine drives it,
    logits kept: ``a`` is prefilled whole (13 of 16 rows real), ``b``
    in chunks of unequal real lengths with pad rows (7 of 8, 8 of 8, 4
    of 8), both then decode four steps in one batch and SWAP LANES
    between steps; ``a`` ends, and ``c`` takes its slot as ``a`` left
    it, prefilled in one padded chunk beside ``b``'s decode. Returns
    ``{name: (positions, logits)}``: every row whose logits the
    programs gave, against the position in the sequence it stands at."""
    cache = serving.KVCache.for_config(cfg, num_blocks=64, block_size=BLOCK,
                                       state_slots=2)
    step = serving.make_decode_step(
        model if wrong is None else _WrongProgram(model, wrong), cache)
    state = cache.init_state()
    a, b, c = (np.asarray(sequences[k]) for k in "abc")
    got = {k: ([], []) for k in "abc"}
    width = 8

    def keep(name, position, logits):
        got[name][0].append(position)
        got[name][1].append(np.asarray(logits))

    def lanes(names):
        return dict(tables=cache.table_array(names, width),
                    slots=cache.slot_array(names))

    cache.allocate("a", len(a))
    cache.allocate("b", len(b))
    assert list(cache.slot_array(["a", "b"])) == [0, 1]
    out = step.prefill(params, state, np.pad(a[:13], (0, 3))[None],
                       np.array([13], np.int32), **lanes(["a"]))
    keep("a", 12, out.logits[0])
    state = out.cache
    at = 0
    for n in (7, 8, 4):
        out = step.prefill_chunk(
            params, state, np.pad(b[at:at + n], (0, 8 - n))[None],
            np.array([at], np.int32), np.array([n], np.int32),
            **lanes(["b"]))
        at += n
        keep("b", at - 1, out.logits[0])
        state = out.cache
    pos = {"a": 13, "b": 19}
    seqs = {"a": a, "b": b, "c": c}
    for i in range(4):
        order = ["a", "b"] if i % 2 == 0 else ["b", "a"]    # lanes swap
        out = step.decode(
            params, state, np.array([seqs[k][pos[k]] for k in order]),
            np.array([pos[k] for k in order], np.int32), **lanes(order))
        for lane, k in enumerate(order):
            keep(k, pos[k], out.logits[lane])
            pos[k] += 1
        state = out.cache
    cache.free("a")
    cache.allocate("c", len(c))
    assert int(cache.slot_array(["c"])[0]) == 0             # a's slot
    out = step.prefill_chunk(
        params, state, np.pad(c[:6], (0, 2))[None], np.zeros(1, np.int32),
        np.array([6], np.int32), **lanes(["c"]))
    keep("c", 5, out.logits[0])
    state = out.cache
    pos["c"] = 6
    for i in range(3):
        order = ["b", "c"] if i % 2 == 0 else ["c", "b"]
        out = step.decode(
            params, state, np.array([seqs[k][pos[k]] for k in order]),
            np.array([pos[k] for k in order], np.int32), **lanes(order))
        for lane, k in enumerate(order):
            keep(k, pos[k], out.logits[lane])
            pos[k] += 1
        state = out.cache
    return {k: (np.asarray(p), np.stack(l)) for k, (p, l) in got.items()}


SEQUENCES = {"a": tokens(17, 61), "b": tokens(26, 62), "c": tokens(9, 63)}
# float32 against float32: what is left is the order of the sums (the
# chunked form against a scan over tokens). 2e-6 of the largest logit:
# the true program reads 2.5e-7; the nearest wrong model is a state
# carried in bfloat16 over these 26 tokens, 7.3e-6; every other one
# reads 2e-3 or more
SLOT_LIMIT = 2e-6


def _worst_logit_error(params, got, arch, **wrong):
    worst = 0.0
    for name, (positions, logits) in got.items():
        want, _ = gref.forward(params, SEQUENCES[name], positions, arch,
                               **wrong)
        worst = max(worst, float(np.abs(logits - np.asarray(want)).max()
                                 / np.abs(np.asarray(want)).max()))
    return worst


@pytest.fixture(scope="module")
def through_the_slots(built):
    model, params = built("granite")
    return _through_the_slots(model, params, granite_config(), SEQUENCES)


def test_prefill_chunks_and_decode_through_the_slots(built,
                                                     through_the_slots):
    """Logits, not tokens: a whole-prompt prefill with pad rows, chunks
    of unequal real lengths with pad rows, decode with the two lanes
    swapped between steps, and a slot taken over by a new sequence as
    its first owner left it, each row against the reference's full
    pass over its sequence."""
    _, params = built("granite")
    assert {k: len(p) for k, (p, _) in through_the_slots.items()} == {
        "a": 5, "b": 10, "c": 4}
    assert _worst_logit_error(params, through_the_slots,
                              granite_arch()) < SLOT_LIMIT


@pytest.mark.parametrize("fault", list(gref.FAULTS) + [
    "fp8_everywhere", "pads_advance", "slot_not_restarted"])
def test_the_logits_refuse_a_wrong_granite(built, through_the_slots, fault):
    """A model that is wrong in one way reads over twice the limit the
    true one passes by a factor of eight. Wrong references against what the true
    program gave: the gate after the norm, no ``dt_bias``, no
    convolution bias, scores scaled by ``head_dim^-0.5`` (32^-0.5, not
    1/32), residuals added whole, the state carried in bfloat16,
    everything in fp8. Wrong programs against the true reference: pad
    rows that advance the state, a reused slot that is not restarted."""
    model, params = built("granite")
    if fault in ("pads_advance", "slot_not_restarted"):
        got = _through_the_slots(model, params, granite_config(), SEQUENCES,
                                 wrong=fault)
        error = _worst_logit_error(params, got, granite_arch())
    else:
        wrong = dict(round_to=jnp.float8_e4m3fn) \
            if fault == "fp8_everywhere" else dict(faults=(fault,))
        error = _worst_logit_error(params, through_the_slots,
                                   granite_arch(), **wrong)
    assert error > 2 * SLOT_LIMIT, error


def test_a_split_dispatch_advances_every_state_once(built, monkeypatch):
    """``_isolate``'s binary split after a fault at the decode site:
    the site raises BEFORE the jitted call, so the failed dispatch
    advanced no state, and the halves' retries advance each lane's
    once: the served tokens are those of a run with no fault. (A
    replayed dispatch would advance a state twice, and the tokens
    would differ.)"""
    from apex_tpu.resilience import faults

    model, params = built("granite")
    cfg = granite_config()
    requests = lambda: [  # noqa: E731
        serving.Request(id=i, prompt=tokens(n, 70 + i), max_new_tokens=8)
        for i, n in enumerate([6, 11, 9, 14])]
    engine, cache = _engine(model, params, cfg, prefill_chunk=8)
    plain = _serve(engine, cache, requests())
    calls = {"n": 0}
    check = faults.check

    def failing(site):
        if site == "decode_step":
            calls["n"] += 1
            if calls["n"] in (2, 5):         # two top-level dispatches
                raise faults.FaultError("injected: decode_step")
        return check(site)

    monkeypatch.setattr(faults, "check", failing)
    engine, cache = _engine(model, params, cfg, prefill_chunk=8)
    split = _serve(engine, cache, requests())
    assert calls["n"] > 8
    for i in range(4):
        assert split[i].finish_reason == "length"
        assert split[i].tokens == plain[i].tokens, i
    assert cache.slots_in_use == 0 and cache.blocks_in_use == 0


def test_a_recurrent_model_matches_no_prefix_and_holds_a_slot(built):
    """The stated rule: a prompt of a model with recurrent layers takes
    no prefix match and publishes nothing, though a second request
    repeats the first one's prompt; each live sequence holds one slot;
    ``held`` counts slots, their bytes and the K/V blocks' bytes at the
    steps' ends; the slots come back at the end."""
    model, params = built("granite")
    cfg = granite_config()
    engine, cache = _engine(model, params, cfg, prefill_chunk=8)
    assert cache.state_slots == 4 and cache.num_layers == 1
    state = cache.init_state()
    assert [p.shape for p in state.state] == [(5, 3, 8, 16, 16),
                                              (5, 3, 3 * 160)]
    prompt = tokens(21, 80)
    engine.submit(serving.Request(id=0, prompt=prompt, max_new_tokens=4))
    for _ in range(2):
        state, _ = engine.step(state)
    engine.submit(serving.Request(id=1, prompt=prompt, max_new_tokens=4))
    seen = []
    while not engine.idle():
        state, report = engine.step(state)
        seen.append((cache.slots_in_use, dict(engine.held)))
    assert cache.prefix_stats()["hits"] == 0
    assert cache.prefix_stats()["published_blocks"] == 0
    assert max(n for n, _ in seen) == 2 and cache.slots_in_use == 0
    held = engine.held
    slot = 3 * (8 * 16 * 16 + 3 * 160) * 4          # float32, three layers
    assert cache.slot_bytes() == slot
    assert held["state_bytes"] == held["state_slots"] * slot > 0
    assert held["kv_bytes"] == (held["block_layers"]
                                * cache.block_bytes()) > 0
    done = {r.id: r for r in engine.drain()}
    assert done[0].tokens == done[1].tokens


def test_a_plain_models_state_has_no_new_leaf(built):
    """A model all of whose layers have keys: the cache's state is the
    two pools and nothing else (no leaf: its programs are unchanged),
    its packed layouts have no slot field, the engine counts no slot."""
    model, params = built("mellum")
    engine, cache = _engine(model, params, mellum_config())
    state = cache.init_state()
    assert state.state is None and len(jax.tree.leaves(state)) == 2
    assert cache.state_slots == 0 and cache.slot_array(["x"][:0]).size == 0
    assert set(engine.held) == {"block_layers", "behind_window"}
    from apex_tpu.serving.decode import packed_layout

    for fn in ("decode_step", "prefill_step", "prefill_chunk"):
        plain = [n for n, _ in packed_layout(fn, 4, 8, 16, 5)]
        slotted = [n for n, _ in packed_layout(fn, 4, 8, 16, 5, True)]
        assert "state_slots" not in plain
        assert [n for n in slotted if n != "state_slots"] == plain
    with pytest.raises(ValueError, match="go together"):
        serving.KVCache(1, 2, 32, num_blocks=4, state_slots=2)


def test_one_setting_says_what_scales_the_embedding():
    """``embedding_scale`` (sqrt(hidden)) and ``embedding_multiplier``
    are one decision: a configuration that gives both is refused, not
    read as the multiplier; and the mixer's sizes are all that
    ``Mamba2Config`` holds (the model's width, eps and types are the
    model's)."""
    cfg = granite_config()
    assert not cfg.embedding_scale and cfg.embedding_multiplier == 12.0
    with pytest.raises(ValueError, match="give one"):
        dataclasses.replace(cfg, embedding_scale=True)
    assert {f.name for f in dataclasses.fields(Mamba2Config)} == {
        "num_heads", "head_dim", "state_size", "conv_width", "chunk"}


def test_the_nine_expert_shares_add_up_to_the_uncut_layer():
    """72 experts top-10 in eight shares of nine (the cell's cut of
    ``granite-4.0-h-small``'s layer): the routed parts the shares
    compute, plus the shared MLP counted ONCE, equal the uncut layer as
    the reference computes it and as the program does with
    ``held=None``."""
    full = HeldMoEConfig(hidden_size=32, expert_ffn_size=8, num_experts=72,
                         top_k=10, router="softmax", shared_ffn_size=16,
                         dtype=jnp.float32)
    x = jnp.asarray(np.random.default_rng(3).normal(size=(2, 13, 32)),
                    jnp.float32)
    whole = seeded(jax.eval_shape(
        lambda k: HeldMoEMLP(full).init(k, x), jax.random.PRNGKey(0)), 9)
    a = granite_arch(held=None)._replace(top_k=10)
    want, facts = gref._experts(x.reshape(-1, 32), whole["params"], a, None)
    assert facts["held_pairs"] == facts["pairs"] == 26 * 10
    total = 0.0
    for first in range(0, 72, 9):
        share = dict(whole["params"])
        for name in ("w_gate", "w_up", "w_down"):
            share[name] = whole["params"][name][first:first + 9]
        if first:                                # the shared MLP once
            for name in ("shared_gate", "shared_up", "shared_down"):
                share[name] = jnp.zeros_like(share[name])
        cfg = HeldMoEConfig(**{**full.__dict__, "held": (first, 9)})
        part = HeldMoEMLP(cfg).apply({"params": share}, x)
        assert float(jnp.abs(part).max()) > 0
        total = total + part
    np.testing.assert_allclose(np.asarray(total).reshape(-1, 32),
                               np.asarray(want), atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(HeldMoEMLP(full).apply(whole, x)).reshape(-1, 32),
        np.asarray(want), atol=2e-5)


def test_the_step_kernel_updates_the_slots_where_they_lie():
    """``ops/ssm_step.py`` interpreted against the gather, update and
    scatter it replaces, at a size the kernel takes (32 heads of 8 with
    a state of 128): four lanes over six slots and two layers, a fresh
    lane (from zeros, though its slot holds NaN), two dummies that
    share the trash slot; only the named slots of the named layer
    change. Float32 both: the same products, a sum in another order."""
    from apex_tpu.ops.ssm_step import ssm_step_by_slot

    rng = np.random.default_rng(4)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731,E501
    pool = f(6, 2, 32, 8, 128).at[3, 1].set(jnp.nan)
    slots = jnp.array([2, 3, 5, 5])
    fresh = jnp.array([False, True, False, False])
    args = (f(4, 32, 8), jax.nn.softplus(f(4, 32)), -jnp.exp(f(32)),
            f(4, 128), f(4, 128), 1.0 + 0.1 * f(32))
    want_y, want = ssm_step_by_slot(pool, slots, fresh, 1, *args, impl="xla")
    got_y, got = ssm_step_by_slot(pool, slots, fresh, 1, *args,
                                  impl="interpret")
    np.testing.assert_allclose(np.asarray(got_y[:3]), np.asarray(want_y[:3]),
                               rtol=1e-5, atol=1e-5)
    for slot in (2, 3):
        np.testing.assert_allclose(np.asarray(got[slot, 1]),
                                   np.asarray(want[slot, 1]), rtol=1e-6,
                                   atol=1e-6)
    untouched = np.ones((6, 2), bool)
    untouched[[2, 3, 5], 1] = False
    np.testing.assert_array_equal(np.asarray(got)[untouched],
                                  np.asarray(pool)[untouched])
    assert np.isfinite(np.asarray(got[3, 1])).all()
