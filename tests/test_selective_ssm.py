"""Mamba-1's selective scan (``models/ssm.py`` ``SelectiveMixer``), its
two kernels (``ops/ssm_scan.py``, ``ops/ssm_step.py``
``selective_step_by_slot``) and the ``jamba`` block it serves in
(``models/decoder.py`` ``mamba1`` layers), on the CPU at small sizes,
against the float32 reference (``models/decoder_reference_jamba.py``)
on seeded random weights.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import decoder_reference_jamba as jref
from apex_tpu.models import ssm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _toy_config():
    with open(os.path.join(ROOT, "benchmark", "tests", "rehearsal",
                           "configs", "toy-jamba.json")) as f:
        return json.load(f)


def _recurrence(S, x, delta, A, B, C, D, z):
    """The recurrence written out in numpy, a lane, a row and a channel
    at a time, on the state as ``(E, N)``."""
    S, x, delta, A, B, C, D, z = (np.asarray(t, np.float64)
                                  for t in (S, x, delta, A, B, C, D, z))
    b, s, E = x.shape
    y = np.zeros((b, s, E))
    for i in range(b):
        for t in range(s):
            S[i] = (np.exp(delta[i, t][:, None] * A) * S[i]
                    + (delta[i, t] * x[i, t])[:, None] * B[i, t][None, :])
            y[i, t] = S[i] @ C[i, t] + D * x[i, t]
    return y * z / (1 + np.exp(-z)), S


def _inputs(key, b, s, E, N=16):
    k = jax.random.split(key, 8)
    S = jax.random.normal(k[0], (b, E // 128, N, 128))
    x = jax.random.normal(k[1], (b, s, E))
    delta = jax.nn.softplus(jax.random.normal(k[2], (b, s, E)) - 2.0)
    A = -jnp.exp(jax.random.normal(k[3], (E, N)))
    B = jax.random.normal(k[4], (b, s, N))
    C = jax.random.normal(k[5], (b, s, N))
    D = jax.random.normal(k[6], (E,))
    z = jax.random.normal(k[7], (b, s, E))
    return S, x, delta, A, B, C, D, z


def _as_rows(S):          # (b, E/128, N, 128) -> (b, E, N)
    b, G, N, L = S.shape
    return np.asarray(S).transpose(0, 1, 3, 2).reshape(b, G * L, N)


def test_the_scan_is_the_recurrence():
    S, x, delta, A, B, C, D, z = _inputs(jax.random.PRNGKey(0), 2, 9, 256)
    y, S_end = ssm.selective_scan(S, x, delta, A, B, C, D, z)
    want_y, want_S = _recurrence(_as_rows(S), x, delta, A, B, C, D, z)
    np.testing.assert_allclose(y, want_y, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(_as_rows(S_end), want_S, rtol=2e-5,
                               atol=2e-5)


@pytest.mark.parametrize("b,s,E", [(1, 16, 128), (2, 40, 256),
                                   (1, 300, 512)])
def test_scan_kernel_is_the_jnp_scan(b, s, E):
    """The Pallas scan, interpreted, against ``selective_scan``: rows not
    a multiple of the kernel's sixteen, several row blocks and channel
    blocks, a lane whose last rows are pads (Delta 0: it stands
    still)."""
    from apex_tpu.ops.ssm_scan import ssm_scan

    S, x, delta, A, B, C, D, z = _inputs(jax.random.PRNGKey(s), b, s, E)
    delta = delta.at[b - 1, s // 2:].set(0.0)
    z = z.astype(jnp.bfloat16)
    want_y, want_S = ssm_scan(S, x, delta, A, B, C, D, z, impl="xla")
    y, S_end = ssm_scan(S, x, delta, A, B, C, D, z, impl="interpret")
    assert y.dtype == jnp.bfloat16 and y.shape == (b, s, E)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(want_y, np.float32), rtol=1e-2,
                               atol=1e-2)
    np.testing.assert_allclose(S_end, want_S, rtol=1e-5, atol=1e-5)
    # the pads stood still: the lane's state is the one after its last
    # real row
    half, _ = ssm_scan(S[b - 1:], x[b - 1:, :s // 2], delta[b - 1:, :s // 2],
                       A, B[b - 1:, :s // 2], C[b - 1:, :s // 2], D,
                       z[b - 1:, :s // 2], impl="xla")
    _, S_half = ssm.selective_scan(S[b - 1:], x[b - 1:, :s // 2],
                                   delta[b - 1:, :s // 2], A,
                                   B[b - 1:, :s // 2], C[b - 1:, :s // 2], D,
                                   z[b - 1:, :s // 2])
    np.testing.assert_allclose(S_end[b - 1:], S_half, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_step_by_slot_walks_the_slots(impl):
    """One token on the lanes' states where they lie: a lane's slot is
    advanced and no other; a ``fresh`` lane over a slot holding NaN
    starts from zeros; dummy lanes all name the trash slot."""
    from apex_tpu.ops.ssm_step import selective_step_by_slot

    E, N = 256, 16
    k = jax.random.split(jax.random.PRNGKey(3), 6)
    pool = jax.random.normal(k[0], (6, 3, E // 128, N, 128))
    pool = pool.at[2].set(jnp.nan)
    slots = jnp.array([2, 4, 0, 5, 5])
    fresh = jnp.array([True, False, False, False, False])
    x = jax.random.normal(k[1], (5, E))
    delta = jax.nn.softplus(jax.random.normal(k[2], (5, E)))
    A = -jnp.exp(jax.random.normal(k[3], (E, N)))
    B = jax.random.normal(k[4], (5, N))
    C = jax.random.normal(k[5], (5, N))
    y, out = selective_step_by_slot(pool, slots, fresh, 1, x, delta, A, B,
                                    C, impl=impl)
    start = pool[slots[:3], 1].at[0].set(0.0)
    want_y, want_S = ssm.selective_step(start, x[:3], delta[:3], A, B[:3],
                                        C[:3])
    np.testing.assert_allclose(y[:3], want_y, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(out[slots[:3], 1], want_S, rtol=1e-5,
                               atol=1e-5)
    assert bool(jnp.isfinite(out[2, 1]).all())
    # the other layers of the slots, and the slots no lane names, as
    # they were
    for slot in (1, 3):
        np.testing.assert_array_equal(out[slot], pool[slot])
    for slot in (0, 4):
        np.testing.assert_array_equal(out[slot, 0], pool[slot, 0])
        np.testing.assert_array_equal(out[slot, 2], pool[slot, 2])


def _mixer(E=128, N=16, R=8, hidden=64):
    cfg = ssm.SelectiveSSMConfig(inner=E, state_size=N, dt_rank=R)
    mixer = ssm.SelectiveMixer(cfg, hidden_size=hidden, dtype=jnp.float32,
                               param_dtype=jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(5), (2, 24, hidden))
    params = mixer.init(jax.random.PRNGKey(6), u[:, :4])
    return cfg, mixer, params, u


def _pools(cfg, slots):
    return tuple(jnp.zeros((slots + 1, 1, *shape), dtype)
                 for shape, dtype in cfg.state_shapes(jnp.float32))


@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_chunks_then_steps_are_one_pass(impl):
    """The mixer over 24 tokens at once, against chunks of 10 and 6 (the
    second lane's last chunk padded: its pads stand still) handed on
    through the state slots, then one token at a time."""
    cfg, mixer, params, u = _mixer()
    whole, _ = mixer.apply(params, u)
    slots, pools = jnp.array([1, 0]), _pools(cfg, 2)

    def call(part, fresh, lengths=None):
        nonlocal pools
        m = ssm.SelectiveMixer(cfg, hidden_size=64, dtype=jnp.float32,
                               param_dtype=jnp.float32, impl=impl)
        out, pools = m.apply(params, part,
                             state_ctx=(pools, 0, slots, fresh),
                             lengths=lengths)
        return out

    outs = [call(u[:, :10], jnp.array([True, True]))]
    # a chunk of 8 rows a lane: lane 1 has 6 real rows and 2 pads
    outs.append(call(jnp.concatenate([u[:, 10:16],
                                      jnp.zeros((2, 2, 64))], axis=1),
                     jnp.array([False, False]),
                     lengths=jnp.array([6, 6]))[:, :6])
    for t in range(16, 24):
        outs.append(call(u[:, t:t + 1], jnp.array([False, False])))
    np.testing.assert_allclose(jnp.concatenate(outs, axis=1), whole,
                               rtol=2e-4, atol=2e-5)


def test_layer_pattern_of_the_cell():
    """``attn_layer_period`` 14 and ``attn_layer_offset`` 7: attention
    at layers 7 and 21 and nowhere else; the other 26 are ``mamba1``,
    whose state a slot holds with the channels minor-most."""
    from benchmark.drivers import serve_jamba

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "ai21-jamba2-3b.json")) as f:
        config = json.load(f)
    cfg = serve_jamba.decoder_config(config)
    assert [i for i, (a, _) in enumerate(cfg.layers) if a == "full"] == [
        7, 21]
    assert {a for a, _ in cfg.layers} == {"full", "mamba1"}
    assert {m for _, m in cfg.layers} == {"dense"}
    assert cfg.kv_layers == (7, 21) and len(cfg.state_layers) == 26
    assert (cfg.num_kv_heads, cfg.head_dim) == (1, 128)
    assert cfg.state_shapes() == (((26, 40, 16, 128), jnp.float32),
                                  ((26, 3 * 5120), jnp.bfloat16))
    assert cfg.rotary_of("full") is None and not cfg.qk_norm
    assert cfg.attention_scale is None              # 128^-0.5


def test_one_model_has_one_kind_of_state():
    from apex_tpu.models.decoder import DecoderConfig, Mamba2Config

    sel = ssm.SelectiveSSMConfig(inner=128, state_size=16, dt_rank=8)
    base = dict(vocab_size=64, hidden_size=64, num_heads=2, num_kv_heads=1,
                head_dim=32, max_seq_len=64, ffn_hidden_size=32)
    with pytest.raises(ValueError, match="selective is set exactly"):
        DecoderConfig(layers=(("mamba1", "dense"), ("full", "dense")),
                      **base)
    with pytest.raises(ValueError, match="one kind of state"):
        DecoderConfig(layers=(("mamba1", "dense"), ("mamba", "dense")),
                      selective=sel,
                      mamba=Mamba2Config(num_heads=2, head_dim=32,
                                         state_size=16), **base)
    with pytest.raises(ValueError, match="groups of 128"):
        ssm.SelectiveSSMConfig(inner=96, state_size=16, dt_rank=8)


def _toy_model(dtype="float32"):
    from apex_tpu.models.decoder import PatternDecoder
    from benchmark.drivers import serve_jamba

    config = _toy_config()
    config["assumed"] = dict(config["assumed"], dtype=dtype,
                             param_dtype=dtype)
    cfg = serve_jamba.decoder_config(config)
    params = serve_jamba.init_params(cfg, 11, config["assumed"])
    return config, cfg, PatternDecoder(cfg), params


def test_the_block_is_the_reference():
    """The toy block's one pass (``mamba1`` layers and an attention layer
    of ONE KV head for two query heads, no rotary) against the float32
    reference, by logits."""
    from benchmark.drivers import serve_jamba

    config, cfg, model, params = _toy_model()
    tokens = np.random.default_rng(0).integers(0, 512, 40).astype(np.int32)
    logits = model.apply(params, jnp.asarray(tokens)[None])[:, 0]
    want = jref.forward(params, tokens, np.arange(40),
                        serve_jamba.arch_of(config, cfg))
    err = jnp.abs(logits - want).max() / jnp.abs(want).max()
    assert float(err) < 2e-5, float(err)


@pytest.mark.parametrize("fault", ["fp8_everywhere"] + list(jref.FAULTS))
def test_each_control_is_refused(fault):
    """Every wrong model ``controls_jamba.py`` runs is far from the
    program where the true reference is near it."""
    from benchmark.drivers import serve_jamba

    config, cfg, model, params = _toy_model()
    arch = serve_jamba.arch_of(config, cfg)
    tokens = np.random.default_rng(1).integers(0, 512, 48).astype(np.int32)
    logits = model.apply(params, jnp.asarray(tokens)[None])[:, 0]
    wrong = (dict(round_to=jnp.float8_e4m3fn) if fault == "fp8_everywhere"
             else dict(faults=(fault,)))
    rows = np.arange(48)

    def off(**kw):
        ref = jref.forward(params, tokens, rows, arch, **kw)
        return float((jnp.abs(logits - ref).max(-1)
                      / jnp.abs(ref).max(-1)).max())

    assert off() < 2e-5
    assert off(**wrong) > 100 * config["reference_logit_error_max"]


def test_prefill_then_decode_through_the_engine_is_the_reference():
    """A prompt prefilled whole, then decoded through ``DecodeStep`` over
    the paged pool and the state slots, with a second sequence in the
    batch; the logits of every decoded row against the reference's
    full pass over the same tokens."""
    from apex_tpu import serving
    from benchmark.drivers import serve_jamba

    config, cfg, model, params = _toy_model()
    arch = serve_jamba.arch_of(config, cfg)
    cache = serving.KVCache.for_config(cfg, num_blocks=32, block_size=8,
                                       state_slots=3)
    step = serving.make_decode_step(model, cache)
    state = cache.init_state()
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, 512, n).astype(np.int32) for n in (13, 5)]
    ids = ["a", "b"]
    for sid, p in zip(ids, prompts):
        cache.allocate(sid, len(p) + 6)
    width = 8
    tables = cache.table_array(ids, width)
    slots = cache.slot_array(ids, 2)
    toks = np.zeros((2, 16), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    lengths = np.array([len(p) for p in prompts], np.int32)
    out = step.prefill(params, state, toks, lengths, tables, slots=slots)
    state, seqs = out.cache, [list(p) for p in prompts]
    rows = [[np.asarray(out.logits[i])] for i in range(2)]
    for _ in range(5):
        nxt = np.asarray(out.next_token)
        for i in range(2):
            seqs[i].append(int(nxt[i]))
        positions = np.array([len(s) - 1 for s in seqs], np.int32)
        out = step.decode(params, state, nxt.astype(np.int32), positions,
                          tables, slots=slots)
        state = out.cache
        for i in range(2):
            rows[i].append(np.asarray(out.logits[i]))
    for i, p in enumerate(prompts):
        want = jref.forward(params, np.asarray(seqs[i], np.int32),
                            np.arange(len(p) - 1, len(seqs[i])), arch)
        got = np.stack(rows[i])
        err = np.abs(got - np.asarray(want)).max() / np.abs(want).max()
        assert err < 5e-5, (i, err)


def test_a_fresh_sequence_in_a_reused_slot_starts_from_zeros():
    """A slot whose last owner left NaN (a quarantined tenant) serves the
    next sequence as if it were new."""
    from apex_tpu import serving
    from benchmark.drivers import serve_jamba

    config, cfg, model, params = _toy_model()
    arch = serve_jamba.arch_of(config, cfg)
    cache = serving.KVCache.for_config(cfg, num_blocks=16, block_size=8,
                                       state_slots=1)
    step = serving.make_decode_step(model, cache)
    state = cache.init_state()
    state = state._replace(state=tuple(s.at[0].set(jnp.nan)
                                       if jnp.issubdtype(s.dtype, jnp.floating)
                                       else s for s in state.state))
    prompt = np.random.default_rng(3).integers(1, 512, 11).astype(np.int32)
    cache.allocate("x", 16)
    toks = np.zeros((1, 16), np.int32)
    toks[0, :11] = prompt
    out = step.prefill(params, state, toks, np.array([11], np.int32),
                       cache.table_array(["x"], 2),
                       slots=cache.slot_array(["x"], 1))
    want = jref.forward(params, prompt, [10], arch)
    err = np.abs(np.asarray(out.logits) - np.asarray(want)).max()
    assert np.isfinite(np.asarray(out.logits)).all()
    assert err / np.abs(want).max() < 2e-5
