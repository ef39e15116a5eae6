"""Serving tier (apex_tpu/serving, docs/serving.md): paged KV cache,
donation-aware prefill/decode steps, and the continuous batcher.

Anchors:

- prefill-then-N-decode-steps matches the full-sequence forward within
  fp32 tolerance (the decode-parity contract), and the cache
  write-then-gather path is BITWISE (pure data movement);
- block-table reuse-after-free correctness and admission-control
  refusal at pool exhaustion;
- scheduler join/evict golden sequences, the fault drills
  (``serving_pool_exhausted`` / ``decode_step_exception``), and the
  compile-plane contract (bucketed shapes; zero recompiles after
  warmup).
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from apex_tpu import serving, telemetry  # noqa: E402
from apex_tpu.models.gpt import GPTConfig, GPTModel  # noqa: E402
from apex_tpu.resilience import faults  # noqa: E402
from apex_tpu.serving.kv_cache import (  # noqa: E402
    KVCache,
    PoolExhausted,
    append_kv,
    append_kv_chunk,
    append_kv_prefill,
    bucket,
    gather_kv,
)

VOCAB, SEQ, HID, LAYERS, HEADS, KV = 64, 64, 32, 2, 4, 2
BLOCKS, BS = 16, 4


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
    # ONE DecodeStep for the whole module: jax.jit caches by function
    # identity, so sharing it means each bucketed shape compiles once
    # across every test below
    model, _ = model_and_params
    return serving.make_decode_step(model, fresh_cache())


def make_batcher(model, params, step_fn, cache, **kw):
    reg = telemetry.MetricsRegistry()
    sink = telemetry.InMemorySink()
    reg.add_sink(sink)
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_prefill_batch", 2)
    b = serving.ContinuousBatcher(model, params, cache, step_fn=step_fn,
                                  registry=reg, **kw)
    return b, reg, sink


# ---------------------------------------------------------------------------
# allocator
# ---------------------------------------------------------------------------


class TestAllocator:
    def test_blocks_for(self):
        c = fresh_cache()
        assert c.blocks_for(1) == 1
        assert c.blocks_for(BS) == 1
        assert c.blocks_for(BS + 1) == 2
        assert c.blocks_for(0) == 1          # a sequence occupies space

    def test_allocate_free_reuse(self):
        c = fresh_cache()
        a = c.allocate("a", 2 * BS)
        b = c.allocate("b", 2 * BS)
        assert len(a) == 2 and len(b) == 2
        assert not set(a) & set(b)
        assert serving.TRASH_BLOCK not in a + b
        assert c.blocks_in_use == 4
        c.free("a")
        assert c.blocks_in_use == 2
        # reuse-after-free: the freed blocks are handed out again
        c2 = c.allocate("c", 2 * BS)
        assert set(c2) == set(a)
        assert c.blocks_in_use == 4

    def test_admission_refusal_at_exhaustion(self):
        c = fresh_cache(num_blocks=4)
        c.allocate("a", 3 * BS)
        assert not c.can_admit(2 * BS)
        with pytest.raises(PoolExhausted) as ei:
            c.allocate("b", 2 * BS)
        assert ei.value.needed == 2
        assert ei.value.free == 1
        assert c.blocks_in_use == 3          # refusal leaks nothing
        assert c.can_admit(BS)
        c.allocate("b", BS)

    def test_double_allocate_raises(self):
        c = fresh_cache()
        c.allocate("a", BS)
        with pytest.raises(ValueError, match="already allocated"):
            c.allocate("a", BS)

    def test_table_array(self):
        c = fresh_cache()
        c.allocate("a", 2 * BS)
        t = c.table_array(["a"], width=4, batch=3)
        assert t.shape == (3, 4)
        assert list(t[0, :2]) == c.table("a")
        assert (t[0, 2:] == serving.TRASH_BLOCK).all()
        assert (t[1:] == serving.TRASH_BLOCK).all()
        with pytest.raises(ValueError, match="width"):
            c.table_array(["a"], width=1)

    def test_free_unknown_is_noop(self):
        c = fresh_cache()
        assert c.free("nope") == 0


class TestPrefixProbe:
    """prefix_match_len — the router's placement probe — at its edges:
    degenerate prompts, a probe spanning the whole pool, and the
    read-only contract (a probe never references, revives, or evicts
    anything the admission path would then miss)."""

    def _publish(self, c, seq, prompt, total):
        c.allocate(seq, total)
        c.publish_prefix(seq, prompt)

    def test_empty_and_single_token_prompts(self):
        c = fresh_cache()
        assert c.prefix_match_len([]) == 0
        assert c.prefix_match_len([5]) == 0
        # still 0 when that very block IS published: the last prompt
        # token always prefills (the first-token logits must exist),
        # so a one-token prompt can never match
        self._publish(c, "a", [5] * BS, 2 * BS)
        assert c.prefix_match_len([5]) == 0
        assert c.prefix_match_len([5] * BS) == 0        # cap len - 1
        assert c.prefix_match_len([5] * (BS + 1)) == BS

    def test_full_pool_probe_caps_at_len_minus_one(self):
        c = fresh_cache()                # BLOCKS blocks, all published
        prompt = [int(x) for x in np.random.RandomState(2).randint(
            0, VOCAB, BLOCKS * BS)]
        self._publish(c, "a", prompt, BLOCKS * BS)
        c.free("a")                      # zero-ref: all blocks cached
        # probing the exact published prompt leaves its own last token
        # to prefill; one token more matches every published block
        assert c.prefix_match_len(prompt) == (BLOCKS - 1) * BS
        assert c.prefix_match_len(prompt + [7]) == BLOCKS * BS
        # divergence in the first block: nothing matches
        assert c.prefix_match_len([prompt[0] + 1] + prompt[1:]) == 0

    def test_probe_never_mutates(self):
        c = fresh_cache()
        prompt = [int(x) for x in np.random.RandomState(3).randint(
            0, VOCAB, 3 * BS)]
        self._publish(c, "a", prompt, 4 * BS)
        tbl = c.table("a")
        c.free("a")
        before = c.prefix_stats()
        free_before = c.free_blocks
        refs_before = [c.block_ref(b) for b in tbl]
        for _ in range(3):
            assert c.prefix_match_len(prompt) == 2 * BS
        # read-only: no stats moved (hits/misses belong to admission),
        # no block referenced, nothing evicted or freed
        assert c.prefix_stats() == before
        assert c.free_blocks == free_before
        assert [c.block_ref(b) for b in tbl] == refs_before
        # and the real reservation still finds what the probe promised
        m = c.allocate_prefix("b", prompt, 4 * BS)
        assert m.shared_blocks == 2
        assert m.matched >= 2 * BS


# ---------------------------------------------------------------------------
# pool ops: append + gather is bitwise
# ---------------------------------------------------------------------------


class TestPoolOps:
    def test_prefill_append_then_gather_bitwise(self):
        c = fresh_cache()
        state = c.init_state()
        rng = np.random.RandomState(1)
        s, b, d = 10, 2, HID // HEADS
        k = jnp.asarray(rng.randn(LAYERS, b, KV, s, d), jnp.float32)
        v = jnp.asarray(rng.randn(LAYERS, b, KV, s, d), jnp.float32)
        for i in range(b):
            c.allocate(i, s)
        tables = jnp.asarray(c.table_array([0, 1], width=3))
        lengths = jnp.asarray([s, 7], jnp.int32)
        state = append_kv_prefill(state, k, v, tables, lengths)
        gk, gv = gather_kv(state, tables)
        assert gk.shape == (LAYERS, b, KV, 3 * BS, d)
        # bitwise: the gathered prefix IS the written bytes
        np.testing.assert_array_equal(np.asarray(gk)[:, 0, :, :s],
                                      np.asarray(k)[:, 0])
        np.testing.assert_array_equal(np.asarray(gv)[:, 1, :, :7],
                                      np.asarray(v)[:, 1, :, :7])

    def test_prefill_pads_land_in_trash(self):
        c = fresh_cache()
        state = c.init_state()
        rng = np.random.RandomState(2)
        s, d = 8, HID // HEADS
        c.allocate("real", 2 * BS)
        c.allocate("victim", 2 * BS)
        k = jnp.asarray(rng.randn(LAYERS, 1, KV, s, d), jnp.float32)
        # write the victim's full 8 slots first
        vt = jnp.asarray(c.table_array(["victim"], width=2))
        state = append_kv_prefill(state, k, k, vt,
                                  jnp.asarray([s], jnp.int32))
        before = np.asarray(gather_kv(state, vt)[0])
        # now a short prefill on "real": positions >= length are pads
        rt = jnp.asarray(c.table_array(["real"], width=2))
        state = append_kv_prefill(state, k, k, rt,
                                  jnp.asarray([3], jnp.int32))
        after = np.asarray(gather_kv(state, vt)[0])
        np.testing.assert_array_equal(before, after)

    def test_single_token_append_bitwise(self):
        c = fresh_cache()
        state = c.init_state()
        rng = np.random.RandomState(3)
        d = HID // HEADS
        c.allocate("a", 3 * BS)
        tables = jnp.asarray(c.table_array(["a"], width=3))
        rows = []
        for t in range(2 * BS + 1):      # crosses a block boundary
            kt = jnp.asarray(rng.randn(LAYERS, 1, KV, d), jnp.float32)
            rows.append(np.asarray(kt))
            state = append_kv(state, kt, kt, tables,
                              jnp.asarray([t], jnp.int32))
        gk, _ = gather_kv(state, tables)
        got = np.asarray(gk)[:, 0]            # (LAYERS, KV, 3*BS, d)
        for t, row in enumerate(rows):
            np.testing.assert_array_equal(got[:, :, t], row[:, 0])


def _scatter_append(state, k_new, v_new, tables, positions):
    """The append as one scatter a pool over all lanes, which the
    in-place updates replaced: the plain reference."""
    bs = state.k.shape[2]
    w = tables.shape[1]
    blk = jnp.take_along_axis(
        tables, jnp.clip(positions[:, None] // bs, 0, w - 1), axis=1)[:, 0]
    slot = positions % bs
    return serving.KVCacheState(k=state.k.at[:, blk, slot].set(k_new),
                                v=state.v.at[:, blk, slot].set(v_new))


def _scatter_chunk(state, k_new, v_new, tables, starts, lengths):
    """The chunk's rows by one scatter a pool, pads to the trash
    block: the plain reference."""
    bs = state.k.shape[2]
    b, w = tables.shape
    s = k_new.shape[3]
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None, :], (b, s))
    valid = pos < lengths[:, None]
    if starts is not None:
        pos = pos + starts[:, None]
    blk = jnp.take_along_axis(tables, jnp.clip(pos // bs, 0, w - 1), axis=1)
    blk = jnp.where(valid, blk, serving.TRASH_BLOCK)
    slot = pos % bs

    def one(pool, new):
        return pool.at[:, blk, slot].set(new.transpose(0, 1, 3, 2, 4))

    return serving.KVCacheState(k=one(state.k, k_new), v=one(state.v, v_new))


#: name -> (chunk length, starts or None, lengths); tables of 4 blocks
#: of BS rows a lane, so a lane holds 4 * BS positions
APPEND_CHUNKS = {
    "from-a-block-edge": (2 * BS, [BS, 0, 2 * BS], [2 * BS, 2 * BS, 2 * BS]),
    "inside-a-block-after-written-rows": (
        2 * BS, [BS + 1, 3, 2 * BS - 1], [2 * BS, 2 * BS, 2 * BS]),
    "lengths-end-inside-a-block-and-at-0": (
        2 * BS, [BS, 2, 0], [BS + 1, 0, 2 * BS - 1]),
    "two-lanes-of-unequal-length": (3 * BS, [0, BS + 2, 0], [3 * BS, 2, 0]),
    "whole-prompt-prefill": (3 * BS + 1, None, [3 * BS + 1, 5, 0]),
}


@pytest.mark.parametrize("d", [8, 128], ids=["head-dim-8", "head-dim-128"])
class TestAppendInPlace:
    """``append_kv`` / ``append_kv_chunk`` write the pool by updates
    in place, a lane (and a block) at a time; every real block then
    holds bitwise what one scatter over all lanes left there. Only the
    trash block's contents are free."""

    @staticmethod
    def pool(kv, d, seed):
        rng = np.random.RandomState(seed)
        shape = (LAYERS, BLOCKS + 1, BS, kv, d)
        state = serving.KVCacheState(
            k=jnp.asarray(rng.randn(*shape), jnp.bfloat16),
            v=jnp.asarray(rng.randn(*shape), jnp.bfloat16))
        # three lanes of four distinct real blocks each, the last lane's
        # table half trash (a sequence that has not reserved them)
        tables = 1 + rng.permutation(BLOCKS)[:12].reshape(3, 4)
        tables[2, 2:] = serving.TRASH_BLOCK
        return rng, state, jnp.asarray(tables, jnp.int32)

    @staticmethod
    def same_real_blocks(got, want):
        for g, w in zip((got.k, got.v), (want.k, want.v)):
            np.testing.assert_array_equal(
                np.asarray(g.astype(jnp.float32))[:, 1:],
                np.asarray(w.astype(jnp.float32))[:, 1:])

    @pytest.mark.parametrize("kv", [2, 4, 8, 16])
    def test_decode_with_dummy_lanes_on_the_trash_block(self, kv, d):
        rng, state, tables = self.pool(kv, d, 11)
        # lanes 1 and 3 are dummies: an all-trash table, position 0
        tables = jnp.stack([tables[0], jnp.zeros(4, jnp.int32), tables[1],
                            jnp.zeros(4, jnp.int32)])
        positions = jnp.asarray([2 * BS + 1, 0, 4 * BS - 1, 0], jnp.int32)
        k = jnp.asarray(rng.randn(LAYERS, 4, kv, d), jnp.bfloat16)
        v = jnp.asarray(rng.randn(LAYERS, 4, kv, d), jnp.bfloat16)
        got = jax.jit(append_kv)(state, k, v, tables, positions)
        want = _scatter_append(state, k, v, tables, positions)
        self.same_real_blocks(got, want)
        assert not np.array_equal(np.asarray(got.k.astype(jnp.float32)),
                                  np.asarray(state.k.astype(jnp.float32)))

    @pytest.mark.parametrize("kv", [2, 4, 8, 16])
    @pytest.mark.parametrize("case", list(APPEND_CHUNKS))
    def test_chunk(self, case, kv, d):
        s, starts, lengths = APPEND_CHUNKS[case]
        rng, state, tables = self.pool(kv, d, 12)
        k = jnp.asarray(rng.randn(LAYERS, 3, kv, s, d), jnp.bfloat16)
        v = jnp.asarray(rng.randn(LAYERS, 3, kv, s, d), jnp.bfloat16)
        lengths = jnp.asarray(lengths, jnp.int32)
        if starts is None:
            got = jax.jit(append_kv_prefill)(state, k, v, tables, lengths)
        else:
            starts = jnp.asarray(starts, jnp.int32)
            got = jax.jit(append_kv_chunk)(state, k, v, tables, starts,
                                           lengths)
        want = _scatter_chunk(state, k, v, tables, starts, lengths)
        self.same_real_blocks(got, want)
        # the rows it had before a chunk that starts inside a block,
        # and everything a lane of length 0 owns, are what they were
        untouched = np.asarray(state.k.astype(jnp.float32))
        after = np.asarray(got.k.astype(jnp.float32))
        for lane, n in enumerate(np.asarray(lengths)):
            at = 0 if starts is None else int(starts[lane])
            for pos in range(4 * BS):
                blk = int(tables[lane, pos // BS])
                if blk != serving.TRASH_BLOCK and not at <= pos < at + n:
                    np.testing.assert_array_equal(
                        after[:, blk, pos % BS], untouched[:, blk, pos % BS])


def _written_pool(seed, num_blocks=BLOCKS):
    """A pool whose every row differs, and ragged block tables over
    it (trash-padded, one lane all trash)."""
    cache = fresh_cache(num_blocks=num_blocks)
    shape = cache.init_state().k.shape
    rng = np.random.RandomState(seed)
    state = serving.KVCacheState(
        k=jnp.asarray(rng.randn(*shape), jnp.float32),
        v=jnp.asarray(rng.randn(*shape), jnp.float32))
    for i, n in enumerate([3 * BS, BS + 1, 4 * BS]):
        cache.allocate(i, n)
    tables = np.concatenate(
        [cache.table_array([0, 1, 2], width=4),
         np.full((1, 4), serving.TRASH_BLOCK, np.int32)])
    return state, jnp.asarray(tables)


def _assert_gathered(got, want, lens, *, group=None, handed=None):
    """``got`` (b, kv, L, d) is ``want`` in each lane's live blocks.
    From the kernel (``group`` blocks a grid step) the rest of a lane's
    last live group is zeros and everything past it is ``handed``, the
    array it wrote into; otherwise the rest is only finite."""
    got, want = np.asarray(got), np.asarray(want)
    for i, n in enumerate(np.asarray(lens)):
        blocks = -(-int(n) // BS)
        live = blocks * BS
        np.testing.assert_array_equal(got[i, :, :live], want[i, :, :live])
        if group is None:
            assert np.isfinite(got[i, :, live:]).all()
            continue
        edge = max(-(-blocks // group), 1) * group * BS
        assert not got[i, :, live:edge].any()
        np.testing.assert_array_equal(got[i, :, edge:],
                                      np.asarray(handed)[i, :, edge:])


class TestLayerGather:
    """What the compiled programs read the pool through: each layer
    gathers its own context (ops/kv_gather.py inside the model), as far
    as each lane's keys go, and that far it is layer by layer what
    ``gather_kv`` gives for all at once."""

    @pytest.mark.parametrize("lens", [
        [0, 0, 0, 0], [6, 1, 7, 3],             # nothing; mid-block
        [BS, BS, 3 * BS, BS],                   # a block edge
        [2 * BS, 2 * BS, 2 * BS, 2 * BS],       # a group's edge, at 2
        [4 * BS, 4 * BS, 4 * BS, 4 * BS],       # the whole width
        [9, BS, 4 * BS, 0]],
        ids=["none", "mid-block", "block-edge", "group-edge", "whole",
             "mixed"])
    @pytest.mark.parametrize("impl,group", [("xla", 16), ("interpret", 16),
                                            ("interpret", 2),
                                            ("interpret", 1)])
    def test_kv_gather_is_gather_kv_layer_by_layer(self, impl, group, lens):
        from apex_tpu.ops.kv_gather import kv_gather

        state, tables = _written_pool(4)
        want_k, want_v = gather_kv(state, tables)
        rng = np.random.RandomState(5)
        handed = tuple(jnp.asarray(rng.randn(*want_k.shape[1:]), jnp.float32)
                       for _ in range(2))
        lens = jnp.asarray(lens, jnp.int32)
        for layer in range(LAYERS):
            # a traced layer index, as the layer scan passes it
            got = jax.jit(lambda st, l: kv_gather(
                st.k, st.v, l, tables, lens, handed, impl=impl,
                group=group))(state, jnp.int32(layer))
            for g, want, into in zip(got, (want_k, want_v), handed):
                _assert_gathered(
                    g, want[layer], lens, handed=into,
                    group=None if impl == "xla" else min(group, 4))

    @pytest.mark.parametrize("lens", [
        [0, 0, 0, 0], [6, 1, 7, 3], [BS, BS, 3 * BS, BS],
        [9, BS, 4 * BS, 0], [5 * BS, 4 * BS + 1, 4 * BS - 1, 2]],
        ids=["none", "mid-block", "block-edge", "mixed", "past-the-table"])
    def test_live_blocks_counts_what_the_kernel_writes(self, lens):
        """The engine's count (``ContinuousBatcher.gathered``, numpy on
        the host) and the kernel's liveness are one function: a block a
        grid step at a time, the blocks of a lane that come back other
        than they were handed in are its ``live_blocks``, and its first
        block whatever its length."""
        from apex_tpu.ops.kv_gather import kv_gather, live_blocks

        state, tables = _written_pool(4)
        width = tables.shape[1]
        want = live_blocks(np.asarray(lens, np.int32), BS, width)
        np.testing.assert_array_equal(
            want, live_blocks(jnp.asarray(lens, jnp.int32), BS, width))
        assert want.dtype == np.int32 and want.max() <= width
        kv, d = state.k.shape[3:]
        handed = jnp.full((len(lens), kv, width * BS, d), 7.0)
        got, _ = kv_gather(state.k, state.v, 1, tables,
                           jnp.asarray(lens, jnp.int32), (handed, handed),
                           impl="interpret", group=1)
        written = (np.asarray(got) != 7.0).any(axis=(1, 3))   # (b, w * BS)
        written = written.reshape(len(lens), width, BS).any(axis=2)
        np.testing.assert_array_equal(written.sum(axis=1),
                                      np.maximum(want, 1))

    @pytest.mark.parametrize("impl", ["xla", "interpret"])
    def test_kv_gather_handed_nothing_writes_into_zeros(self, impl):
        from apex_tpu.ops.kv_gather import kv_gather

        state, tables = _written_pool(4)
        want = gather_kv(state, tables)
        lens = jnp.asarray([9, BS, 4 * BS, 0], jnp.int32)
        got = kv_gather(state.k, state.v, 1, tables, lens, impl=impl,
                        group=1)
        for g, w in zip(got, want):
            _assert_gathered(g, w[1], lens, handed=jnp.zeros_like(g),
                             group=None if impl == "xla" else 1)

    @pytest.mark.parametrize("impl", ["xla", "interpret"])
    @pytest.mark.parametrize("scan_layers", [True, False])
    @pytest.mark.parametrize("s", [1, 3])
    def test_model_gathers_each_layer_bitwise(self, scan_layers, s, impl):
        model = GPTModel(tiny_config(scan_layers=scan_layers,
                                     softmax_impl=impl))
        params = model.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
        state, tables = _written_pool(5)
        b = tables.shape[0]
        lens = jnp.asarray([9, 4, 12, 0], jnp.int32)
        toks = jnp.ones((b, s), jnp.int32)
        pos = lens[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
        _, grown = model.apply(
            params, toks, positions=pos,
            kv_ctx=(state.k, state.v, tables, lens), return_kv=True,
            mutable=["intermediates"])
        sown = grown["intermediates"]
        if scan_layers:
            (got_k, got_v), = sown["layers"]["layer"]["attention"]["kv_ctx"]
        else:
            per = [sown[f"layer_{i}"]["attention"]["kv_ctx"][0]
                   for i in range(LAYERS)]
            got_k, got_v = (jnp.stack([kv[j] for kv in per])
                            for j in (0, 1))
        want_k, want_v = gather_kv(state, tables)
        # past a lane's live blocks: zeros, or what an earlier layer
        # left there (its context, its token's own K/V)
        bound = max(float(jnp.abs(t).max()) for t in (state.k, state.v)) + 1e3
        for got, want in ((got_k, want_k), (got_v, want_v)):
            for layer in range(LAYERS):
                _assert_gathered(got[layer], want[layer], lens)
            assert float(jnp.abs(got).max()) <= bound


# ---------------------------------------------------------------------------
# decode parity vs the full-sequence forward
# ---------------------------------------------------------------------------


class TestDecodeParity:
    def _parity(self, model, params, step_fn, plens, n_decode, tol=3e-5):
        rng = np.random.RandomState(7)
        b = len(plens)
        s = max(plens) + n_decode
        toks = rng.randint(0, VOCAB, (b, s)).astype(np.int32)
        full = np.asarray(model.apply(params, jnp.asarray(toks)))
        cache = fresh_cache()
        state = cache.init_state()
        for i in range(b):
            cache.allocate(i, s)
        w = max(len(cache.table(i)) for i in range(b))
        tables = cache.table_array(list(range(b)), w)
        out = step_fn.prefill(params, state, toks[:, :max(plens)],
                              np.asarray(plens, np.int32), tables)
        state = out.cache
        got = np.asarray(out.logits)
        for i in range(b):
            ref = full[plens[i] - 1, i]
            np.testing.assert_allclose(got[i], ref, atol=tol, rtol=tol)
        positions = np.asarray(plens, np.int32)
        for _ in range(n_decode):
            cur = toks[np.arange(b), positions]       # teacher forcing
            out = step_fn.decode(params, state, cur, positions, tables)
            state = out.cache
            got = np.asarray(out.logits)
            ids = np.asarray(out.next_token)
            for i in range(b):
                ref = full[positions[i], i]
                np.testing.assert_allclose(got[i], ref, atol=tol,
                                           rtol=tol)
                assert ids[i] == int(np.argmax(got[i]))
            positions = positions + 1

    def test_prefill_then_decode_matches_full_forward(
            self, model_and_params, step_fn):
        model, params = model_and_params
        # mixed lengths in one batch: every sequence sits at its own
        # offset — the per-sequence positions/ctx_mask contract
        self._parity(model, params, step_fn, plens=[12, 7], n_decode=6)

    def test_parity_unscanned_layers(self):
        # scan_layers=False takes the python-loop path through the new
        # kv plumbing; same parity contract
        model = GPTModel(tiny_config(scan_layers=False))
        rng = np.random.RandomState(0)
        toks = jnp.asarray(rng.randint(0, VOCAB, (1, 8)), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), toks)
        cache = fresh_cache()
        step = serving.make_decode_step(model, cache)
        self._parity(model, params, step, plens=[6, 9], n_decode=3)

    @pytest.mark.parametrize("scan_layers", [True, False])
    def test_token_in_its_slot_at_ragged_positions(self, scan_layers):
        # one decode batch: a lane in the LAST slot of its table, a
        # lane whose token opens a new block, a lane mid-block and a
        # dummy lane (all-trash table, position 0). The token's K/V
        # sits in slot positions[b] of the gathered context, where
        # append_kv writes it afterwards.
        model = GPTModel(tiny_config(scan_layers=scan_layers))
        params = model.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
        w = 4
        plens = [w * BS - 1, 2 * BS, BS + 1]
        assert plens[1] % BS == 0
        rng = np.random.RandomState(11)
        toks = rng.randint(0, VOCAB, (3, w * BS)).astype(np.int32)
        full = np.asarray(model.apply(params, jnp.asarray(toks)))
        cache = fresh_cache()
        step = serving.make_decode_step(model, cache)
        state = cache.init_state()
        for i, n in enumerate(plens):
            cache.allocate(i, n + 1)
        assert len(cache.table(0)) == w
        tables = cache.table_array([0, 1, 2], w)
        out = step.prefill(params, state, toks[:, :max(plens)],
                           np.asarray(plens, np.int32), tables)
        tables = np.concatenate(
            [tables, np.full((1, w), serving.TRASH_BLOCK, np.int32)])
        positions = np.asarray(plens + [0], np.int32)
        cur = np.append(toks[np.arange(3), plens], 0).astype(np.int32)
        out = step.decode(params, out.cache, cur, positions, tables)
        got = np.asarray(out.logits)
        for i, n in enumerate(plens):
            np.testing.assert_allclose(got[i], full[n, i], atol=3e-5,
                                       rtol=3e-5)
        assert np.isfinite(got[3]).all()
        # and the token's K/V is now in the pool at that very slot:
        # the next step reads it back as part of the written prefix
        gk, _ = gather_kv(out.cache, jnp.asarray(tables))
        assert np.abs(np.asarray(gk)[:, 1, :, plens[1]]).min() > 0
        assert np.abs(np.asarray(gk)[:, 1, :, plens[1] + 1]).max() == 0

    @pytest.mark.parametrize("fn,s", [("decode_step", 1),
                                      ("prefill_chunk", 8)])
    def test_lowered_programs_gather_per_layer(self, model_and_params,
                                               step_fn, monkeypatch, fn, s):
        # the mechanism engages on every dispatch or not at all: in
        # the lowered program no array holds every layer's context,
        # decode concatenates nothing along the key axis, and the
        # attention call's key length is exactly width * block_size
        from apex_tpu.ops import attention

        model, params = model_and_params
        calls = []
        real = attention.flash_attention

        def recording(q, k, v, **kw):
            calls.append((q.shape, k.shape, v.shape,
                          kw["kv_segment_ids"].shape))
            return real(q, k, v, **kw)

        monkeypatch.setattr(attention, "flash_attention", recording)
        b, w = 4, 8
        L, d = w * BS, HID // HEADS
        state = jax.eval_shape(fresh_cache().init_state)
        text = step_fn.lower(fn, params, state, b, w, s).as_text()
        keys = L if s == 1 else L + s
        # (the layer scan traces its body more than once); a decode
        # call carries the query heads of a kv head as one block's rows
        q = (b, KV, HEADS // KV, d) if s == 1 else (b, HEADS, s, d)
        assert set(calls) == {(q, (b, KV, keys, d),
                               (b, KV, keys, d), (b, keys))}
        for dtype in ("f32", "i32", "i1"):
            assert f"tensor<{LAYERS}x{b}x{KV}x{L}x{d}x{dtype}>" not in text
            if s == 1:
                assert f"x{L + 1}x{d}x{dtype}>" not in text
                assert f"tensor<{b}x{L + 1}x{dtype}>" not in text

    def test_explicit_positions_match_default(self, model_and_params):
        # the satellite anchor: positions are an explicit input, not
        # arange(seq) derived from the input shape — (s,) and (b, s)
        # forms agree with the default bitwise
        model, params = model_and_params
        rng = np.random.RandomState(9)
        toks = jnp.asarray(rng.randint(0, VOCAB, (2, 10)), jnp.int32)
        base = model.apply(params, toks)
        p1 = model.apply(params, toks,
                         positions=jnp.arange(10, dtype=jnp.int32))
        p2 = model.apply(params, toks, positions=jnp.broadcast_to(
            jnp.arange(10, dtype=jnp.int32)[None], (2, 10)))
        np.testing.assert_array_equal(np.asarray(base), np.asarray(p1))
        np.testing.assert_array_equal(np.asarray(base), np.asarray(p2))

    def test_single_token_forward_at_offset(self, model_and_params):
        # a one-token forward at position t (no cache, no prefix) uses
        # exactly the position-t embedding row
        model, params = model_and_params
        tok = jnp.asarray([[5]], jnp.int32)
        a = model.apply(params, tok,
                        positions=jnp.asarray([3], jnp.int32))
        b = model.apply(params, tok,
                        positions=jnp.asarray([[3]], jnp.int32))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        c = model.apply(params, tok,
                        positions=jnp.asarray([[4]], jnp.int32))
        assert np.abs(np.asarray(b) - np.asarray(c)).max() > 0


# ---------------------------------------------------------------------------
# scheduler
# ---------------------------------------------------------------------------


class TestScheduler:
    def test_join_evict_golden(self, model_and_params, step_fn):
        model, params = model_and_params
        cache = fresh_cache()
        eng, reg, _ = make_batcher(model, params, step_fn, cache)
        state = cache.init_state()
        r = [serving.Request(id=i, prompt=[1 + i] * 5, max_new_tokens=n)
             for i, n in enumerate([2, 4, 4])]
        eng.submit(r[0])
        eng.submit(r[1])
        eng.submit(r[2])
        # step 0: two admissions (max_prefill_batch=2), both prefill
        # (first token) + decode (second); r2 queued
        state, rep = eng.step(state)
        assert rep["admitted"] == [0, 1]
        assert rep["decoded"] == [0, 1]
        assert rep["queued"] == 1
        assert rep["finished"] == [0]          # max_new=2: done already
        # step 1: r2 joins the in-flight r1 — the continuous join
        state, rep = eng.step(state)
        assert rep["admitted"] == [2]
        assert rep["decoded"] == [1, 2]
        assert rep["finished"] == []
        blocks_mid = rep["blocks_in_use"]
        # step 2: r1 finishes (4 tokens) and frees its blocks
        state, rep = eng.step(state)
        assert rep["finished"] == [1]
        assert rep["blocks_in_use"] < blocks_mid
        # drain to completion
        while not eng.idle():
            state, rep = eng.step(state)
        assert rep["finished"] == [2]
        assert cache.blocks_in_use == 0
        res = {x.id: x for x in eng.drain()}
        assert [len(res[i].tokens) for i in range(3)] == [2, 4, 4]
        assert all(res[i].finish_reason == "length" for i in range(3))
        assert reg.gauge("serving_kv_blocks_in_use").value() == 0
        assert reg.counter("serving_requests").value(
            outcome="length") == 3

    def test_admission_defers_until_blocks_free(self, model_and_params,
                                                step_fn):
        model, params = model_and_params
        # pool fits ONE request's span (3 blocks of 4 = prompt 5 +
        # max_new 6 = 11 tokens); the second must wait for the first
        cache = fresh_cache(num_blocks=3)
        eng, reg, _ = make_batcher(model, params, step_fn, cache)
        state = cache.init_state()
        eng.submit(serving.Request(id="a", prompt=[1] * 5,
                                   max_new_tokens=6))
        eng.submit(serving.Request(id="b", prompt=[2] * 5,
                                   max_new_tokens=6))
        state, rep = eng.step(state)
        assert rep["admitted"] == ["a"]
        assert rep["queued"] == 1
        assert reg.counter("serving_admission_deferred").value() >= 1
        admitted_b_at = None
        for i in range(1, 20):
            state, rep = eng.step(state)
            if rep["admitted"] == ["b"]:
                admitted_b_at = i
            if eng.idle():
                break
        assert admitted_b_at is not None
        res = {x.id: x for x in eng.drain()}
        assert res["a"].finish_reason == "length"
        assert res["b"].finish_reason == "length"
        assert res["b"].ttft_s > res["a"].ttft_s

    def test_oversized_request_rejected(self, model_and_params, step_fn):
        model, params = model_and_params
        cache = fresh_cache(num_blocks=2)
        eng, reg, sink = make_batcher(model, params, step_fn, cache)
        state = cache.init_state()
        eng.submit(serving.Request(id="big", prompt=[1] * 8,
                                   max_new_tokens=32))
        state, rep = eng.step(state)
        assert rep["admitted"] == []
        res = eng.drain()
        assert len(res) == 1 and res[0].finish_reason == "error"
        assert "can never be admitted" in res[0].error
        names = [e["event"] for e in sink.events]
        assert "serving_request_error" in names

    def test_eos_finishes_early(self, model_and_params, step_fn):
        model, params = model_and_params
        cache = fresh_cache()
        eng, _, _ = make_batcher(model, params, step_fn, cache)
        state = cache.init_state()
        # greedy decode is deterministic: learn the tokens, then rerun
        # with eos = the 2nd generated token
        eng.submit(serving.Request(id=0, prompt=[3] * 6,
                                   max_new_tokens=6))
        while not eng.idle():
            state, _ = eng.step(state)
        ref = eng.drain()[0]
        assert len(ref.tokens) == 6
        eos = ref.tokens[1]
        eng.submit(serving.Request(id=1, prompt=[3] * 6,
                                   max_new_tokens=6, eos_id=eos))
        while not eng.idle():
            state, _ = eng.step(state)
        out = eng.drain()[0]
        assert out.finish_reason == "eos"
        assert out.tokens == ref.tokens[:ref.tokens.index(eos) + 1]
        assert cache.blocks_in_use == 0

    def test_serve_loop_completes_all(self, model_and_params, step_fn):
        model, params = model_and_params
        cache = fresh_cache()
        eng, _, _ = make_batcher(model, params, step_fn, cache)
        state = cache.init_state()
        rng = np.random.RandomState(4)
        reqs = [serving.Request(
            id=i, prompt=rng.randint(0, VOCAB, (rng.randint(2, 9),)),
            max_new_tokens=int(rng.randint(1, 6))) for i in range(9)]
        state, results = serving.serve_loop(eng, state, reqs)
        assert sorted(r.id for r in results) == list(range(9))
        for r in results:
            req = reqs[r.id]
            assert len(r.tokens) == req.max_new_tokens
            assert r.ttft_s is not None and r.ttft_s >= 0
        assert cache.blocks_in_use == 0

    def test_static_batch_generate_same_tokens(self, model_and_params,
                                               step_fn):
        # the bench baseline produces the SAME greedy tokens as the
        # continuous engine — only scheduling differs
        model, params = model_and_params
        rng = np.random.RandomState(5)
        reqs = [serving.Request(
            id=i, prompt=rng.randint(0, VOCAB, (rng.randint(2, 9),)),
            max_new_tokens=int(rng.randint(2, 6))) for i in range(5)]
        cache = fresh_cache()
        eng, _, _ = make_batcher(model, params, step_fn, cache)
        state, cb = serving.serve_loop(eng, cache.init_state(), reqs)
        cache2 = fresh_cache()
        _, st = serving.static_batch_generate(
            model, params, cache2, cache2.init_state(), reqs,
            batch_size=4, step_fn=step_fn)
        cb = {r.id: r.tokens for r in cb}
        st = {r.id: r.tokens for r in st}
        assert cb == st


class _SlotChecked:
    """A ``DecodeStep`` that checks, at every decode dispatch, what
    the decode program takes for granted: each live lane's table
    already covers the slot its token is about to be put in."""

    def __init__(self, inner):
        self.inner = inner
        self.decodes = 0

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def decode(self, params, state, tokens, positions, tables, **kw):
        tables, positions = np.asarray(tables), np.asarray(positions)
        for row, pos in zip(tables, positions):
            if (row != serving.TRASH_BLOCK).any():       # a live lane
                assert pos // BS < tables.shape[1]
                assert row[pos // BS] != serving.TRASH_BLOCK
                self.decodes += 1
        return self.inner.decode(params, state, tokens, positions, tables,
                                 **kw)


class TestDecodeSlotIsReserved:
    """The decode program puts the step's token into slot
    ``positions[b]`` of the gathered context instead of after it: the
    slot exists because a request's table covers prompt +
    max_new_tokens before it ever decodes."""

    def _requests(self):
        rng = np.random.RandomState(6)
        # prompts that end on a block boundary, one short of it, and
        # spanning several chunks; generations that run to the very
        # last slot of the reservation
        return [serving.Request(
            id=i, prompt=rng.randint(0, VOCAB, (plen,)), max_new_tokens=new)
            for i, (plen, new) in enumerate(
                [(BS, 4), (2 * BS - 1, 2 * BS + 1), (3, 5), (22, BS),
                 (4 * BS, 1), (9, 3 * BS - 1)])]

    @pytest.mark.parametrize("engine", ["monolithic", "chunked", "static"])
    def test_every_decode_finds_its_slot(self, model_and_params, step_fn,
                                         engine):
        model, params = model_and_params
        cache = fresh_cache(num_blocks=64)
        checked = _SlotChecked(step_fn)
        reqs = self._requests()
        if engine == "static":
            _, results = serving.static_batch_generate(
                model, params, cache, cache.init_state(), reqs,
                batch_size=4, step_fn=checked)
        else:
            kw = {"prefill_chunk": 8} if engine == "chunked" else {}
            eng, _, _ = make_batcher(model, params, checked, cache, **kw)
            _, results = serving.serve_loop(eng, cache.init_state(), reqs)
        assert {r.id: len(r.tokens) for r in results} == \
            {r.id: r.max_new_tokens for r in reqs}
        assert checked.decodes >= sum(r.max_new_tokens - 1 for r in reqs)


# ---------------------------------------------------------------------------
# fault drills + flight bundles
# ---------------------------------------------------------------------------


class TestFaultDrills:
    def test_pool_exhausted_sheds_load(self, model_and_params, step_fn,
                                       tmp_path, monkeypatch):
        from apex_tpu import records
        from apex_tpu.telemetry import flight

        monkeypatch.setattr(records, "RECORDS_DIR", str(tmp_path))
        model, params = model_and_params
        cache = fresh_cache()
        eng, reg, sink = make_batcher(model, params, step_fn, cache)
        state = cache.init_state()
        flight.enable()
        try:
            with faults.inject(pool_exhausted_steps=frozenset({0})):
                eng.submit(serving.Request(id=0, prompt=[1] * 4,
                                           max_new_tokens=2))
                state, rep = eng.step(state)
                # shed: stays queued, nothing admitted, event + bundle
                assert rep["admitted"] == []
                assert rep["queued"] == 1
                names = [e["event"] for e in sink.events]
                assert "serving_pool_exhausted" in names
                # next step admits normally (the fault names step 0)
                state, rep = eng.step(state)
                assert rep["admitted"] == [0]
        finally:
            flight.disable()
        rec = records.latest_record(flight.FLIGHT_KIND,
                                    require_backend=None)
        assert rec is not None
        assert rec["payload"]["trigger"] == "serving_pool_exhausted"
        while not eng.idle():
            state, _ = eng.step(state)
        assert eng.drain()[0].finish_reason == "length"

    def test_decode_exception_quarantines_and_continues(
            self, model_and_params, step_fn, tmp_path, monkeypatch):
        # a STEP-indexed injected exception fails every binary-split
        # retry too, so the whole (single-member) batch quarantines —
        # under the serving_quarantine trigger, not the old
        # engine-fatal serving_request_error path
        from apex_tpu import records
        from apex_tpu.telemetry import flight

        monkeypatch.setattr(records, "RECORDS_DIR", str(tmp_path))
        model, params = model_and_params
        cache = fresh_cache()
        eng, reg, sink = make_batcher(model, params, step_fn, cache)
        state = cache.init_state()
        flight.enable()
        try:
            with faults.inject(decode_exception_steps=frozenset({0})):
                eng.submit(serving.Request(id="dead", prompt=[1] * 4,
                                           max_new_tokens=4))
                state, rep = eng.step(state)
                assert rep["finished"] == ["dead"]
                assert rep["quarantined"] == ["dead"]
                # degradation: blocks freed, bundle dumped, error result
                assert cache.blocks_in_use == 0
                res = eng.drain()
                assert res[0].finish_reason == "error"
                assert "injected decode-step exception" in res[0].error
            # engine keeps serving after the fault window
            eng.submit(serving.Request(id="alive", prompt=[2] * 4,
                                       max_new_tokens=2))
            while not eng.idle():
                state, _ = eng.step(state)
            assert eng.drain()[0].finish_reason == "length"
        finally:
            flight.disable()
        rec = records.latest_record(flight.FLIGHT_KIND,
                                    require_backend=None)
        assert rec is not None
        assert rec["payload"]["trigger"] == "serving_quarantine"
        assert "dead" in str(rec["payload"]["extra"]["requests"])
        assert reg.counter("serving_quarantined").value(
            reason="exception") == 1

    def test_env_knob_grammar(self):
        inj = faults.FaultInjector.from_env(
            "serving_pool_exhausted=2,5;decode_step_exception=3")
        assert inj.should_pool_exhaust(2)
        assert inj.should_pool_exhaust(5)
        assert not inj.should_pool_exhaust(3)
        with pytest.raises(faults.FaultError):
            inj.maybe_decode_exception(3)
        inj.maybe_decode_exception(2)        # no-op off-plan


# ---------------------------------------------------------------------------
# compile plane: bucketed shapes, zero recompiles after warmup
# ---------------------------------------------------------------------------


class TestCompilePlane:
    def test_decode_buckets_observed_no_recompiles_after_warmup(
            self, model_and_params):
        from apex_tpu.telemetry import compiled as _compiled

        model, params = model_and_params
        cache = fresh_cache()
        step = serving.make_decode_step(model, cache)
        reg = telemetry.MetricsRegistry()
        sink = telemetry.InMemorySink()
        reg.add_sink(sink)
        tracker = _compiled.enable(registry=reg, storm_threshold=100)
        try:
            eng = serving.ContinuousBatcher(
                model, params, cache, step_fn=step, max_batch=4,
                max_prefill_batch=2, registry=reg)
            state = eng.warmup(cache.init_state())
            warm_events = [e["event"] for e in sink.events]
            n_warm_recompiles = warm_events.count("recompile")
            keys = step.compile_keys()
            # decode pads to max_batch with one width bucket: ONE program
            assert keys["decode_step"] == 1
            # prefill: batch buckets {1, 2} x one seq bucket
            assert keys["prefill_step"] == 2
            assert tracker.summary()["signatures"]["decode_step"] == 1
            # hot loop: everything is a cache hit — zero NEW events
            rng = np.random.RandomState(6)
            reqs = [serving.Request(
                id=i, prompt=rng.randint(0, VOCAB, (rng.randint(2, 9),)),
                max_new_tokens=int(rng.randint(1, 5)))
                for i in range(8)]
            state, results = serving.serve_loop(eng, state, reqs)
            assert len(results) == 8
            hot_events = [e["event"] for e in sink.events]
            assert hot_events.count("recompile") == n_warm_recompiles
            assert step.compile_keys() == keys
        finally:
            _compiled.disable()
