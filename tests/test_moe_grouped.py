"""``ops/moe_grouped.py``: the held experts' grouped product as one
Pallas kernel, run interpreted here against ``lax.ragged_dot``, and
the grouped form of ``moe/held.py`` through it against the dense form.
What the TPU compiler makes of it (the cut programs' kernels, the two
bodies a program) is ``tests/test_tpu_compile.py``'s."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from apex_tpu import _backend
from apex_tpu.moe import held
from apex_tpu.ops.moe_grouped import (ROW_TILE, column_tile, moe_grouped,
                                      row_tile, visits)


def _operands(m, k, n, sizes, dtype=jnp.bfloat16, seed=0):
    a, b = jax.random.split(jax.random.PRNGKey(seed))
    rows = jax.random.normal(a, (m, k), jnp.float32).astype(dtype)
    w = (jax.random.normal(b, (len(sizes), k, n), jnp.float32)
         / np.sqrt(k)).astype(dtype)
    return rows, w, jnp.asarray(sizes, jnp.int32)


def _assert_matches_ragged_dot(rows, w, sizes):
    """The kernel, interpreted, is ``lax.ragged_dot`` on the rows the
    groups hold, in its dtype, to two ulps of the largest value (the
    sums run in another order); rows past them are the caller's."""
    want = lax.ragged_dot(rows, w, sizes)
    got = moe_grouped(rows, w, sizes, impl="interpret")
    assert got.shape == want.shape and got.dtype == want.dtype
    held_rows = int(np.asarray(sizes).sum())
    want = np.asarray(want[:held_rows], np.float32)
    got = np.asarray(got[:held_rows], np.float32)
    ulp = float(jnp.finfo(rows.dtype).eps)
    scale = max(float(np.abs(want).max(initial=0.0)), 1e-30)
    np.testing.assert_array_less(np.abs(got - want), 2 * ulp * scale + 1e-30)


@pytest.mark.parametrize("sizes", [
    [5, 0, 0, 19, 1, 0, 7, 0],          # empty groups between and after
    [0, 0, 40, 0],                       # one group holds every pair
    [0, 0, 0, 0],                        # no pair is held
], ids=["empty-groups", "one-group", "none-held"])
def test_groups_empty_or_whole(sizes):
    _assert_matches_ragged_dot(*_operands(40, 128, 256, sizes))


def test_pairs_past_the_held_experts():
    """Rows past ``sum(sizes)`` (the pairs whose expert lives elsewhere,
    sorted last) are not computed: the held rows match, whatever lies
    after them."""
    _assert_matches_ragged_dot(*_operands(96, 128, 128, [7, 0, 12, 3]))


@pytest.mark.parametrize("m", [130, 300])
def test_pair_count_not_a_multiple_of_the_tile(m):
    """More rows than a tile and no multiple of it: the last tile is
    partial, and groups straddle tile edges."""
    assert m % row_tile(m)
    sizes = [m // 5, 0, m // 3, m // 7, m - m // 5 - m // 3 - m // 7 - 2]
    _assert_matches_ragged_dot(*_operands(m, 128, 128, sizes, seed=m))


def test_trinity_decode_proportion():
    """Trinity's decode call: 16 rows x top-4 over 256 experts, 32 held,
    a quarter of a pair an expert: 64 rows, one tile, most groups
    empty."""
    rng = np.random.default_rng(3)
    ids = rng.integers(0, 256, size=64)
    flat = np.where(ids < 32, ids, 32)
    sizes = np.bincount(flat, minlength=33)[:32]
    assert row_tile(64) == 64 and (sizes == 0).sum() > 16
    _assert_matches_ragged_dot(*_operands(64, 384, 384, sizes))


@pytest.mark.parametrize("h,f,experts,count,top_k,rows", [
    (288, 112, 64, 64, 8, 24),     # Mellum2's 2304 x 896, an eighth
    (512, 96, 72, 9, 10, 24),      # Granite's 4096 x 768
    (384, 384, 256, 32, 4, 48),    # Trinity's 3072 x 3072
], ids=["mellum2", "granite", "trinity"])
def test_cut_configurations_widths_scaled_down(h, f, experts, count, top_k,
                                               rows):
    """Each expert cell's three products at its widths over eight, in
    the cell's proportion of pairs to held experts: gate and up
    ``(h, f)``, down ``(f, h)``."""
    rng = np.random.default_rng(h)
    ids = np.argsort(-rng.normal(size=(rows, experts)), 1)[:, :top_k]
    flat = np.where(ids < count, ids, count).reshape(-1)
    sizes = np.bincount(flat, minlength=count + 1)[:count]
    x, w_up, sizes = _operands(rows * top_k, h, f, sizes, seed=1)
    _assert_matches_ragged_dot(x, w_up, sizes)
    y, w_down, _ = _operands(rows * top_k, f, h, sizes, seed=2)
    _assert_matches_ragged_dot(y, w_down, sizes)


def test_xla_impl_is_ragged_dot_itself():
    rows, w, sizes = _operands(40, 64, 128, [10, 0, 25])
    np.testing.assert_array_equal(
        np.asarray(moe_grouped(rows, w, sizes, impl="xla"), np.float32),
        np.asarray(lax.ragged_dot(rows, w, sizes), np.float32))


def test_visits_walk_the_tiles_the_groups_cover():
    """Row tiles of 16 over 80 rows, groups of 0, 20, 0, 12, 30 rows and
    18 past them: group 1 covers tiles 0 and 1, group 3 tile 1, group 4
    tiles 2 and 3; in group order, a shared tile once a group, an empty
    group and the rows past the groups none."""
    offsets, group, tile, count = visits(
        jnp.asarray([0, 20, 0, 12, 30], jnp.int32), 80, 16)
    n = int(count)
    assert list(np.asarray(offsets)) == [0, 0, 20, 20, 32, 62]
    assert list(np.asarray(group)[:n]) == [1, 1, 3, 4, 4]
    assert list(np.asarray(tile)[:n]) == [0, 1, 1, 2, 3]
    assert group.shape == tile.shape == (80 // 16 + 5 - 1,)


def test_tiles_follow_the_shape():
    """128 rows a visit, fewer in 16-row steps for a smaller call; a
    whole column tile of weights a block up to 8 MiB: Mellum2's and
    Granite's experts whole, Trinity's in three."""
    assert [row_tile(m) for m in (8192, 20480, 300, 64, 4, 130)] == [
        ROW_TILE, 128, 128, 64, 16, 128]
    assert column_tile(2304, 896, 2) == 896
    assert column_tile(896, 2304, 2) == 2304
    assert column_tile(4096, 768, 2) == 768
    assert column_tile(768, 4096, 2) == 4096
    assert column_tile(3072, 3072, 2) == 1024


def test_one_body_a_shape_in_an_unrolled_program():
    """The jitted entry point: three layers of three products lower two
    functions, one a distinct shape (gate and up share one), not nine
    (the set-up guard at full size is ``test_tpu_compile``'s)."""
    def layers(x, w_gate, w_up, w_down, sizes):
        for i in range(3):
            with jax.named_scope(f"layer_{i}"):
                gate = moe_grouped(x, w_gate, sizes, impl="interpret")
                up = moe_grouped(x, w_up, sizes, impl="interpret")
                x = moe_grouped(gate * up, w_down, sizes, impl="interpret")
        return x

    s = jax.ShapeDtypeStruct
    text = jax.jit(layers).lower(
        s((64, 128), jnp.bfloat16), s((4, 128, 256), jnp.bfloat16),
        s((4, 128, 256), jnp.bfloat16), s((4, 256, 128), jnp.bfloat16),
        s((4,), jnp.int32)).as_text()
    assert len(re.findall(r"func\.func private @_moe_grouped", text)) == 2


@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_grouped_form_matches_the_dense_form(monkeypatch, impl):
    """``grouped_experts`` (through the kernel, or ``ragged_dot``) and
    ``dense_experts`` on the same input: the same sum over each row's
    chosen held experts, to the grouped form's bf16 rounding of its
    intermediate products (two ulps of a row's largest value)."""
    monkeypatch.setenv("APEX_TPU_IMPL", impl)
    _backend.default_impl.cache_clear()
    try:
        rng = np.random.default_rng(11)
        n, h, f, experts, k, first, count = 24, 128, 96, 32, 4, 8, 16
        x = jnp.asarray(rng.normal(size=(n, h)), jnp.bfloat16)
        ids = jnp.asarray(np.argsort(-rng.normal(size=(n, experts)), 1)
                          [:, :k], jnp.int32)
        weights = jnp.asarray(rng.dirichlet(np.ones(k), size=n),
                              jnp.float32)
        w = [jnp.asarray(rng.normal(size=s) / np.sqrt(s[1]), jnp.bfloat16)
             for s in ((count, h, f), (count, h, f), (count, f, h))]
        args = (x, weights, ids, *w, (first, count), jnp.bfloat16)
        grouped = np.asarray(held.grouped_experts(*args), np.float32)
        dense = np.asarray(held.dense_experts(*args), np.float32)
    finally:
        _backend.default_impl.cache_clear()
    scale = np.abs(dense).max(axis=1, keepdims=True)
    assert (np.abs(grouped - dense) <= 2 * 2.0 ** -7 * scale).all()
    assert np.abs(dense).max() > 0
