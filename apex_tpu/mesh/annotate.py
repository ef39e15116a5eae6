"""GSPMD sharding hints for the model + serving planes.

Where the Megatron substrate inserts EXPLICIT collectives
(`reduce_from_tensor_parallel_region` after every row-parallel matmul),
this module inserts HINTS: `with_sharding_constraint` pins on the
activations that tell XLA where the data lives, and the compiler picks
the collectives. The model code calls :func:`constrain_*` helpers that
are exact identity (return the argument object) unless a >1-device
GSPMD mesh is armed — so the single-chip paths and the legacy
explicit-collective path (inside a `shard_map` axis) are untouched.

Serving side: :func:`shard_params_for_serving` commits a GPT
checkpoint model-sharded (column kernels split on the output dim, row
kernels on the input dim — the same dims the legacy substrate shards)
and :func:`shard_kv_pool` splits the paged KV pool on its ``kv_heads``
dim, so `prefill`/`prefill_chunk`/`decode` run with every attention
head's KV resident on the chip that owns the head. Verified
token-identical vs the unsharded engine by ``tools/check_mesh.sh``.
"""

from __future__ import annotations

from typing import Any, Optional


def mesh_active() -> bool:
    """True iff the annotate hooks should fire: a GSPMD mesh with more
    than one device is armed AND we are not inside a legacy
    explicit-collective region (a `shard_map`-traced tensor axis) —
    the substrate-exclusivity guarantee applied at trace time."""
    from apex_tpu.mesh import mesh as _mesh

    if not _mesh.mesh_initialized() or _mesh.mesh_size() <= 1:
        return False
    from apex_tpu.transformer.parallel_state import TENSOR_AXIS
    from apex_tpu.transformer.tensor_parallel.layers import _inside_axis

    return not _inside_axis(TENSOR_AXIS)


def constrain(x, *spec):
    """``with_sharding_constraint(x, P(*spec))`` on the current mesh
    when armed; identity otherwise. ``spec`` entries are axis names or
    None, one per array dim (trailing dims may be omitted).

    An axis whose size does not divide the array dim is DROPPED from
    the hint (shapes are static at trace time) — e.g. a 2-sequence
    serving micro-batch on a 4-way ``batch`` axis stays replicated
    instead of failing the GSPMD divisibility check; the remaining
    dims keep their pins."""
    if not mesh_active():
        return x
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from apex_tpu.mesh import mesh as _mesh

    sizes = _mesh.axis_sizes()
    fitted = [
        a if (a is None or x.shape[i] % sizes.get(a, 1) == 0) else None
        for i, a in enumerate(spec)]
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(_mesh.current_mesh(), P(*fitted)))


def on_shards(fn, impl, in_specs, out_specs):
    """``fn`` — a function that holds a Pallas kernel — as a per-shard
    island under the armed mesh.

    The TPU compiler cannot partition a Mosaic kernel: on more than
    one device it lowers only inside a ``shard_map`` that is manual
    over every mesh axis ("Mosaic kernels cannot be automatically
    partitioned"). So where a >1-device mesh is armed and ``impl``
    resolves to a kernel, ``fn`` runs under ``jax.shard_map`` with the
    given ``PartitionSpec``s (one per positional argument, a pytree
    argument taking one as a prefix; ``out_specs`` shaped like the
    result) and XLA moves the data into that layout and back — the
    local island under the one mesh. Everywhere else (no mesh, one
    device, ``impl="xla"``, inside a legacy ``shard_map`` axis) ``fn``
    comes back as it is, so those programs do not change.

    An axis that does not divide a dim it is named for is dropped from
    every spec, as in :func:`constrain`: that dim stays whole on each
    shard, which any function whose shards are independent tolerates."""
    from apex_tpu._backend import resolve_impl

    if resolve_impl(impl) == "xla" or not mesh_active():
        return fn
    import jax
    from jax.sharding import PartitionSpec as P

    from apex_tpu.mesh import mesh as _mesh

    mesh = _mesh.current_mesh()
    sizes = _mesh.axis_sizes()

    def island(*args):
        unfit = {a for spec, x in zip(in_specs, args)
                 for i, a in enumerate(spec)
                 if a is not None and x.shape[i] % sizes[a]}

        def fit(spec):
            return P(*[None if a in unfit else a for a in spec])

        def is_spec(x):
            return isinstance(x, P)

        return jax.shard_map(
            fn, mesh=mesh,
            in_specs=tuple(fit(spec) for spec in in_specs),
            out_specs=jax.tree.map(fit, out_specs, is_leaf=is_spec),
            check_vma=False)(*args)

    return island


# -- the model's hint vocabulary (seq-major (s, b, h) interior) ------------


def constrain_hidden(x):
    """An (s, b, hidden) activation between blocks: batch split,
    hidden replicated (the layout both column and row matmuls agree
    on)."""
    from apex_tpu.mesh.mesh import BATCH_AXIS

    return constrain(x, None, BATCH_AXIS, None)


def constrain_column_parallel(x):
    """An (s, b, local) activation AFTER a column-parallel matmul
    (qkv / fc1): the feature dim is split across ``model`` — this is
    the pin that lets XLA keep the matmul local instead of gathering
    the weight."""
    from apex_tpu.mesh.mesh import BATCH_AXIS, MODEL_AXIS

    return constrain(x, None, BATCH_AXIS, MODEL_AXIS)


def constrain_batch_major(x):
    """A (b, s, ...) boundary array (tokens, embedding output before
    the transpose): batch split on the data axis."""
    from apex_tpu.mesh.mesh import BATCH_AXIS

    return constrain(x, BATCH_AXIS)


def constrain_experts(x):
    """An array whose LEADING dim is experts (the ``w1``/``w2`` expert
    weights, the capacity path's (E, C, h) dispatch buffer): expert dim
    split on ``model``, everything else replicated. Pinning the
    dispatch buffer this way after the token-major scatter is what
    makes XLA lower the MoE dispatch/combine to the token all-to-all
    (docs/moe.md) — the GSPMD analog of the legacy shard_map
    ``lax.all_to_all`` in :class:`~apex_tpu.moe.ExpertParallelMLP`."""
    from apex_tpu.mesh.mesh import MODEL_AXIS

    return constrain(x, MODEL_AXIS)


def constrain_replicated(x):
    """Pin fully replicated. The dropless MoE group-GEMM's ragged
    per-expert groups align to NO mesh axis — GSPMD cannot partition
    ``lax.ragged_dot`` correctly when its operands carry sharding
    seeds (the global group sizes don't survive a split of either the
    expert or the token dim) — so its endpoints are pinned replicated
    and the capacity impl carries the EP scaling (docs/moe.md)."""
    return constrain(x)


def constrain_logits(x):
    """(s, b, vocab) logits: batch split, vocab replicated — the
    compiler inserts the row-parallel reduce upstream when the
    embedding/readout is vocab-split."""
    from apex_tpu.mesh.mesh import BATCH_AXIS

    return constrain(x, None, BATCH_AXIS, None)


# -- serving: model-sharded checkpoint + kv_heads-sharded pool -------------


def serving_param_shardings(params: Any, *, mesh=None) -> Any:
    """NamedSharding tree for a model-sharded serving checkpoint —
    the GPT plan's specs (legacy ``tensor`` dims renamed onto this
    mesh's ``model`` axis) on the given/current mesh."""
    from apex_tpu.mesh import mesh as _mesh

    plan = _mesh.plan_gpt(params, mesh=mesh)
    return plan.param_shardings()


def shard_params_for_serving(params: Any, *, mesh=None) -> Any:
    """Commit a GPT checkpoint model-sharded for serving; identity on
    a 1-device (or absent) mesh."""
    from apex_tpu.mesh import mesh as _mesh

    m = mesh if mesh is not None else (
        _mesh.current_mesh() if _mesh.mesh_initialized() else None)
    if m is None:
        return params
    plan = _mesh.plan_gpt(params, mesh=m)
    return plan.shard_params(params)


def shard_kv_pool(state: Any, *, mesh=None) -> Any:
    """Commit a paged `KVCacheState` (pools shaped
    ``(layers, blocks+1, block_size, kv_heads, head_dim)``) with the
    ``kv_heads`` dim split on the ``model`` axis — each chip holds the
    KV of exactly the heads whose qkv shard it owns, so decode
    attention stays collective-free until the output projection.
    Identity on a 1-device (or absent) mesh."""
    from apex_tpu.mesh import mesh as _mesh

    m = mesh if mesh is not None else (
        _mesh.current_mesh() if _mesh.mesh_initialized() else None)
    if m is None or int(m.devices.size) <= 1:
        return state
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from apex_tpu.mesh.mesh import MODEL_AXIS

    sh = NamedSharding(m, P(None, None, None, MODEL_AXIS, None))
    return jax.tree.map(lambda x: jax.device_put(x, sh), state)


__all__ = [
    "constrain",
    "constrain_batch_major",
    "constrain_column_parallel",
    "constrain_experts",
    "constrain_hidden",
    "constrain_logits",
    "constrain_replicated",
    "mesh_active",
    "on_shards",
    "serving_param_shardings",
    "shard_kv_pool",
    "shard_params_for_serving",
]
