"""A lane's recurrent state in a pool of state slots: the one place
that says how it is reached. :func:`read_lanes` / :func:`write_lanes`
take the lanes' states of one layer out of a pool and put them back (a
slice a lane for the few lanes of a prefill call, one gather and one
scatter for many); both pools of ``models/ssm.py``'s mixer go through
them. :func:`ssm_step_by_slot` is one token of the Mamba-2 recurrence
for every lane of a decode batch on the lanes' states WHERE THEY LIE:
a kernel on a TPU, the accessors around ``ssm.ssm_step`` elsewhere.

``models/ssm.py``'s one-step form, ``S' = a S + (d x) (x) B`` and ``y =
S' C``, over a pool ``(slots + 1, state layers, H, P, N)``
(``serving/kv_cache.py``). Lane ``i``'s state is slot ``slots[i]``'s,
and a lane is not a slot: XLA reads the lanes' states out by a gather,
updates the copy, reads it again for ``y`` and writes it back by a
scatter, a 268 MB array written or read seven times a layer at the
benchmark's size (64 lanes of 4.19 MB: 3.3 ms a layer on a v5e; my chip
run, PR 35). The kernel here walks the slots instead: the grid is
(lanes, groups of heads), the block of the pool a grid step works on is
picked by ``slots`` (prefetched scalars), and the pool is aliased to
the result, so a state is read once and written once where it lies.

Dummy lanes all name the trash slot and follow one another through it,
in order; what it holds is nobody's. A ``fresh`` lane starts from zeros
whatever its slot held (``jnp.where`` selects: a NaN a quarantined
tenant left does not come through).

The kernel is named ``ssm_step`` in the compiled program, which is what
the benchmark's readers find it by (``ssm_state_pct``,
``ssm_step_roofline``).

:func:`selective_step_by_slot` is the same walk for Mamba-1's
recurrence (``models/ssm.py`` :class:`SelectiveMixer`), a decay for
each channel and state: ``S' = exp(Delta A) S + Delta x B`` and ``y =
S' C`` over a pool ``(slots + 1, state layers, E / 128, N, 128)``,
the channels minor-most, so that ``N`` (16) pads no tile. A grid step
is a lane's whole state of one layer (``SELECTIVE_GROUPS`` groups of
128 channels); ``exp(Delta A)`` is computed in the kernel. It is named
``ssm_step_selective``: the readers' ``^ssm_step`` and ``^ssm_`` find
it too.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu._backend import interpret_flag, resolve_impl

#: heads a grid step: a block of the pool is then (32, P, N), 1 MiB of
#: float32 at P 64 and N 128
HEADS = 32


#: the most lanes whose states a call reads and writes a slice a lane
#: (a prefill call's); more go by one gather and one scatter (a decode
#: call's 64 lanes of convolution rows)
SLICED_LANES = 8


def read_lanes(pool, layer: int, slots, fresh):
    """Layer ``layer`` of the lanes' slots out of a state pool
    ``(slots + 1, layers, ...)``: ``(b, ...)``, zeros for a lane that
    starts a sequence (``fresh``; ``jnp.where`` selects, so what a
    slot's last owner left, a NaN too, does not come through).

    Few lanes are read a slice a lane: one gather of a single lane is
    a slice to the TPU compiler, which then lays the whole pool out
    anew around it (2.45 GB copied there and back in a one-lane
    program; my chip run, PR 35). The barrier, here and in
    :func:`write_lanes`, keeps the caller's arithmetic out of the
    slice's and the update's fusions: fused with them, the order of
    dimensions it likes best becomes the whole pool's."""
    b, rest = slots.shape[0], pool.shape[2:]
    if b <= SLICED_LANES:
        rows = lax.optimization_barrier(jnp.concatenate([
            lax.dynamic_slice(pool, (slots[i], layer) + (0,) * len(rest),
                              (1, 1, *rest))[0] for i in range(b)]))
    else:
        rows = pool[slots, layer]
    keep = (~fresh).reshape((-1,) + (1,) * len(rest))
    return jnp.where(keep, rows, jnp.zeros((), rows.dtype))


def write_lanes(pool, layer: int, slots, new):
    """The lanes' new state ``(b, ...)`` into layer ``layer`` of their
    slots: an update in place a lane for few lanes
    (``kv_cache.append_kv``'s idiom), one scatter for many. Lanes that
    name one slot (dummies, the trash slot) leave any one's there."""
    new = new.astype(pool.dtype)
    if slots.shape[0] > SLICED_LANES:
        return pool.at[slots, layer].set(new)
    new = lax.optimization_barrier(new)
    for i in range(slots.shape[0]):
        pool = lax.dynamic_update_slice(
            pool, new[i][None, None],
            (slots[i], layer) + (0,) * (pool.ndim - 2))
    return pool


def _uses_kernel(pool, impl: str) -> bool:
    """The kernel takes a pool whose ``(P, N)`` planes are whole
    float32 tiles and whose heads divide into groups; XLA any other."""
    _, _, H, P, N = pool.shape
    return (impl != "xla" and pool.dtype == jnp.float32
            and H % HEADS == 0 and P % 8 == 0 and N % 128 == 0)


def _kernel(slots_ref, fresh_ref, a_ref, dx_ref, b_ref, c_ref, s_ref,
            y_ref, out_ref):
    """One lane's group of heads. ``a_ref`` / ``dx_ref`` / ``y_ref``
    hold (P, heads): a head is a lane of the vector registers, so a
    head's column broadcasts along N without being turned."""
    del slots_ref                      # the index maps' alone
    fresh = fresh_ref[pl.program_id(0)] != 0
    B, C = b_ref[...], c_ref[...]                          # (1, N)
    for h in range(s_ref.shape[0]):
        S = s_ref[h]                                       # (P, N)
        S = jnp.where(fresh, jnp.zeros_like(S), S)
        new = a_ref[:, h:h + 1] * S + dx_ref[:, h:h + 1] * B
        out_ref[h] = new
        y_ref[:, h:h + 1] = jnp.sum(new * C, axis=1, keepdims=True)


def ssm_step_by_slot(pool, slots, fresh, layer: int, x, dt, A, B, C, D, *,
                     impl=None):
    """``pool`` (slots + 1, layers, H, P, N); ``slots`` (b,) int32 and
    ``fresh`` (b,) bool a lane; ``layer`` the state layer (static);
    ``x`` (b, H, P), ``dt`` (b, H) after the softplus, ``A`` / ``D``
    (H,), ``B`` / ``C`` (b, N), float32. Returns ``(y (b, H, P)
    float32, the pool with the lanes' slots of ``layer`` advanced one
    token)``; the pool must be donated for the update to be in place.
    """
    from apex_tpu.models import ssm

    impl = resolve_impl(impl)
    if not _uses_kernel(pool, impl):
        y, new = ssm.ssm_step(read_lanes(pool, layer, slots, fresh),
                              x, dt, A, B, C, D)
        return y, write_lanes(pool, layer, slots, new)
    b, H, P = x.shape
    N = pool.shape[-1]
    groups = H // HEADS

    def turned(t):                     # (b, H, P) -> (b, groups, P, HEADS)
        return t.reshape(b, groups, HEADS, P).transpose(0, 1, 3, 2)

    a = jnp.broadcast_to(jnp.exp(dt * A)[:, :, None], (b, H, P))
    by_lane = pl.BlockSpec((None, None, P, HEADS),
                           lambda i, g, slots, fresh: (i, g, 0, 0))
    row = pl.BlockSpec((None, 1, N), lambda i, g, slots, fresh: (i, 0, 0))
    state = pl.BlockSpec((None, None, HEADS, P, N),
                         lambda i, g, slots, fresh: (slots[i], layer, g, 0, 0))
    y, pool = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b, groups),
            in_specs=[by_lane, by_lane, row, row, state],
            out_specs=[by_lane, state]),
        out_shape=[jax.ShapeDtypeStruct((b, groups, P, HEADS), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operands count the prefetched scalars: the pool -> the pool
        input_output_aliases={6: 1},
        # dummy lanes follow one another through the trash slot: in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret_flag(impl),
        name="ssm_step",
    )(slots.astype(jnp.int32), fresh.astype(jnp.int32), turned(a),
      turned(dt[:, :, None] * x), B[:, None, :], C[:, None, :], pool)
    y = y.transpose(0, 1, 3, 2).reshape(b, H, P)
    return y + D[None, :, None] * x, pool


#: groups of 128 channels a grid step of the selective step: Jamba's
#: 5120 channels whole, 320 KiB of a lane's state in and out a step
SELECTIVE_GROUPS = 40


def _selective_kernel(slots_ref, fresh_ref, dx_ref, bc_ref, a_ref, s_ref,
                      y_ref, out_ref):
    """One lane's groups of channels. ``dx_ref`` (2, groups, 128) holds
    ``Delta`` and ``x`` a channel, ``bc_ref`` (N, 2) the token's ``B``
    and ``C`` as columns: a column broadcasts along the channels."""
    del slots_ref                      # the index maps' alone
    fresh = fresh_ref[pl.program_id(0)] != 0
    B, C = bc_ref[:, 0:1], bc_ref[:, 1:2]                  # (N, 1)
    for g in range(s_ref.shape[0]):
        S = s_ref[g]                                       # (N, 128)
        S = jnp.where(fresh, jnp.zeros_like(S), S)
        d, x = dx_ref[0, g:g + 1, :], dx_ref[1, g:g + 1, :]
        new = jnp.exp(d * a_ref[g]) * S + B * (d * x)
        out_ref[g] = new
        y_ref[g:g + 1, :] = jnp.sum(new * C, axis=0, keepdims=True)


def selective_step_by_slot(pool, slots, fresh, layer: int, x, delta, A, B,
                           C, *, impl=None):
    """``pool`` (slots + 1, layers, E/128, N, 128); ``slots`` (b,) int32
    and ``fresh`` (b,) bool a lane; ``layer`` the state layer (static);
    ``x``, ``delta`` (b, E), ``A`` (E, N), ``B`` / ``C`` (b, N),
    float32. Returns ``(sum_n S' C (b, E) float32, the pool with the
    lanes' slots of ``layer`` advanced one token)``; ``D x`` and the
    gate are the caller's. The pool must be donated for the update to
    be in place."""
    from apex_tpu.models import ssm

    impl = resolve_impl(impl)
    if impl == "xla":
        y, new = ssm.selective_step(read_lanes(pool, layer, slots, fresh),
                                    x, delta, A, B, C)
        return y, write_lanes(pool, layer, slots, new)
    b, E = x.shape
    _, _, G, N, L = pool.shape
    groups = math.gcd(G, SELECTIVE_GROUPS)
    f32 = jnp.float32
    dx = jnp.stack([delta, x], axis=1).astype(f32).reshape(b, 2, G, L)
    bc = jnp.stack([B, C], axis=-1).astype(f32)            # (b, N, 2)
    lanes = pl.BlockSpec((None, groups, L),
                         lambda i, g, slots, fresh: (i, g, 0))
    state = pl.BlockSpec((None, None, groups, N, L),
                         lambda i, g, slots, fresh: (slots[i], layer, g, 0, 0))
    y, pool = pl.pallas_call(
        _selective_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b, G // groups),
            in_specs=[
                pl.BlockSpec((None, 2, groups, L),
                             lambda i, g, slots, fresh: (i, 0, g, 0)),
                pl.BlockSpec((None, N, 2),
                             lambda i, g, slots, fresh: (i, 0, 0)),
                pl.BlockSpec((groups, N, L),
                             lambda i, g, slots, fresh: (g, 0, 0)),
                state],
            out_specs=[lanes, state]),
        out_shape=[jax.ShapeDtypeStruct((b, G, L), f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operands count the prefetched scalars: the pool -> the pool
        input_output_aliases={5: 1},
        # dummy lanes follow one another through the trash slot: in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret_flag(impl),
        name="ssm_step_selective",
    )(slots.astype(jnp.int32), fresh.astype(jnp.int32), dx, bc,
      ssm.decay_table(A.astype(f32)), pool)
    return y.reshape(b, E), pool


__all__ = ["SELECTIVE_GROUPS", "read_lanes", "selective_step_by_slot",
           "ssm_step_by_slot", "write_lanes"]
