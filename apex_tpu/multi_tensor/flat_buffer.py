"""Flat parameter-space machinery.

The reference packs lists of tensor pointers into kernel-arg structs and
iterates chunks on-device (ref: csrc/multi_tensor_apply.cuh:16-147,
apex/multi_tensor_apply/multi_tensor_apply.py:3-30). On TPU the equivalent
is a *flat parameter space*: a pytree of arrays is packed into one 1-D
buffer (each leaf padded to a fixed alignment), fused Pallas kernels run
over the whole buffer in lane-aligned tiles, and per-tensor semantics
(LAMB trust ratios, per-tensor L2 norms) come from a static tile->leaf map
instead of device-side pointer tables.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

# Default per-leaf alignment in elements. 2048 = (16 sublanes x 128 lanes),
# the minimum bf16 tile, so any tile size that divides the alignment never
# straddles a leaf boundary for fp32 or bf16 buffers.
DEFAULT_ALIGN = 2048


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True)
class FlatSpace:
    """Static layout of a pytree flattened into one aligned 1-D buffer."""

    treedef: Any
    shapes: tuple[tuple[int, ...], ...]
    dtypes: tuple[Any, ...]
    offsets: tuple[int, ...]
    sizes: tuple[int, ...]
    padded_sizes: tuple[int, ...]
    total: int
    align: int

    @classmethod
    def create(cls, tree: Any, align: int = DEFAULT_ALIGN) -> "FlatSpace":
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        shapes, dtypes, offsets, sizes, padded = [], [], [], [], []
        off = 0
        for leaf in leaves:
            size = int(np.prod(leaf.shape)) if leaf.shape else 1
            psize = _round_up(max(size, 1), align)
            shapes.append(tuple(leaf.shape))
            dtypes.append(jnp.dtype(leaf.dtype))
            offsets.append(off)
            sizes.append(size)
            padded.append(psize)
            off += psize
        return cls(
            treedef=treedef,
            shapes=tuple(shapes),
            dtypes=tuple(dtypes),
            offsets=tuple(offsets),
            sizes=tuple(sizes),
            padded_sizes=tuple(padded),
            total=off,
            align=align,
        )

    # -- packing -----------------------------------------------------------

    @property
    def num_leaves(self) -> int:
        return len(self.shapes)

    def pack(self, tree: Any, dtype: Optional[Any] = None) -> jax.Array:
        """Flatten ``tree`` into one 1-D buffer, optionally casting leaves.

        Padding elements are zero — harmless for every fused op in this
        package (updates of zero state stay zero; norms add zero).
        """
        leaves = self.treedef.flatten_up_to(tree)
        dt = jnp.dtype(dtype) if dtype is not None else None
        parts = []
        for leaf, size, psize in zip(leaves, self.sizes, self.padded_sizes):
            flat = jnp.ravel(leaf)
            if dt is not None:
                flat = flat.astype(dt)
            if psize != size:
                flat = jnp.pad(flat, (0, psize - size))
            parts.append(flat)
        return jnp.concatenate(parts) if len(parts) > 1 else parts[0]

    def unpack(self, buf: jax.Array, dtype: str = "original") -> Any:
        """Inverse of :meth:`pack`.

        ``dtype='original'`` casts each leaf back to its recorded dtype;
        ``dtype='buffer'`` keeps the buffer dtype (e.g. fp32 master values).
        """
        leaves = []
        for shape, ldt, off, size in zip(
            self.shapes, self.dtypes, self.offsets, self.sizes
        ):
            leaf = jax.lax.slice(buf, (off,), (off + size,)).reshape(shape)
            if dtype == "original":
                leaf = leaf.astype(ldt)
            leaves.append(leaf)
        return self.treedef.unflatten(leaves)

    def zeros(self, dtype=jnp.float32) -> jax.Array:
        return jnp.zeros((self.total,), dtype=dtype)

    def grad_fn(self, loss_fn, *, has_aux: bool = False,
                with_value: bool = False):
        """Differentiate a pytree-taking loss straight into this space.

        ``loss_fn(params, *args, **kwargs)`` sees the unpacked tree;
        the returned function takes the FLAT master buffer and yields
        gradients already in the flat layout (unpack's transpose
        scatters every leaf cotangent back into one buffer), so a
        training loop never pays the per-leaf pack that
        ``FlatFusedOptimizer.step`` performs on tree gradients —
        feed the result to ``step_flat`` / ``make_train_step``::

            flat_grad = state.space.grad_fn(loss_fn)
            g = flat_grad(state.master, batch)
            new_params, state = opt.step_flat(state, g)

        ``with_value=True`` returns ``jax.value_and_grad`` of the same
        flat function; ``has_aux`` passes through to the transform.
        """
        def flat_loss(master, *args, **kwargs):
            # the optimizer's share of a step (telemetry.compiled.PARTS):
            # the cast and unpack of the master and, as its transpose,
            # every leaf's gradient into the one flat buffer
            with jax.named_scope("optimizer"):
                tree = self.unpack(master)
            return loss_fn(tree, *args, **kwargs)

        if with_value:
            return jax.value_and_grad(flat_loss, has_aux=has_aux)
        return jax.grad(flat_loss, has_aux=has_aux)

    # -- per-tensor maps ---------------------------------------------------

    def tile_leaf_ids(self, tile_elems: int) -> np.ndarray:
        """Static int32 map from tile index -> leaf index.

        Requires the alignment to be a multiple of ``tile_elems`` so no
        tile straddles two leaves (the TPU analog of the reference's
        block->(tensor, chunk) table, csrc/multi_tensor_apply.cuh:98-116).
        """
        if self.align % tile_elems:
            raise ValueError(
                f"tile_elems={tile_elems} must divide align={self.align} "
                "for per-tensor fused ops"
            )
        ids = np.empty((self.total // tile_elems,), dtype=np.int32)
        for i, (off, psize) in enumerate(zip(self.offsets, self.padded_sizes)):
            ids[off // tile_elems : (off + psize) // tile_elems] = i
        return ids

    def elementwise_leaf_values(self, per_leaf: jax.Array) -> jax.Array:
        """Broadcast a (num_leaves,) array to a (total,) buffer (XLA path)."""
        reps = np.asarray(self.padded_sizes)
        return jnp.repeat(per_leaf, reps, total_repeat_length=self.total)


def pack_like(space: FlatSpace, trees: Sequence[Any], dtype=jnp.float32):
    """Pack several congruent pytrees with one layout."""
    return [space.pack(t, dtype=dtype) for t in trees]


# ---------------------------------------------------------------------------
# Segmented layout (single-pass per-tensor optimizers)
# ---------------------------------------------------------------------------


@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True, eq=False)
class SegmentMeta:
    """Static companion of a segment-aligned :class:`FlatSpace`.

    A *segment* is ``seg_elems`` consecutive buffer elements. The
    layout guarantees every leaf either (a) lives entirely inside one
    segment ("small", recorded in the per-subtile ``slot_ids`` map) or
    (b) starts at a segment boundary and owns a whole number of
    segments ("large", listed in ``large``). This is what lets a
    single kernel pass compute per-tensor norms *and* apply them: each
    small leaf's reduction is segment-local (apex_tpu/multi_tensor/
    segmented.py), while the few large leaves fall back to the
    two-stage path over their contiguous slices.

    Registered static (like :class:`FlatSpace`) so it can ride inside
    optimizer state: the meta then travels WITH the space it was built
    against, and a second ``init()`` over a different tree can never
    pair an old state with fresh metadata.
    """

    seg_elems: int                     # elements per segment
    n_segments: int                    # total // seg_elems
    small_segments: tuple[int, ...]    # segment indices the kernel sweeps
    # (n_small_segments, seg_elems // align) local slot per subtile,
    # -1 for padding subtiles
    slot_ids: Any
    # (n_small_segments, max_slots) global leaf index per slot, -1 pad
    slot_leaf: Any
    max_slots: int
    # (leaf_idx, start_elem, padded_elems) per large leaf
    large: tuple[tuple[int, int, int], ...]
    # kernel-schedule knobs resolved at init time (multi_tensor/
    # segmented.py): whether p stays resident in scratch, and the
    # update-term stash dtype (by name — dtypes aren't hashable)
    stash_p: bool = True
    u_dtype_name: str = "float32"

    # static-pytree contract: hashable + comparable despite the numpy
    # id-map fields (frozen dataclass __eq__/__hash__ would choke on
    # them). The key is cached: as a static node inside optimizer state
    # it gets hashed at EVERY jitted-step cache lookup, and the id maps
    # are megabytes at large model scales.
    def _key(self):
        cached = getattr(self, "_key_cache", None)
        if cached is None:
            cached = (
                self.seg_elems, self.n_segments, self.small_segments,
                self.max_slots, self.large, self.stash_p,
                self.u_dtype_name,
                np.asarray(self.slot_ids).tobytes(),
                np.asarray(self.slot_leaf).tobytes(),
            )
            object.__setattr__(self, "_key_cache", cached)
        return cached

    def __eq__(self, other):
        if self is other:
            return True
        return (type(other) is SegmentMeta
                and self._key() == other._key())

    def __hash__(self):
        cached = getattr(self, "_hash_cache", None)
        if cached is None:
            cached = hash(self._key())
            object.__setattr__(self, "_hash_cache", cached)
        return cached


# Conservative per-core VMEM the segmented kernel may spend on scratch:
# the guide's planning number is ~16 MB/core total, and the kernel also
# needs its streamed blocks (double-buffered, ~3.5 MB at the default
# chunk). Overridable for chips with more VMEM.
DEFAULT_SEG_VMEM_BUDGET = 10 * 1024 * 1024


def default_seg_elems(total_estimate: int,
                      cap: Optional[int] = None,
                      chunk: int = 512 * 128,
                      scratch_bytes_per_elem: int = 8) -> int:
    """Segment size matched to the workload: ~1/8 of the buffer
    (so small models get several segments and tiny CPU tests don't
    drag a mostly-padding segment through interpret mode), clamped to
    [1 chunk, cap] and rounded to a chunk multiple. The default cap is
    sized so the kernel's VMEM scratch (``scratch_bytes_per_elem`` *
    seg_elems — 8 for the fp32 u+p stash pair) fits the budget; a
    too-large segment is not a slowdown but a Mosaic compile failure."""
    if cap is None:
        cap = DEFAULT_SEG_VMEM_BUDGET // max(scratch_bytes_per_elem, 1)
    want = max(chunk, min(cap, total_estimate // 8))
    return ((want + chunk - 1) // chunk) * chunk


def segmented_space(
    tree: Any,
    seg_elems: Optional[int] = None,
    max_slots: int = 512,
    align: int = DEFAULT_ALIGN,
) -> tuple[FlatSpace, SegmentMeta]:
    """A :class:`FlatSpace` whose leaf padding is segment-aware, plus
    the static segment metadata.

    Leaf order is preserved (pack/unpack stay the plain concatenate /
    slice of FlatSpace); padding grows only where a small leaf would
    straddle a segment boundary, where a segment would exceed
    ``max_slots`` leaves, or before/after a large leaf (which must own
    whole segments). Overhead is bounded by one segment per large leaf
    plus boundary slack — negligible at real model scales.
    """
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if seg_elems is None:
        est = sum(
            _round_up(int(np.prod(l.shape)) if l.shape else 1, align)
            for l in leaves)
        seg_elems = default_seg_elems(est)
    if seg_elems % align:
        raise ValueError(f"seg_elems {seg_elems} must be a multiple of "
                         f"the alignment {align}")
    shapes, dtypes, offsets, sizes, padded = [], [], [], [], []
    # per-small-leaf (segment, start, padded, leaf_idx); large list
    small_places, large_places = [], []
    off = 0
    seg_fill_slots = 0
    for idx, leaf in enumerate(leaves):
        size = int(np.prod(leaf.shape)) if leaf.shape else 1
        base_pad = _round_up(max(size, 1), align)
        if base_pad > seg_elems:
            start = _round_up(off, seg_elems)
            psize = _round_up(base_pad, seg_elems)
            large_places.append((idx, start, psize))
            seg_fill_slots = max_slots    # force a fresh segment next
        else:
            start = off
            seg_room = seg_elems - (start % seg_elems)
            if base_pad > seg_room or seg_fill_slots >= max_slots:
                start = _round_up(off, seg_elems)
                seg_fill_slots = 0
            if start % seg_elems == 0:
                seg_fill_slots = 0
            small_places.append((start // seg_elems, start, base_pad, idx))
            seg_fill_slots += 1
            psize = base_pad
        # absorb any gap into the PREVIOUS leaf's padding so FlatSpace
        # offsets (cumulative padded sizes) stay consistent
        if offsets and start != off:
            padded[-1] += start - off
        elif start != off:
            raise AssertionError("first leaf cannot need a gap")
        shapes.append(tuple(leaf.shape))
        dtypes.append(jnp.dtype(leaf.dtype))
        offsets.append(start)
        sizes.append(size)
        padded.append(psize)
        off = start + psize
    total = _round_up(off, seg_elems)
    if padded:
        padded[-1] += total - off

    space = FlatSpace(
        treedef=treedef, shapes=tuple(shapes), dtypes=tuple(dtypes),
        offsets=tuple(offsets), sizes=tuple(sizes),
        padded_sizes=tuple(padded), total=total, align=align,
    )

    n_segments = total // seg_elems
    sub_per_seg = seg_elems // align
    large_segs = set()
    for _, start, psize in large_places:
        for s in range(start // seg_elems, (start + psize) // seg_elems):
            large_segs.add(s)
    small_segments = tuple(
        s for s in range(n_segments) if s not in large_segs)
    seg_pos = {s: i for i, s in enumerate(small_segments)}
    slot_ids = np.full((len(small_segments), sub_per_seg), -1, np.int32)
    slot_leaf = np.full((len(small_segments), max_slots), -1, np.int32)
    next_slot = {}
    for seg, start, psize, idx in small_places:
        row = seg_pos[seg]
        slot = next_slot.get(seg, 0)
        next_slot[seg] = slot + 1
        if slot >= max_slots:
            raise AssertionError("layout exceeded max_slots")
        slot_leaf[row, slot] = idx
        lo = (start % seg_elems) // align
        hi = lo + psize // align
        slot_ids[row, lo:hi] = slot
    used_slots = max(next_slot.values(), default=1)
    # trim the slot axis to the real maximum (rounded up for lanes)
    ms = max(8, int(_round_up(used_slots, 8)))
    slot_leaf = slot_leaf[:, :ms]
    meta = SegmentMeta(
        seg_elems=seg_elems, n_segments=n_segments,
        small_segments=small_segments, slot_ids=slot_ids,
        slot_leaf=slot_leaf, max_slots=ms,
        large=tuple(large_places),
    )
    return space, meta
