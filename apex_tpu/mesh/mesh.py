"""One process-global named mesh — the GSPMD substrate (ROADMAP item 1).

The Megatron-style substrate (`transformer/parallel_state.py`) reaches
scale through EXPLICIT collectives: `shard_map` over its mesh, layers
calling `psum`/`all_gather` by axis name. This module is the
TPU-idiomatic replacement (SNIPPETS.md [1], docs/mesh.md): ONE named
mesh with `batch`/`model`/`pipe` axes, `NamedSharding`s on the arrays,
`with_sharding_constraint` hints inside the model
(:mod:`~apex_tpu.mesh.annotate`), and the XLA compiler inserting every
collective — the same model code runs unmodified from one chip to a
full slice.

Three guarantees this module owns:

- **1-chip identity** — on a 1-device mesh (or no mesh at all) every
  entry point (`shard_params` / `shard_state` / `shard_batch`, the
  annotate hooks) returns its input object unchanged, so every
  pre-mesh test path and compiled program is untouched byte for byte.
- **one substrate for execution** — since PR-16 the mesh owns every
  execution schedule (training, pipeline, serving); what remains of
  `parallel_state` is trace-scoped explicit-collective layers
  (shard_map tensor/context parallelism) whose axes only bind inside
  their own traces, so the two may coexist in one process — the old
  ``SubstrateConflictError`` exclusivity check is gone with the
  legacy pipeline runtime that needed it.
- **one compile, published** — :class:`MeshTrainStep` runs the
  fused-optimizer hot path as ONE donated GSPMD program per layout,
  with compile-plane observation (PR-6 tracker discipline) and its
  real input/output shardings published through
  ``telemetry.sharding.publish_shardings`` (the module's first
  in-repo producer).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Sequence, Tuple

import numpy as np

BATCH_AXIS = "batch"
MODEL_AXIS = "model"
PIPE_AXIS = "pipe"

#: outer -> inner; model innermost so the latency-critical axis rides
#: ICI-adjacent devices (the same discipline parallel_state applies to
#: its "tensor" axis)
MESH_AXES = (BATCH_AXIS, PIPE_AXIS, MODEL_AXIS)


# module-level state, the parallel_state._MESH shape
_MESH: Optional[Any] = None


def mesh_initialized() -> bool:
    return _MESH is not None


def current_mesh():
    if _MESH is None:
        raise RuntimeError(
            "GSPMD mesh is not initialized (call mesh.initialize_mesh "
            "first)")
    return _MESH


def mesh_size() -> int:
    """Total devices of the live mesh (1 when none is live — the
    degenerate case every identity guarantee keys on)."""
    if _MESH is None:
        return 1
    return int(math.prod(_MESH.devices.shape))


def axis_sizes() -> Dict[str, int]:
    """``{axis: size}`` of the live mesh (all 1s when none is live)."""
    if _MESH is None:
        return {a: 1 for a in MESH_AXES}
    return {str(a): int(s) for a, s in zip(_MESH.axis_names,
                                           _MESH.devices.shape)}


def initialize_mesh(batch: Optional[int] = None, model: int = 1,
                    pipe: int = 1, *,
                    devices: Optional[Sequence] = None):
    """Build (and arm) the process-global GSPMD mesh.

    ``batch`` defaults to ``n_devices // (model * pipe)`` so the
    common call is ``initialize_mesh(model=2)``. A 1-device mesh is a
    legal, fully-supported degenerate case: every sharding becomes a
    no-op and the annotate hooks stay disarmed.
    """
    global _MESH
    import jax
    from jax.sharding import Mesh

    devs = list(devices if devices is not None else jax.devices())
    world = len(devs)
    model, pipe = int(model), int(pipe)
    if model < 1 or pipe < 1:
        raise ValueError(f"axis sizes must be >= 1 (model={model}, "
                         f"pipe={pipe})")
    if batch is None:
        if world % (model * pipe):
            raise ValueError(
                f"device count {world} not divisible by "
                f"model({model}) x pipe({pipe})")
        batch = world // (model * pipe)
    batch = int(batch)
    if batch * model * pipe != world:
        raise ValueError(
            f"batch({batch}) x model({model}) x pipe({pipe}) != "
            f"device count {world}")
    shape = (batch, pipe, model)
    if devices is None:
        # topology-aware on TPU (model innermost on ICI neighbours); a
        # plain reshape on backends with no topology. An error here is
        # the caller's to see.
        from jax.experimental import mesh_utils

        arr = mesh_utils.create_device_mesh(
            shape, devices=devs, allow_split_physical_axes=True)
    else:
        # the caller's own list, in the caller's order
        arr = np.asarray(devs).reshape(shape)
    _MESH = Mesh(arr, MESH_AXES)
    return _MESH


def destroy_mesh() -> None:
    global _MESH
    _MESH = None


# -- ShardingPlan ----------------------------------------------------------


def _named(mesh, spec):
    from jax.sharding import NamedSharding

    return NamedSharding(mesh, spec)


@dataclasses.dataclass(frozen=True)
class ShardingPlan:
    """How one model's arrays lie on one mesh: a PartitionSpec per
    param leaf, the batch spec, and replicated flat optimizer state.

    Every ``shard_*`` entry point is IDENTITY (returns the argument
    object itself) on a 1-device mesh — the degenerate case that keeps
    every existing single-chip path untouched."""

    mesh: Any
    param_specs: Any                      # pytree of PartitionSpec
    batch_spec: Any                       # PartitionSpec for (b, ...) arrays

    @property
    def n_devices(self) -> int:
        return int(math.prod(self.mesh.devices.shape))

    def is_identity(self) -> bool:
        return self.n_devices <= 1

    def param_shardings(self) -> Any:
        """NamedSharding per param leaf (spec-tree shaped)."""
        import jax
        from jax.sharding import PartitionSpec as P

        return jax.tree.map(lambda s: _named(self.mesh, s),
                            self.param_specs,
                            is_leaf=lambda x: isinstance(x, P))

    def shard_params(self, params: Any) -> Any:
        """``device_put`` the param tree onto its plan shardings;
        identity on a 1-device mesh."""
        if self.is_identity():
            return params
        import jax

        return jax.tree.map(jax.device_put, params,
                            self.param_shardings())

    def shard_state(self, state: Any) -> Any:
        """Commit a :class:`~apex_tpu.optimizers.fused.FlatOptState`'s
        buffers (master + slots + counters) REPLICATED on the mesh —
        the flat 1-D packing interleaves leaves, so the fused update
        stays a local program and data parallelism comes from the
        batch axis alone. Identity on a 1-device mesh."""
        if self.is_identity():
            return state
        import jax
        from jax.sharding import PartitionSpec as P

        rep = _named(self.mesh, P())
        return jax.tree.map(lambda x: jax.device_put(x, rep), state)

    def shard_batch(self, batch: Any) -> Any:
        """Commit a batch-major array (or pytree of them) split on the
        ``batch`` axis; identity on a 1-device mesh."""
        if self.is_identity():
            return batch
        import jax

        sh = _named(self.mesh, self.batch_spec)
        return jax.tree.map(lambda x: jax.device_put(x, sh), batch)

    def detail(self) -> Dict[str, Any]:
        """JSON-able summary for bench records / flight bundles."""
        import jax
        from jax.sharding import PartitionSpec as P

        leaves = jax.tree.leaves(
            self.param_specs, is_leaf=lambda x: isinstance(x, P))
        sharded = sum(1 for s in leaves if any(a is not None for a in s))
        return {
            "mesh": axis_sizes() if self.mesh is _MESH else {
                str(a): int(s) for a, s in zip(self.mesh.axis_names,
                                               self.mesh.devices.shape)},
            "n_devices": self.n_devices,
            "batch_spec": str(tuple(self.batch_spec)),
            "param_leaves": len(leaves),
            "param_leaves_sharded": sharded,
        }


def plan_gpt(params: Any, *, mesh=None) -> ShardingPlan:
    """The GPT :class:`ShardingPlan`: the existing `gpt_param_specs`
    tree with the legacy ``tensor`` axis renamed to this mesh's
    ``model`` axis (the two substrates shard the SAME dims — column
    kernels on the output dim, row kernels on the input dim, the
    embedding on vocab), batch-major inputs split on ``batch``."""
    import jax
    from jax.sharding import PartitionSpec as P

    from apex_tpu.models.gpt import gpt_param_specs
    from apex_tpu.transformer.parallel_state import TENSOR_AXIS

    mesh = mesh if mesh is not None else current_mesh()

    def rename(spec):
        return P(*[MODEL_AXIS if a == TENSOR_AXIS else a for a in spec])

    specs = jax.tree.map(rename, gpt_param_specs(params),
                         is_leaf=lambda x: isinstance(x, P))
    return ShardingPlan(mesh=mesh, param_specs=specs,
                        batch_spec=P(BATCH_AXIS))


# module-level entry points (the ISSUE-named surface); thin delegates
# so callers without a plan object in hand still get the identity
# guarantee documented in one place


def shard_params(plan: ShardingPlan, params: Any) -> Any:
    return plan.shard_params(params)


def shard_state(plan: ShardingPlan, state: Any) -> Any:
    return plan.shard_state(state)


def shard_batch(plan: ShardingPlan, batch: Any) -> Any:
    return plan.shard_batch(batch)


# -- the mesh-sharded train step -------------------------------------------


class MeshTrainStep:
    """The fused train step over a :class:`ShardingPlan`: flat-space
    value_and_grad + ``opt.step_flat`` as ONE donated jitted program,
    batch split on the mesh's ``batch`` axis, flat optimizer state
    replicated, activations laid out by the model's annotate hints —
    XLA inserts the gradient all-reduce (there is no explicit
    collective anywhere on this path).

    On an identity plan the program is the plain single-device jit —
    no in/out shardings, byte-identical to an unsharded step. Compile
    discipline follows ``optimizers/train_step.py``: new layouts are
    observed (``fn="mesh_train_step"``) and labeled, hits are one dict
    lookup; each new layout also publishes its compiled shardings
    (``telemetry.sharding``).
    """

    FN = "mesh_train_step"

    def __init__(self, model, optimizer, plan: ShardingPlan, *,
                 loss_fn=None, loss_has_aux: bool = False,
                 aux_sink=None):
        self.model = model
        self.opt = optimizer
        self.plan = plan
        if loss_fn is None:
            from apex_tpu.models.gpt import gpt_loss_fn

            def loss_fn(p, tokens, labels):
                return gpt_loss_fn(model.apply(p, tokens), labels)

        self._loss_fn = loss_fn
        # loss_has_aux: loss_fn returns (scalar, aux_pytree) — the MoE
        # path's per-step stats. The public step signature stays
        # (new_state, loss); aux lands on self.last_aux and is pushed
        # through aux_sink(aux) each step (telemetry/moe.py's
        # publish_moe_step is the standard sink).
        self._has_aux = bool(loss_has_aux)
        self._aux_sink = aux_sink
        self.last_aux: Any = None
        self._jitted: Dict[Any, Any] = {}      # per-FlatSpace program
        self._seen: set = set()                # (space, seg_meta, shape)
        self._step_count = 0                   # for the moe_* fault plan

    def init(self, params: Any) -> Any:
        """``opt.init`` then commit the state per the plan (identity
        on 1 device).

        Params are re-replicated BEFORE the flat pack: the eager
        ravel+pad+concatenate in ``FlatSpace.pack`` mis-propagates
        mixed per-leaf shardings (the uneven concat can land as an
        unreduced replica sum), so packing must always see one
        uniform layout. The master is replicated on the mesh anyway
        (``ShardingPlan.shard_state``); tensor-parallel layouts come
        from the plan's activation/param constraints inside the jitted
        program, not from the packed buffer."""
        if not self.plan.is_identity():
            import jax
            from jax.sharding import PartitionSpec as P

            rep = _named(self.plan.mesh, P())
            params = jax.tree.map(lambda x: jax.device_put(x, rep),
                                  params)
        return self.plan.shard_state(self.opt.init(params))

    def _update(self, state, g):
        """``opt.step_flat`` on the flat state (call while tracing the
        step). The state and the reduced gradient are replicated, so
        where the fused update is a kernel every device runs it whole
        on its own copy, as an island (``annotate.on_shards``)."""
        from jax.sharding import PartitionSpec as P

        from apex_tpu.mesh import annotate

        return annotate.on_shards(
            lambda state, g: self.opt.step_flat(state, g)[1],
            self.opt.impl, (P(), P()), P())(state, g)

    def _jit_for(self, state) -> Any:
        key = (state.space, state.seg_meta)
        jitted = self._jitted.get(key)
        if jitted is not None:
            return jitted
        import jax

        vg = state.space.grad_fn(self._loss_fn, with_value=True,
                                 has_aux=self._has_aux)

        # the update is the ``optimizer`` part of the step
        # (telemetry.compiled.PARTS), with the unpack of the master and
        # its transpose, the gradient into the flat space
        # (``FlatSpace.grad_fn``)
        if self._has_aux:
            def step(state, tokens, labels):
                (loss, aux), g = vg(state.master, tokens, labels)
                with jax.named_scope("optimizer"):
                    return self._update(state, g), loss, aux
        else:
            def step(state, tokens, labels):
                loss, g = vg(state.master, tokens, labels)
                with jax.named_scope("optimizer"):
                    return self._update(state, g), loss

        if self.plan.is_identity():
            jitted = jax.jit(step, donate_argnums=(0,))
        else:
            from jax.sharding import PartitionSpec as P

            rep = _named(self.plan.mesh, P())
            bsh = _named(self.plan.mesh, self.plan.batch_spec)
            state_sh = jax.tree.map(lambda _: rep, state)
            # pinned in/out state shardings: the donated carry keeps
            # the exact layout across steps, so the hot loop never
            # re-lays-out (and AOT-published shardings stay honest).
            # The aux pytree (when present) replicates — rep is a
            # legal pytree prefix for the whole subtree.
            out_sh = ((state_sh, rep, rep) if self._has_aux
                      else (state_sh, rep))
            jitted = jax.jit(step, donate_argnums=(0,),
                             in_shardings=(state_sh, bsh, bsh),
                             out_shardings=out_sh)
        self._jitted[key] = jitted
        return jitted

    def lower(self, state, tokens, labels):
        """``jax.jit(...).lower`` passthrough (``TrainStep.lower``'s
        sibling): the step program's memory analysis and text without
        running it. Shapes (``jax.ShapeDtypeStruct``) do for every
        argument, so nothing need be resident to ask."""
        return self._jit_for(state).lower(state, tokens, labels)

    def _apply_moe_faults(self, state):
        """The moe_router_collapse / moe_expert_dead drills
        (resilience/faults.py): edit the flat master through the
        space's unpack/pack round trip BEFORE the dispatch — data-level
        poisoning through the REAL routing program, the
        decode_nonfinite idiom applied to params. No-op (the same
        state object) off-plan."""
        from apex_tpu.resilience import faults as _faults

        inj = _faults.active()
        if inj is None:
            return state
        collapse = inj.should_collapse_router(self._step_count)
        dead = inj.dead_expert()
        if not collapse and dead is None:
            return state
        from apex_tpu.moe import poison_moe_params

        tree = poison_moe_params(state.space.unpack(state.master),
                                 collapse=collapse, dead_expert=dead)
        master = state.space.pack(tree, dtype=state.master.dtype)
        if not self.plan.is_identity():
            import jax
            from jax.sharding import PartitionSpec as P

            master = jax.device_put(master, _named(self.plan.mesh, P()))
        return state._replace(master=master)

    def _signature(self, state, tokens) -> Dict[str, Any]:
        return {"fn": self.FN, "space_total": int(state.space.total),
                "num_leaves": int(state.space.num_leaves),
                "segmented": state.seg_meta is not None,
                "batch": int(tokens.shape[0]),
                "seq": int(tokens.shape[1]),
                "mesh": axis_sizes() if self.plan.mesh is _MESH else {
                    str(a): int(s) for a, s in
                    zip(self.plan.mesh.axis_names,
                        self.plan.mesh.devices.shape)}}

    def step(self, state, tokens, labels) -> Tuple[Any, Any]:
        """One fused step; ``state`` is DONATED — rebind it. Returns
        ``(new_state, loss)`` (aux, when the loss carries one, lands
        on ``last_aux`` / the aux sink — the loop signature never
        changes)."""
        import jax.numpy as jnp

        state = self._apply_moe_faults(state)
        self._step_count += 1
        tokens = self.plan.shard_batch(jnp.asarray(tokens, jnp.int32))
        labels = self.plan.shard_batch(jnp.asarray(labels, jnp.int32))
        jitted = self._jit_for(state)
        key = (state.space, state.seg_meta, tuple(tokens.shape))
        if key not in self._seen:
            # compile-plane cold path (train_step.py discipline): the
            # signature is observed, the compiling dispatch labeled,
            # and — the sharding plane's producer — the program's REAL
            # compiled shardings are introspected and published before
            # the run (before: the donated state is still live here).
            self._seen.add(key)
            from apex_tpu.telemetry import compiled as _compiled
            from apex_tpu.telemetry import sharding as _sharding

            signature = self._signature(state, tokens)
            _compiled.observe(self.FN, signature)
            _compiled.register_program("jit_step", signature, jitted,
                                       (state, tokens, labels))
            _sharding.publish_shardings(_sharding.jitted_shardings(
                jitted, state, tokens, labels, fn=self.FN))
            with _compiled.label(self.FN):
                out = jitted(state, tokens, labels)
        else:
            out = jitted(state, tokens, labels)
        if self._has_aux:
            new_state, loss, aux = out
            self.last_aux = aux
            if self._aux_sink is not None:
                self._aux_sink(aux)
            return new_state, loss
        return out

    __call__ = step


def make_mesh_train_step(model, optimizer, plan: ShardingPlan, *,
                         loss_fn=None, loss_has_aux: bool = False,
                         aux_sink=None) -> MeshTrainStep:
    """Build the GSPMD train step for ``model`` over ``plan``.

    ``loss_fn(params, tokens, labels) -> scalar`` defaults to the GPT
    LM loss (``gpt_loss_fn(model.apply(params, tokens), labels)``).
    With ``loss_has_aux=True`` the loss returns ``(scalar, aux)`` and
    each step deposits ``aux`` on ``step.last_aux`` / pushes it
    through ``aux_sink`` (the MoE stats path, docs/moe.md) — the loop
    signature stays ``state, loss = step(...)``. The returned step's
    ``init`` commits the optimizer state per the plan and
    ``step``/``__call__`` donates it."""
    return MeshTrainStep(model, optimizer, plan, loss_fn=loss_fn,
                         loss_has_aux=loss_has_aux, aux_sink=aux_sink)


__all__ = [
    "BATCH_AXIS",
    "MODEL_AXIS",
    "PIPE_AXIS",
    "MESH_AXES",
    "MeshTrainStep",
    "ShardingPlan",
    "axis_sizes",
    "current_mesh",
    "destroy_mesh",
    "initialize_mesh",
    "make_mesh_train_step",
    "mesh_initialized",
    "mesh_size",
    "plan_gpt",
    "shard_batch",
    "shard_params",
    "shard_state",
]
