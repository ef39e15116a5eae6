"""Zero-copy fused train-step path.

``make_train_step`` compiles the whole optimizer hot path — loss-scale
unscale, optional global-grad-norm clipping, nonfinite detection, the
fused update, and the loss-scaler schedule — into ONE jitted,
donation-aware program:

- ``state.master`` and every slot buffer are donated
  (``donate_argnums``), so the update runs in-place and the compiled
  step never holds two master-sized copies of the optimizer state:
  peak optimizer HBM drops by ~the master+slots size vs a non-donating
  step (the jit-level analog of the reference's in-place
  ``multi_tensor_*`` updates, csrc/multi_tensor_apply.cuh:44-147).
- grad unscale (``1/loss_scale``) never materializes an unscaled
  buffer: on kernel impls it folds into the update kernel's scalar; on
  the XLA impl the multiply fuses into the update's read of ``g``.
  Nonfinite detection rides the update kernel's existing
  ``check_finite`` sweep.
- when clipping is on, the global-grad-norm reduction is ONE fused
  read (`multi_tensor.fused_unscale_l2norm`) whose result feeds
  FusedLAMB's in-update clip through the ``global_grad_norm``
  plumbing — no second norm pass inside the update, and no unscale
  sweep before it. (An exact pre-moment clip fundamentally needs one
  read of the gradients before the update consumes them — the clip
  factor is a global function of every element — so the clip path is
  update+1 passes; everything else is zero-extra-pass.)
- per-tensor grad norms (``with_grad_norm=True``) ride the update
  itself: the segmented kernel's phase-0 one-hot accumulators
  and the two-stage stage-1 sumsq partials (multi_tensor/segmented.py,
  multi_tensor/ops.py) — monitoring at zero extra HBM passes.

Compiled steps are cached in an eviction-free dict keyed on the
optimizer + options (jax.jit then specializes per static FlatSpace
layout); `step_cache_stats` — also surfaced through
``apex_tpu.profiler`` — reports factory and per-layout hit/miss
counts. With the compile tracker armed
(``telemetry.compiled.enable()``), every NEW layout additionally
publishes its abstract signature — a second distinct signature is a
re-trace and emits a ``recompile`` event with the signature diff; the
XLA compile duration lands in ``compile_ms{fn="train_step"}`` (see
docs/observability.md "compile & memory plane").

HBM-accesses-per-element budget this path targets (see
docs/train_step.md): optax per-leaf fusion ~7, the classic two-stage
flat schedule ~10, segmented one-pass kernel + this step path 7
(8 with ``seg_stash_p=False``; +1 when clipping).

Composition with amp (the reference's ``with amp.scale_loss(...)``
flow, apex/amp/handle.py:16-158)::

    scaler = amp.make_scaler(amp_state.properties)
    step = make_train_step(opt, scaler=scaler)
    flat_grad = state.space.grad_fn(
        lambda p, scale: loss_fn(p) * scale)      # grads of SCALED loss
    g = flat_grad(state.master, scaler_state.loss_scale)
    state, scaler_state, aux = step(state, g, scaler_state)
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from apex_tpu._backend import resolve_impl
from apex_tpu.amp.scaler import LossScaler, ScalerState
from apex_tpu.multi_tensor.ops import fused_unscale_l2norm
from apex_tpu.optimizers.fused import FlatFusedOptimizer, FlatOptState, FusedLAMB


class StepAux(NamedTuple):
    """Per-step diagnostics returned by a fused train step."""

    found_inf: jax.Array                      # f32 {0,1}
    grad_norm: Optional[jax.Array] = None     # unscaled global L2 norm
    grad_norm_per_tensor: Optional[jax.Array] = None
    loss_scale: Optional[jax.Array] = None    # scale the step unscaled by
    # (n_buffers, num_leaves) uint32 bitwise checksums of the UPDATED
    # master + slots, computed in-jit every ``fingerprint_every``
    # applied steps (zeros off-boundary); None when the option is off
    state_fingerprint: Optional[jax.Array] = None


class TrainStep:
    """A compiled, donation-aware optimizer step (see module docstring).

    Call as ``step(state, flat_grads)`` or, with a scaler,
    ``step(state, flat_grads, scaler_state)``. Returns
    ``(new_state, aux)`` / ``(new_state, new_scaler_state, aux)``.
    The state (and scaler state) arguments are DONATED: rebind them to
    the returned values — the passed-in buffers are dead after the call.
    """

    def __init__(self, opt: FlatFusedOptimizer, scaler: Optional[LossScaler],
                 jitted, body, options: Dict[str, Any]):
        self.opt = opt
        self.scaler = scaler
        self.options = dict(options)
        self._jitted = jitted
        self._body = body
        self._chained: Dict[int, Any] = {}
        self._layouts = set()
        self._telemetry = None          # host-side StepTimeline, or None

    def _track(self, state: FlatOptState) -> bool:
        """Record the static layout; True when it is NEW on this step
        (the dispatch about to run will trace+compile)."""
        key = (state.space, state.seg_meta)
        if key in self._layouts:
            _STATS["layout_hits"] += 1
            return False
        self._layouts.add(key)
        _STATS["layout_misses"] += 1
        return True

    def _signature(self, state: FlatOptState) -> Dict[str, Any]:
        """JSON-able abstract signature of this dispatch — what the
        compile tracker diffs to name a re-trace (a changed static
        option, a new flat-space layout)."""
        import hashlib

        space = state.space
        sig: Dict[str, Any] = dict(self.options)
        # the padded total alone can collide across layouts (alignment
        # rounds small leaves up to the same quantum): a digest of the
        # per-leaf shapes/dtypes pins the layout exactly
        sig.update(space_total=int(space.total),
                   num_leaves=int(space.num_leaves),
                   space_digest=hashlib.sha256(
                       repr((space.shapes, tuple(map(str, space.dtypes)),
                             space.offsets)).encode()).hexdigest()[:12],
                   segmented=state.seg_meta is not None,
                   scaler=self.scaler is not None)
        return sig

    def __call__(self, state: FlatOptState, flat_grads: jax.Array,
                 scaler_state: Optional[ScalerState] = None, *, lr=None):
        new_layout = self._track(state)
        if self.scaler is not None:
            if scaler_state is None:
                raise ValueError(
                    "this step was built with a scaler; pass scaler_state")
            args = (state, flat_grads, scaler_state, lr)
        elif scaler_state is not None:
            raise ValueError(
                "this step was built without a scaler; drop scaler_state "
                "or rebuild with make_train_step(opt, scaler=...)")
        else:
            args = (state, flat_grads, lr)
        if new_layout:
            # compile-plane cold path: this dispatch traces+compiles a
            # new static layout. Publish the signature (recompile
            # detection — a second distinct signature of "train_step"
            # is a re-trace) and label the dispatch so the monitoring
            # bridge attributes the XLA compile duration. Both are
            # no-ops (one module-global read) with no tracker armed;
            # layout HITS never reach this branch, so the hot loop —
            # and the `disabled is step` / <1%-overhead contracts —
            # are untouched.
            from apex_tpu.telemetry import compiled as _compiled

            signature = self._signature(state)
            _compiled.observe("train_step", signature)
            _compiled.register_program("jit_jitted", signature,
                                       self._jitted, args)
            with _compiled.label("train_step"):
                return self._dispatch(args)
        return self._dispatch(args)

    def _dispatch(self, args):
        tl = self._telemetry
        try:
            if tl is None:
                return self._jitted(*args)
            # host-side only: the jitted program (and its argument list)
            # is byte-identical with telemetry on or off. sync=True
            # blocks on the outputs so the span covers device execution,
            # not dispatch. This "step" span is also the goodput
            # ledger's productive/rework feed: record_span pushes it
            # through the timeline's span observer when one is armed
            # (telemetry.goodput.enable), at the cost of one
            # module-global check here.
            t0 = tl.clock()
            outs = self._jitted(*args)
            if tl.sync:
                jax.block_until_ready(outs)
            tl.record_span("step", t0, tl.clock() - t0,
                           category="train_step")
            return outs
        except Exception as e:
            # flight recorder: an exception escaping the fused-step
            # dispatch is the canonical "the run just died" moment —
            # dump the black box before re-raising. The armed-recorder
            # check is one module-global read; with nothing armed this
            # except block costs one try frame on the happy path and
            # nothing else. Host-local trigger: the peers may be
            # mid-step, so no collective is issued.
            from apex_tpu.telemetry import flight as _flight

            if _flight.get_recorder() is not None:
                _flight.notify("train_step_exception", error=e,
                               fleet=False)
            raise

    def with_telemetry(self, telemetry) -> "TrainStep":
        """A view of this step whose dispatches are timed into the
        given :class:`~apex_tpu.telemetry.StepTimeline` as ``"step"``
        spans. The view SHARES the compiled program, chained cache,
        and layout tracking — nothing recompiles. A None or disabled
        timeline returns ``self`` unchanged, so the disabled path is
        exactly the un-instrumented path (tools/check_telemetry.sh
        holds its overhead to <1%)."""
        if telemetry is None or not getattr(telemetry, "enabled", True):
            return self
        view = TrainStep(self.opt, self.scaler, self._jitted, self._body,
                         self.options)
        view._chained = self._chained
        view._layouts = self._layouts
        view._telemetry = telemetry
        return view

    def lower(self, state: FlatOptState, flat_grads: jax.Array,
              scaler_state: Optional[ScalerState] = None, lr=None):
        """``jax.jit(...).lower`` passthrough — lets tests assert the
        compiled program's input/output aliasing (donation) and memory
        analysis without running a step."""
        if self.scaler is not None:
            return self._jitted.lower(state, flat_grads, scaler_state, lr)
        return self._jitted.lower(state, flat_grads, lr)

    def with_options(self, **overrides) -> "TrainStep":
        """A sibling step for the same optimizer/scaler with some
        factory options changed, served from the factory cache — e.g.
        the resilience watchdog's norm-reporting variant
        ``step.with_options(with_grad_norm=True)`` (its per-tensor
        norms ride the segmented kernel's phase-0 accumulators, so a
        monitored step costs zero extra HBM passes)."""
        base = {k: self.options[k] for k in
                ("max_grad_norm", "skip_if_nonfinite", "donate_grads",
                 "with_grad_norm", "fingerprint_every")}
        unknown = set(overrides) - set(base)
        if unknown:
            raise ValueError(
                f"unknown train-step options {sorted(unknown)}; "
                f"overridable: {sorted(base)}")
        base.update(overrides)
        step = make_train_step(self.opt, scaler=self.scaler, **base)
        return step.with_telemetry(self._telemetry)

    def chained(self, k: int):
        """``k`` steps of this train step as ONE jitted call — the same
        fused body iterated in a ``lax.fori_loop`` with the carry
        donated. This is the bench timing protocol (it amortizes
        per-dispatch overhead so schedule comparisons measure memory
        traffic, not Python), and the right shape for drivers that
        checkpoint every k steps.

        Without a scaler: ``fn(state, flat_grads, lr=None) ->
        (state, found_sum)``. With one: ``fn((state, scaler_state),
        flat_grads, lr=None) -> ((state, scaler_state), found_sum)``.
        The same gradient buffer feeds every iteration.
        """
        k = int(k)
        cached = self._chained.get(k)
        if cached is not None:
            return cached
        body = self._body
        if self.scaler is not None:
            @functools.partial(jax.jit, donate_argnums=(0,))
            def chained(carry, flat_grads, lr=None):
                def it(_, c):
                    state, ss, probe = c
                    state, ss, aux = body(state, flat_grads, ss, lr)
                    return state, ss, probe + aux.found_inf
                state, ss, probe = jax.lax.fori_loop(
                    0, k, it, (*carry, jnp.float32(0.0)))
                return (state, ss), probe
        else:
            @functools.partial(jax.jit, donate_argnums=(0,))
            def chained(state, flat_grads, lr=None):
                def it(_, c):
                    state, probe = c
                    state, aux = body(state, flat_grads, None, lr)
                    return state, probe + aux.found_inf
                state, probe = jax.lax.fori_loop(
                    0, k, it, (state, jnp.float32(0.0)))
                return state, probe
        self._chained[k] = chained
        return chained


# eviction-free: a training process uses a handful of (optimizer,
# options) pairs and each compiled step is precious — evicting one
# silently re-pays a multi-second XLA compile mid-training
_FACTORY_CACHE: Dict[tuple, TrainStep] = {}
_STATS = {"factory_hits": 0, "factory_misses": 0,
          "layout_hits": 0, "layout_misses": 0}


def step_cache_stats() -> Dict[str, int]:
    """Counters for the train-step compile cache (also exposed as
    ``apex_tpu.profiler.optimizer_step_cache_stats``): ``factory_*``
    count `make_train_step` lookups, ``layout_*`` count distinct static
    layouts seen by the cached steps (each layout miss is one XLA
    compile; hits reuse it)."""
    return {
        **_STATS,
        "factories": len(_FACTORY_CACHE),
        "layouts": sum(len(s._layouts) for s in _FACTORY_CACHE.values()),
    }


def clear_step_cache() -> None:
    _FACTORY_CACHE.clear()
    for k in _STATS:
        _STATS[k] = 0


def _scaler_key(scaler: Optional[LossScaler]):
    if scaler is None:
        return None
    return (scaler.dynamic, scaler._static_scale, scaler.init_scale,
            scaler.scale_factor, scaler.scale_window,
            scaler.min_loss_scale, scaler.max_loss_scale)


def make_train_step(
    opt: FlatFusedOptimizer,
    *,
    scaler: Optional[LossScaler] = None,
    max_grad_norm: Optional[float] = None,
    skip_if_nonfinite: Optional[bool] = None,
    donate_grads: bool = False,
    with_grad_norm: bool = False,
    fingerprint_every: Optional[int] = None,
    telemetry=None,
) -> TrainStep:
    """Build (or fetch from the cache) the fused train step for ``opt``.

    - ``scaler``: a :class:`~apex_tpu.amp.LossScaler`; the step then
      takes (and donates) a ``ScalerState``, unscales the gradients of
      the SCALED loss in the update sweep itself, and advances the
      scale schedule — the whole ``with amp.scale_loss(...)`` flow in
      one compiled program.
    - ``max_grad_norm``: global-grad-norm clip. Default: the
      optimizer's own ``max_grad_norm`` (FusedLAMB) or off. For
      FusedLAMB the precomputed norm feeds the in-update clip
      (``global_grad_norm``); for other optimizers the clip factor
      folds into the update's ``grad_scale``. Passing a value that
      conflicts with a FusedLAMB's own configured clip raises.
    - ``skip_if_nonfinite``: gate the update on overflow. Default True
      when a scaler is given (the amp dynamic-scaling contract), else
      False.
    - ``donate_grads``: also donate the grad buffer (safe only when the
      caller doesn't reuse it — e.g. grads recomputed every step).
    - ``with_grad_norm``: report per-tensor + global raw-grad norms in
      the aux, reduced inside the update kernels (FusedLAMB; other
      optimizers pay one fused norm read).
    - ``fingerprint_every``: every N applied steps (``count % N == 0``)
      compute per-leaf BITWISE uint32 checksums of the updated master +
      slot buffers inside the jitted program and report them in
      ``aux.state_fingerprint`` (zeros off-boundary — the reduction is
      gated behind ``lax.cond`` so non-boundary steps pay nothing).
      This is the resilience consistency guard's divergence primitive
      (apex_tpu/resilience/guard.py): fingerprints ride the donating
      program itself, so cross-replica integrity monitoring never
      copies or re-reads the state on the host.
    - ``telemetry``: a :class:`~apex_tpu.telemetry.StepTimeline`; each
      dispatch is then timed into it as a ``"step"`` span, HOST-SIDE
      ONLY — telemetry is never part of the factory cache key, adds no
      arguments to the jitted program, and changes no compiled byte
      (the PR-1 donation/bit-match contracts hold verbatim). ``None``
      or a disabled timeline returns the exact cached step object:
      the disabled path IS the un-instrumented path.

    The returned :class:`TrainStep` donates ``state`` (master + every
    slot buffer) and ``scaler_state``; callers MUST rebind both to the
    returned values.
    """
    if fingerprint_every is not None:
        fingerprint_every = int(fingerprint_every)
        if fingerprint_every <= 0:
            raise ValueError(
                f"fingerprint_every must be positive, got {fingerprint_every}")
    key = (id(opt), _scaler_key(scaler), max_grad_norm,
           skip_if_nonfinite, donate_grads, with_grad_norm,
           fingerprint_every)
    cached = _FACTORY_CACHE.get(key)
    if cached is not None:
        _STATS["factory_hits"] += 1
        return cached.with_telemetry(telemetry)
    _STATS["factory_misses"] += 1

    is_lamb = isinstance(opt, FusedLAMB)
    opt_mgn = float(getattr(opt, "max_grad_norm", 0.0) or 0.0)
    mgn = opt_mgn if max_grad_norm is None else float(max_grad_norm)
    if is_lamb and opt_mgn > 0.0 and mgn != opt_mgn:
        raise ValueError(
            f"max_grad_norm={mgn} conflicts with the optimizer's own "
            f"max_grad_norm={opt_mgn}; configure the clip in ONE place")
    # LAMB with its own clip consumes the precomputed norm through
    # global_grad_norm; everything else folds the clip into grad_scale
    internal_clip = is_lamb and opt_mgn > 0.0
    generic_clip = mgn > 0.0 and not internal_clip
    skip = (scaler is not None) if skip_if_nonfinite is None \
        else bool(skip_if_nonfinite)
    impl = resolve_impl(opt.impl)
    # On the XLA impl the unscale is the literal multi_tensor_scale
    # multiply (XLA fuses it into the update's read of g), so the fused
    # step is BITWISE equal to the composed separate-pass reference; on
    # kernel impls the unscale folds into the kernel's grad_scale
    # scalar instead (pallas_call boundaries block producer fusion).
    xla_compose = impl == "xla"

    def body(state, flat_grads, scaler_state, lr):
        g = flat_grads.astype(jnp.float32)
        loss_scale = (scaler_state.loss_scale
                      if scaler_state is not None else None)
        extra_found = None
        grad_scale = 1.0
        ggn = None                      # norm handed to LAMB's clip
        unscaled_norm = None            # aux-reported global grad norm

        if xla_compose and loss_scale is not None:
            inv = 1.0 / loss_scale
            g = g * inv                 # fuses into the update's read
            # multi_tensor_scale's convention: flag non-finite OUTPUTS
            extra_found = jnp.where(
                jnp.all(jnp.isfinite(g)), 0.0, 1.0).astype(jnp.float32)
        elif loss_scale is not None:
            grad_scale = loss_scale     # in-kernel fold (g / grad_scale)

        # LAMB's with_grad_norm rides the update kernel itself, so the
        # only cases that pay this one fused read are clipping (the
        # clip factor must exist BEFORE the update consumes g) and
        # norm-reporting for optimizers without an in-kernel reduction
        if internal_clip or generic_clip or (with_grad_norm
                                             and not is_lamb):
            # one fused read of g; on the xla branch g is already the
            # unscaled buffer, on kernel branches the unscale is a
            # scalar op on the reduced value
            norm, norm_found = fused_unscale_l2norm(
                g, inv_scale=1.0, impl=impl)
            unscaled_norm = (norm / loss_scale
                             if loss_scale is not None and not xla_compose
                             else norm)
            extra_found = (norm_found if extra_found is None
                           else jnp.maximum(extra_found, norm_found))
            if internal_clip:
                # FusedLAMB divides the given norm by grad_scale itself
                ggn = norm
            elif generic_clip:
                clip = jnp.maximum(unscaled_norm / mgn, 1.0)
                grad_scale = (grad_scale * clip
                              if loss_scale is not None and not xla_compose
                              else clip)

        outs = opt.step_flat(
            state, g, lr=lr, grad_scale=grad_scale,
            skip_if_nonfinite=skip,
            global_grad_norm=ggn, extra_found_inf=extra_found,
            with_grad_norm=with_grad_norm and is_lamb)
        gnorm_pt = None
        if with_grad_norm and is_lamb:
            _, new_state, gnorm_pt = outs
            # kernels reduce the RAW streamed gradient; under a scaler
            # on kernel impls that is the scaled one — unscale the
            # reduced values (scalar work)
            if loss_scale is not None and not xla_compose:
                gnorm_pt = gnorm_pt / loss_scale
            unscaled_norm = jnp.sqrt(jnp.sum(gnorm_pt * gnorm_pt))
        else:
            _, new_state = outs

        fingerprint = None
        if fingerprint_every is not None:
            from apex_tpu.resilience.guard import state_fingerprint_array

            def _fp(st):
                return state_fingerprint_array(st)

            def _zeros(st):
                n_bufs = 1 + len(st.slots)
                return jnp.zeros((n_bufs, st.space.num_leaves), jnp.uint32)

            at_boundary = jnp.equal(
                jax.lax.rem(new_state.count,
                            jnp.int32(fingerprint_every)), 0)
            fingerprint = jax.lax.cond(at_boundary, _fp, _zeros, new_state)

        aux = StepAux(found_inf=new_state.found_inf,
                      grad_norm=unscaled_norm,
                      grad_norm_per_tensor=gnorm_pt,
                      loss_scale=loss_scale,
                      state_fingerprint=fingerprint)
        if scaler_state is not None:
            new_scaler_state = scaler.update(scaler_state,
                                             new_state.found_inf)
            return new_state, new_scaler_state, aux
        return new_state, aux

    if scaler is not None:
        donate = (0, 2) + ((1,) if donate_grads else ())

        @functools.partial(jax.jit, donate_argnums=donate)
        def jitted(state, flat_grads, scaler_state, lr):
            # the whole program is the ``optimizer`` part of a step
            # (telemetry.compiled.PARTS)
            with jax.named_scope("optimizer"):
                return body(state, flat_grads, scaler_state, lr)
    else:
        donate = (0,) + ((1,) if donate_grads else ())

        @functools.partial(jax.jit, donate_argnums=donate)
        def jitted(state, flat_grads, lr):
            with jax.named_scope("optimizer"):
                return body(state, flat_grads, None, lr)

    step = TrainStep(opt, scaler, jitted, body, options=dict(
        max_grad_norm=mgn, skip_if_nonfinite=skip, impl=impl,
        donate_grads=donate_grads, with_grad_norm=with_grad_norm,
        fingerprint_every=fingerprint_every))
    _FACTORY_CACHE[key] = step
    return step.with_telemetry(telemetry)


__all__ = ["make_train_step", "TrainStep", "StepAux",
           "step_cache_stats", "clear_step_cache"]
