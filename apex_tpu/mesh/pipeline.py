"""Mesh-native pipeline parallelism — schedules on the ``pipe`` axis.

ROADMAP item 2: the GSPMD replacement for the retired explicit-
collective pipeline (`transformer/pipeline_parallel/schedules.py`,
PR-16). The legacy path drove the ring with `shard_map` + `ppermute`;
here the SAME tick dataflow is expressed as pure array code that XLA
partitions over the mesh's ``pipe`` axis:

- the stage-boundary buffer is a ``(S, seq, mb, hidden)`` array
  constrained ``P("pipe", None, "batch", None)`` — row s lives on pipe
  group s;
- one tick applies every stage body via ``vmap`` over the stage dim
  (each pipe group computes exactly its row's stage) and
  ``jnp.roll(..., axis=0)`` rotates outputs to the next stage — on a
  >1 ``pipe`` axis XLA lowers that roll to a collective-permute, the
  same wire traffic the legacy ``ppermute`` moved, priced by
  ``telemetry.comms.wire_bytes("ppermute", ...)``;
- ``jax.grad`` of the tick scan IS the reverse pipeline (the roll's
  transpose is the reverse rotation), so forward and backward bubbles
  match the schedule without imperative per-rank control flow.

Schedules (:class:`PipelineSpec`):

- ``"gpipe"`` — all-forward-then-all-backward: the plain tick scan,
  M + S - 1 ticks, O(M) saved boundary state, bubble
  ``(S-1)/(M+S-1)``;
- ``"1f1b"`` — same tick order and IDENTICAL values (the 1F1B
  steady-state is a memory schedule, not a different dataflow), but
  the tick scan is chunk-checkpointed in S-tick chunks (the ported
  legacy ``_chunked_scan``) so saved state is ~O(S) ring buffers —
  the property the legacy depth-memory tests pinned;
- ``"interleaved_1f1b"`` — each stage hosts V model chunks (stage s
  holds global chunks ``{c*S + s}``); a microbatch crosses the ring V
  times on fine ticks, V*M + S - 1 of them, cutting the bubble to
  ``(S-1)/(V*M+S-1)`` — strictly below GPipe's on the same layout;
- ``"async_1f1b"`` — EXPERIMENTAL near-zero-bubble variant ("
  Layer-Parallel Training for Transformers", PAPERS.md): the boundary
  buffer is CARRIED ACROSS STEPS, so a step runs exactly M ticks with
  no fill/drain — steady-state bubble ~0 — at the price of truncated
  pipeline backprop (gradient contributions that cross the step
  boundary are dropped; weight staleness up to S-1 ticks) and
  microbatch-slot label alignment across steps. Loss decreases, but
  it is NOT tick-for-tick equal to the synchronous schedules; keep it
  off exact-parity comparisons.

Observability: :class:`MeshPipelineTrainStep` emits one
``pipeline:stage{s}`` span per stage per step into the StepTimeline
(the schedule's analytic per-stage activity window scaled by the
measured step wall time — on a simulated backend the per-tick device
profile is not separable host-side, so the spans are
measurement-scaled schedule geometry, stated as such in their args),
publishes ``pipeline_bubble_fraction{schedule=,stage=}`` gauges plus a
``pipeline`` info blob, and prices the step's boundary rolls through
the comms ledger (``op="ppermute"``) when comms tracing is armed.
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Any, Dict, Optional, Tuple

from apex_tpu.mesh.mesh import (
    BATCH_AXIS,
    PIPE_AXIS,
    MeshTrainStep,
    ShardingPlan,
    _named,
)

SCHEDULES = ("gpipe", "1f1b", "interleaved_1f1b", "async_1f1b")

#: analytic bubble fraction of one schedule on (stages, microbatches,
#: model chunks) — the planner's per-schedule term and the bound the
#: tests assert the measured gauge against
def bubble_fraction(schedule: str, num_stages: int, num_microbatches: int,
                    num_model_chunks: int = 1) -> float:
    s, m, v = int(num_stages), int(num_microbatches), int(num_model_chunks)
    if s <= 1:
        return 0.0
    if schedule == "async_1f1b":
        return 0.0                       # steady state: no fill/drain
    if schedule == "interleaved_1f1b":
        return (s - 1) / (v * m + s - 1)
    return (s - 1) / (m + s - 1)         # gpipe / 1f1b


@dataclasses.dataclass(frozen=True)
class PipelineSpec:
    """One pipeline schedule, validated: ``num_stages`` stage rows,
    ``num_microbatches`` per step, ``num_model_chunks`` (V) model
    chunks per stage for the interleaved schedule (V is forced to 1
    elsewhere). Derived: total scan ticks and the analytic bubble."""

    schedule: str = "1f1b"
    num_stages: int = 2
    num_microbatches: int = 4
    num_model_chunks: int = 1

    def __post_init__(self):
        if self.schedule not in SCHEDULES:
            raise ValueError(
                f"unknown schedule {self.schedule!r}; one of {SCHEDULES}")
        if self.num_stages < 1 or self.num_microbatches < 1:
            raise ValueError(
                f"num_stages ({self.num_stages}) and num_microbatches "
                f"({self.num_microbatches}) must be >= 1")
        if self.schedule == "interleaved_1f1b":
            if self.num_model_chunks < 2:
                raise ValueError(
                    "interleaved_1f1b needs num_model_chunks >= 2 "
                    f"(got {self.num_model_chunks}) — with one chunk "
                    "per stage use '1f1b'")
            if self.num_microbatches % self.num_stages:
                raise ValueError(
                    f"interleaved_1f1b needs num_microbatches "
                    f"({self.num_microbatches}) divisible by num_stages "
                    f"({self.num_stages}) — same constraint as the "
                    "reference schedule")
        elif self.num_model_chunks != 1:
            raise ValueError(
                f"schedule {self.schedule!r} runs one model chunk per "
                f"stage (got num_model_chunks={self.num_model_chunks})")

    @property
    def ticks(self) -> int:
        """Ticks one step scans (fine ticks for interleaved)."""
        if self.schedule == "async_1f1b":
            return self.num_microbatches
        return (self.num_model_chunks * self.num_microbatches
                + self.num_stages - 1)

    @property
    def busy_ticks_per_stage(self) -> int:
        """Ticks each stage row does real work (identical per row —
        the staggering shifts the window, not its width)."""
        return self.num_model_chunks * self.num_microbatches

    @property
    def bubble(self) -> float:
        return bubble_fraction(self.schedule, self.num_stages,
                               self.num_microbatches,
                               self.num_model_chunks)

    def stage_layers(self, num_layers: int) -> int:
        """Layers per (stage, chunk); validates divisibility."""
        denom = self.num_stages * self.num_model_chunks
        if num_layers % denom:
            raise ValueError(
                f"num_layers ({num_layers}) must divide over "
                f"num_stages x num_model_chunks ({denom})")
        return num_layers // denom

    def detail(self) -> Dict[str, Any]:
        return {
            "schedule": self.schedule,
            "num_stages": self.num_stages,
            "num_microbatches": self.num_microbatches,
            "num_model_chunks": self.num_model_chunks,
            "ticks": self.ticks,
            "bubble_fraction": round(self.bubble, 6),
        }


def _chunked_scan(body, carry0, ticks: int, chunk: Optional[int]):
    """``lax.scan`` of ``body(carry, t)`` over ``t in range(ticks)``,
    optionally in checkpointed chunks (ported from the retired legacy
    ``schedules._chunked_scan``).

    With ``chunk`` set, the outer scan's body runs ``chunk`` ticks
    under ``jax.checkpoint``: the backward pass stores one carry per
    chunk boundary and recomputes each chunk's tick residuals
    transiently — O(ticks/chunk + chunk) saved state instead of
    O(ticks). Ticks are padded to a chunk multiple; pipeline ticks are
    no-ops past the end (their activity masks are all false), so the
    padding is harmless.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    if not chunk or chunk >= ticks:
        carry, _ = lax.scan(body, carry0, jnp.arange(ticks))
        return carry
    n_chunks = -(-ticks // chunk)

    def chunk_body(carry, c):
        def inner(carry, i):
            out, _ = body(carry, c * chunk + i)
            return out, None

        carry, _ = lax.scan(inner, carry, jnp.arange(chunk))
        return carry, None

    carry, _ = lax.scan(jax.checkpoint(chunk_body), carry0,
                        jnp.arange(n_chunks))
    return carry


# -- GPT decomposition over the pipe axis ----------------------------------


def _gpt_embed(cfg, p, tokens_mb):
    """GPTModel.__call__'s embedding head on one microbatch — the SAME
    modules/ops so a pipelined loss is value-compatible with the plain
    mesh step (tokens (mb, s) -> hidden (s, mb, h))."""
    import jax.numpy as jnp

    from apex_tpu.mesh import annotate
    from apex_tpu.transformer.tensor_parallel import VocabParallelEmbedding

    emb = VocabParallelEmbedding(
        num_embeddings=cfg.vocab_size, embedding_dim=cfg.hidden_size,
        param_dtype=cfg.param_dtype, dtype=cfg.dtype)
    x = emb.apply({"params": p["embedding"]}, tokens_mb)       # (mb, s, h)
    s = tokens_mb.shape[1]
    pos_emb = jnp.asarray(p["position_embedding"])[None, :s]
    x = annotate.constrain_batch_major(x + pos_emb.astype(cfg.dtype))
    return annotate.constrain_hidden(x.transpose(1, 0, 2))     # (s, mb, h)


def _gpt_head_loss(cfg, p, y, labels_mb):
    """GPTModel.__call__'s final-norm + tied-embedding head + LM loss
    on one microbatch's last-stage output (y (s, mb, h))."""
    import jax.numpy as jnp

    from apex_tpu.mesh import annotate
    from apex_tpu.models.gpt import gpt_loss_fn
    from apex_tpu.normalization import FusedLayerNorm

    y = FusedLayerNorm(cfg.hidden_size).apply(
        {"params": p["final_norm"]}, y)
    table = p["embedding"]["embedding"]
    logits = annotate.constrain_logits(jnp.einsum(
        "sbh,vh->sbv", y.astype(jnp.float32), table.astype(jnp.float32)))
    return gpt_loss_fn(logits, labels_mb)


def _stage_chunk_stacks(cfg, p, spec: PipelineSpec):
    """Reshape the scanned layer stack (L, ...) leaves into
    ``(S, V, per, ...)``: index ``[s, c]`` is the GPTLayer params of
    global model chunk ``c*S + s`` — the interleaved round-robin
    placement (chunk c's s-th stage sits on row s), which degenerates
    to plain contiguous stage blocks at V=1. Row dim 0 is pinned to
    the ``pipe`` axis so each pipe group holds only its stage's
    layers."""
    import jax

    from apex_tpu.mesh import annotate

    S, V = spec.num_stages, spec.num_model_chunks
    per = spec.stage_layers(cfg.num_layers)

    def one(leaf):
        # (L, ...) -> (V, S, per, ...): index (c, s, i) is global layer
        # (c*S + s)*per + i, i.e. chunk c*S+s in chunk order
        vs = leaf.reshape((V, S, per) + leaf.shape[1:])
        return annotate.constrain(vs.transpose((1, 0) + tuple(
            range(2, vs.ndim))), PIPE_AXIS)

    return jax.tree.map(one, p["layers"]["layer"])


def make_pipeline_loss_fn(model, spec: PipelineSpec, *, remat: bool = True):
    """The pipelined GPT LM loss: ``loss_fn(params, tokens, labels) ->
    scalar`` suitable for :class:`~apex_tpu.mesh.mesh.MeshTrainStep`
    (``params`` is the standard scan-layers ``GPTModel.init`` tree —
    no re-layout, no permutation; the stage decomposition happens by
    reshape inside the loss).

    Value-compatible with the non-pipelined mesh step: the mean over
    equal microbatches of per-microbatch mean CE equals the full-batch
    mean CE, so a pp>=2 run matches the pp=1 ``make_mesh_train_step``
    loss to fp32 tolerance. Microbatch losses accumulate in microbatch
    index order by construction (the exit tick of microbatch i
    precedes that of i+1), so the accumulation is bitwise-stable
    across rebuilds of the same spec.
    """
    if spec.schedule == "async_1f1b":
        raise ValueError(
            "async_1f1b carries state across steps — build it with "
            "make_mesh_pipeline_train_step, not as a bare loss_fn")
    cfg = model.config

    def loss_fn(params, tokens, labels):
        import jax
        import jax.numpy as jnp
        from jax import lax

        from apex_tpu.mesh import annotate
        from apex_tpu.models.gpt import GPTLayer

        p = params["params"]
        S, V, m = (spec.num_stages, spec.num_model_chunks,
                   spec.num_microbatches)
        spec.stage_layers(cfg.num_layers)          # validate divisibility
        B, seq = tokens.shape
        if B % m:
            raise ValueError(
                f"global batch {B} not divisible by num_microbatches {m}")
        mbs = B // m
        tokens_mb = tokens.reshape(m, mbs, seq)
        labels_mb = labels.reshape(m, mbs, seq)

        # all-microbatch embeddings up front: (m, s, mb, h) — the same
        # O(B*s*h) residency the non-pipelined step's embedding has
        X = jax.vmap(lambda tb: _gpt_embed(cfg, p, tb))(tokens_mb)
        stacks = _stage_chunk_stacks(cfg, p, spec)
        layer = GPTLayer(cfg)
        rows = jnp.arange(S)
        period = V * S

        def constrain_buf(b):
            return annotate.constrain(b, PIPE_AXIS, None, BATCH_AXIS, None)

        def layer_body(h, lp):
            return layer.apply({"params": lp}, h), None

        if remat:
            layer_body = jax.checkpoint(layer_body)

        def apply_stage(row, chunks, x, t):
            # chunks: (V, per, ...) — this row's chunk stack in local
            # chunk order; the staggered round-robin selects chunk
            # ((t - row) mod V*S) // S (legacy interleaved dataflow)
            if V == 1:
                lp = jax.tree.map(lambda l: l[0], chunks)
            else:
                c = jnp.mod(t - row, period) // S
                lp = jax.tree.map(
                    lambda l: lax.dynamic_index_in_dim(
                        l, c, 0, keepdims=False), chunks)
            y, _ = lax.scan(layer_body, x, lp)
            return y

        def tick(carry, t):
            buf, acc = carry
            # row 0 injects a fresh microbatch whenever it starts
            # chunk 0: the first S ticks of every V*S-tick period
            mb0 = (t // period) * S + jnp.mod(t, S)
            injecting = jnp.logical_and(jnp.mod(t, period) < S, mb0 < m)
            x0 = lax.dynamic_index_in_dim(
                X, jnp.clip(mb0, 0, m - 1), 0, keepdims=False)
            buf = buf.at[0].set(jnp.where(injecting, x0, buf[0]))
            buf = constrain_buf(buf)
            out = jax.vmap(apply_stage, in_axes=(0, 0, 0, None))(
                rows, stacks, buf, t)
            out = constrain_buf(out)
            # row S-1 finishing its LAST chunk exits a microbatch
            u = t - (S - 1)
            mb_out = (u // period) * S + jnp.mod(u, S)
            exiting = jnp.logical_and(
                jnp.logical_and(u >= 0, jnp.mod(u, period) >= (V - 1) * S),
                mb_out < m)
            lab = lax.dynamic_index_in_dim(
                labels_mb, jnp.clip(mb_out, 0, m - 1), 0, keepdims=False)
            # loss head under lax.cond: only exit ticks pay the vocab
            # projection + CE
            acc = acc + lax.cond(
                exiting,
                lambda: jnp.asarray(
                    _gpt_head_loss(cfg, p, out[S - 1], lab), jnp.float32),
                lambda: jnp.float32(0.0))
            # the rotation: row s's output feeds row s+1 next tick; the
            # wrap S-1 -> 0 is the interleaved chunk boundary (and is
            # overwritten by injection otherwise). On a >1 pipe axis
            # XLA lowers this roll to a collective-permute.
            return (constrain_buf(jnp.roll(out, 1, axis=0)), acc), None

        buf0 = constrain_buf(jnp.zeros((S, seq, mbs, cfg.hidden_size),
                                       cfg.dtype))
        chunk = spec.num_stages if spec.schedule != "gpipe" else None
        (_, loss_sum) = _chunked_scan(
            tick, (buf0, jnp.float32(0.0)), spec.ticks, chunk)
        return loss_sum / m

    return loss_fn


def _make_async_loss_fn(model, spec: PipelineSpec, *, remat: bool = True):
    """The async (carried-buffer) pipelined loss:
    ``loss_fn(params, tokens, labels, buf, tick0) -> (loss, new_buf)``.
    Exactly M ticks per step — no fill/drain bubble — with the
    boundary buffer threaded across steps. Backprop is truncated at
    the step boundary (the carried buffer is a constant input), the
    PipeDream-style staleness trade."""
    cfg = model.config
    S, m = spec.num_stages, spec.num_microbatches

    def loss_fn(params, tokens, labels, buf, tick0):
        import jax
        import jax.numpy as jnp
        from jax import lax

        from apex_tpu.mesh import annotate
        from apex_tpu.models.gpt import GPTLayer

        p = params["params"]
        B, seq = tokens.shape
        mbs = B // m
        tokens_mb = tokens.reshape(m, mbs, seq)
        labels_mb = labels.reshape(m, mbs, seq)
        X = jax.vmap(lambda tb: _gpt_embed(cfg, p, tb))(tokens_mb)
        stacks = _stage_chunk_stacks(cfg, p, spec)
        layer = GPTLayer(cfg)

        def constrain_buf(b):
            return annotate.constrain(b, PIPE_AXIS, None, BATCH_AXIS, None)

        def layer_body(h, lp):
            return layer.apply({"params": lp}, h), None

        if remat:
            layer_body = jax.checkpoint(layer_body)

        def apply_stage(chunks, x):
            lp = jax.tree.map(lambda l: l[0], chunks)     # V == 1
            y, _ = lax.scan(layer_body, x, lp)
            return y

        def tick(carry, j):
            buf, acc, cnt = carry
            # inject every tick — the carried buffer means row 0 is
            # always free for the next microbatch
            x0 = lax.dynamic_index_in_dim(X, j, 0, keepdims=False)
            buf = constrain_buf(buf.at[0].set(x0))
            out = constrain_buf(jax.vmap(apply_stage)(stacks, buf))
            # row S-1 holds the microbatch injected S-1 ticks ago —
            # possibly last step (same slot, previous step's tokens);
            # invalid only during the global S-1-tick warmup
            t = tick0 + j
            valid = t >= (S - 1)
            idx = jnp.mod(j - (S - 1), m)
            lab = lax.dynamic_index_in_dim(labels_mb, idx, 0,
                                           keepdims=False)
            mb_loss = lax.cond(
                valid,
                lambda: jnp.asarray(
                    _gpt_head_loss(cfg, p, out[S - 1], lab), jnp.float32),
                lambda: jnp.float32(0.0))
            return (constrain_buf(jnp.roll(out, 1, axis=0)),
                    acc + mb_loss, cnt + valid.astype(jnp.int32)), None

        (new_buf, acc, cnt) = _chunked_scan(
            tick, (buf, jnp.float32(0.0), jnp.int32(0)), m, S)
        loss = acc / jnp.maximum(cnt, 1).astype(jnp.float32)
        return loss, new_buf

    return loss_fn


# -- the pipelined train step ----------------------------------------------


class MeshPipelineTrainStep(MeshTrainStep):
    """:class:`~apex_tpu.mesh.mesh.MeshTrainStep` running a
    :class:`PipelineSpec` schedule: same fused flat-space optimizer,
    same donated one-program hot path and compile-plane discipline,
    with the loss replaced by the pipelined decomposition — plus the
    pipeline observability plane (per-stage StepTimeline spans, the
    ``pipeline_bubble_fraction`` gauges, ppermute pricing in the comms
    ledger).

    The async schedule threads the carried boundary buffer as an extra
    donated jit operand; the host wrapper owns it (``reset_pipeline``
    drops it, e.g. at an epoch boundary with reshuffled data).
    """

    FN = "mesh_pipeline_step"

    def __init__(self, model, optimizer, plan: ShardingPlan,
                 spec: PipelineSpec, *, remat: bool = True):
        self.spec = spec
        self.remat = remat
        self.last_bubble_fraction: Optional[float] = None
        self.last_step_ms: Optional[float] = None
        self._async = spec.schedule == "async_1f1b"
        if self._async:
            self._async_loss = _make_async_loss_fn(model, spec,
                                                   remat=remat)
            self._pipe_buf = None
            self._tick0 = 0
            loss_fn = None          # never used on the async path
        else:
            loss_fn = make_pipeline_loss_fn(model, spec, remat=remat)
        super().__init__(model, optimizer, plan, loss_fn=loss_fn)

    # -- async: buffer-carrying program -----------------------------------

    def reset_pipeline(self) -> None:
        """Drop the async carried buffer (next step warms up again)."""
        self._pipe_buf = None
        self._tick0 = 0

    def _buf_sharding(self, shape):
        # same conservative rule as annotate.constrain: an axis only
        # pins a dim it divides (tiny drills run mbs < dp)
        from jax.sharding import PartitionSpec as P

        sizes = dict(zip(self.plan.mesh.axis_names,
                         self.plan.mesh.devices.shape))

        def axis(name, dim):
            return name if dim % max(int(sizes.get(name, 1)), 1) == 0 \
                else None

        return _named(self.plan.mesh, P(
            axis(PIPE_AXIS, shape[0]), None,
            axis(BATCH_AXIS, shape[2]), None))

    def _async_jit_for(self, state, buf_shape) -> Any:
        key = (state.space, state.seg_meta, buf_shape, "async")
        jitted = self._jitted.get(key)
        if jitted is not None:
            return jitted
        import jax

        vg = state.space.grad_fn(self._async_loss, with_value=True,
                                 has_aux=True)

        def step(state, tokens, labels, buf, tick0):
            (loss, new_buf), g = vg(state.master, tokens, labels, buf,
                                    tick0)
            return self._update(state, g), loss, new_buf

        if self.plan.is_identity():
            jitted = jax.jit(step, donate_argnums=(0, 3))
        else:
            from jax.sharding import PartitionSpec as P

            rep = _named(self.plan.mesh, P())
            bsh = _named(self.plan.mesh, self.plan.batch_spec)
            bufsh = self._buf_sharding(buf_shape)
            state_sh = jax.tree.map(lambda _: rep, state)
            jitted = jax.jit(
                step, donate_argnums=(0, 3),
                in_shardings=(state_sh, bsh, bsh, bufsh, rep),
                out_shardings=(state_sh, rep, bufsh))
        self._jitted[key] = jitted
        return jitted

    def _async_step(self, state, tokens, labels):
        import jax
        import jax.numpy as jnp

        cfg = self.model.config
        tokens = self.plan.shard_batch(jnp.asarray(tokens, jnp.int32))
        labels = self.plan.shard_batch(jnp.asarray(labels, jnp.int32))
        B, seq = tokens.shape
        m, S = self.spec.num_microbatches, self.spec.num_stages
        if B % m:
            raise ValueError(
                f"global batch {B} not divisible by num_microbatches {m}")
        shape = (S, seq, B // m, cfg.hidden_size)
        if self._pipe_buf is None or self._pipe_buf.shape != shape:
            buf = jnp.zeros(shape, cfg.dtype)
            if not self.plan.is_identity():
                buf = jax.device_put(buf, self._buf_sharding(shape))
            self._pipe_buf, self._tick0 = buf, 0
        jitted = self._async_jit_for(state, shape)
        key = (state.space, state.seg_meta, tuple(tokens.shape), "async")
        tick0 = jnp.int32(self._tick0)
        if key not in self._seen:
            self._seen.add(key)
            from apex_tpu.telemetry import compiled as _compiled

            _compiled.observe(self.FN, self._signature(state, tokens))
            with _compiled.label(self.FN):
                new_state, loss, new_buf = jitted(
                    state, tokens, labels, self._pipe_buf, tick0)
        else:
            new_state, loss, new_buf = jitted(
                state, tokens, labels, self._pipe_buf, tick0)
        self._pipe_buf = new_buf
        self._tick0 += m
        return new_state, loss

    # -- the observed step -------------------------------------------------

    def step(self, state, tokens, labels) -> Tuple[Any, Any]:
        from apex_tpu.telemetry import timeline as _timeline

        observe = _timeline.global_enabled()
        t0 = time.perf_counter()
        if self._async:
            out = self._async_step(state, tokens, labels)
        else:
            out = super().step(state, tokens, labels)
        if observe:
            import jax

            jax.block_until_ready(out[1])
        wall_s = time.perf_counter() - t0
        self._emit_telemetry(t0, wall_s, tokens, observe=observe)
        return out

    __call__ = step

    def _emit_telemetry(self, t0: float, wall_s: float, tokens,
                        *, observe: bool) -> None:
        """Per-stage spans + bubble gauges + ppermute pricing for one
        completed step. Span geometry is the schedule's analytic
        activity map scaled by the measured wall time (see module
        docstring); the gauges and the ``pipeline`` info blob are what
        ``tools/check_mesh.sh`` and ``tools/telemetry_dump.py`` read."""
        from apex_tpu.telemetry import metrics as _metrics
        from apex_tpu.telemetry import timeline as _timeline

        spec = self.spec
        T = spec.ticks
        busy = spec.busy_ticks_per_stage
        bf = spec.bubble
        self.last_bubble_fraction = bf
        self.last_step_ms = wall_s * 1e3
        tick_s = wall_s / max(T, 1)
        reg = _metrics.registry()
        g = reg.gauge("pipeline_bubble_fraction",
                      "measured per-stage pipeline bubble fraction")
        stages = []
        for s in range(spec.num_stages):
            # stage s's busy window: ticks [s, s + busy) (the wrap at
            # the interleaved chunk boundary keeps it contiguous)
            fill = min(s, T - busy) if spec.schedule != "async_1f1b" else 0
            span_t0 = t0 + fill * tick_s
            span_dur = busy * tick_s
            stages.append({"stage": s, "busy_ticks": busy,
                           "t0_ms": round(fill * tick_s * 1e3, 4),
                           "dur_ms": round(span_dur * 1e3, 4)})
            g.set(bf, schedule=spec.schedule, stage=str(s))
            if observe:
                _timeline.record_global_span(
                    f"pipeline:stage{s}", span_t0, span_dur,
                    category="pipeline",
                    args={"schedule": spec.schedule, "stage": s,
                          "busy_ticks": busy, "ticks": T,
                          "bubble_fraction": round(bf, 6),
                          "geometry": "analytic-activity-x-measured-wall"})
        reg.gauge("pipeline_ticks",
                  "pipeline scan ticks per step").set(
                      T, schedule=spec.schedule)
        reg.set_info("pipeline", {
            **spec.detail(),
            "step_ms": round(wall_s * 1e3, 4),
            "stages": stages,
        })
        self._price_boundary_transfers(t0, wall_s, tokens)
        self._feed_goodput(t0, wall_s, tokens)

    def _feed_goodput(self, t0: float, wall_s: float, tokens) -> None:
        """Run-ledger attribution for one pipeline step: the pipeline
        has no fused-dispatch ``"step"`` span, so when the ledger is
        armed the whole step wall is recorded as one — productive (or
        rework after a rollback) — and the per-stage spans above land
        in the ledger's ``stages`` diagnostic. Disarmed cost: one
        module-global check."""
        from apex_tpu.telemetry import goodput as _goodput
        from apex_tpu.telemetry import timeline as _timeline

        if _goodput.get_ledger() is None:
            return
        _timeline.record_global_span(
            "step", t0, wall_s, category="train_step",
            args={"pipeline": self.spec.schedule})
        _goodput.observe_step(tokens=int(tokens.size), step_s=wall_s)

    def _price_boundary_transfers(self, t0: float, wall_s: float,
                                  tokens) -> None:
        """One comms-ledger record per step for the boundary rolls:
        T rotations of one (seq, mb, hidden) slab per stage — the
        traffic the legacy ``ppermute`` carried, priced by the same
        wire-bytes model. The duration is the step wall time (the
        rolls overlap compute, so ``measured_mbps`` reads as a LOWER
        bound on the link)."""
        from apex_tpu.telemetry import comms as _comms

        tracer = _comms.get_tracer()
        if tracer is None:
            return
        import numpy as np

        cfg = self.model.config
        B = int(tokens.shape[0])
        seq = int(tokens.shape[1])
        mbs = B // self.spec.num_microbatches
        slab = seq * mbs * cfg.hidden_size * np.dtype(cfg.dtype).itemsize
        payload = slab * self.spec.ticks
        pp = dict(zip(self.plan.mesh.axis_names,
                      self.plan.mesh.devices.shape)).get(PIPE_AXIS, 1)
        wire = _comms.wire_bytes("ppermute", payload, int(pp))
        tracer.record("ppermute", "gspmd", payload, wire, t0, wall_s)


def make_mesh_pipeline_train_step(
        model, optimizer, plan: ShardingPlan,
        spec: Optional[PipelineSpec] = None, *,
        schedule: str = "1f1b", num_microbatches: int = 4,
        num_model_chunks: int = 1,
        remat: bool = True) -> MeshPipelineTrainStep:
    """Build the pipelined GSPMD train step for ``model`` over
    ``plan``. Pass a :class:`PipelineSpec`, or the knobs directly;
    ``num_stages`` defaults to the plan mesh's ``pipe`` axis size
    (min 2 — a pipeline over one stage row is the plain mesh step,
    use :func:`~apex_tpu.mesh.mesh.make_mesh_train_step`)."""
    if spec is None:
        sizes = dict(zip(plan.mesh.axis_names, plan.mesh.devices.shape))
        stages = max(int(sizes.get(PIPE_AXIS, 1)), 2)
        spec = PipelineSpec(
            schedule=schedule, num_stages=stages,
            num_microbatches=num_microbatches,
            num_model_chunks=num_model_chunks)
    return MeshPipelineTrainStep(model, optimizer, plan, spec,
                                 remat=remat)


__all__ = [
    "SCHEDULES",
    "MeshPipelineTrainStep",
    "PipelineSpec",
    "bubble_fraction",
    "make_mesh_pipeline_train_step",
    "make_pipeline_loss_fn",
]
