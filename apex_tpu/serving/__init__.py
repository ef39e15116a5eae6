"""Serving tier: paged KV cache, donation-aware decode step, and a
continuous-batching scheduler (ROADMAP item 1, docs/serving.md).

Opens the inference half of the north star over the existing stack:
the decode/prefill hot path is jitted and donation-aware in the
``optimizers/train_step.py`` discipline (cache pools donated, an
eviction-free per-shape compile cache observed by the PR-6 compile
tracker), the KV cache is block-paged over one preallocated pool
(GQA-sized blocks from ``GPTConfig.kv_heads``), and the scheduler is
instrumented end-to-end with the PR-4/5 telemetry spine plus
flight-recorder triggers for its degradation paths.

    from apex_tpu.serving import (KVCache, make_decode_step,
                                  ContinuousBatcher, serve_loop)

    cache = KVCache.for_config(cfg, num_blocks=256)
    state = cache.init_state()
    batcher = ContinuousBatcher(model, params, cache)
    state, results = serve_loop(batcher, state, requests)

The benchmark's serving cells (``benchmark/drivers/serve_closed.py``)
step a ``ContinuousBatcher`` under closed-loop clients dealt from
committed decks; ``static_batch_generate`` is the static-batch baseline.

The resilience plane (``serving/resilience.py``, docs/serving.md
"Failure modes & recovery") makes the engine degrade per-request:
deadlines (``Request.deadline_ms``), per-request fault isolation
(binary-split quarantine + in-jit nonfinite localization),
preemption-safe drain snapshots a fresh engine resumes bitwise, and
live weight hot-swap (``swap_weights``) at step boundaries.

The hot-path plane (docs/serving.md "Chunked prefill" / "Prefix
cache"): chunked prefill (``ContinuousBatcher(prefill_chunk=...)``)
advances long prompts one bucketed chunk per step co-scheduled with
decode, prefix-sharing KV reuse hands repeated prompt prefixes out as
refcounted read-only blocks with copy-on-write at the divergence
block, and token selection (temperature/top-k/top-p, per-request
counter-based PRNG) is fused inside the decode program —
``temperature=0`` stays bitwise-greedy.

The request plane (``serving/tracing.py`` + ``telemetry/slo.py``,
docs/observability.md "Request plane"): ``RequestTracer`` follows one
request through queued → prefill chunks → decode → quarantine/drain
with perfetto export one track per request (trace ids survive drain/
resume), ``SLOMonitor`` watches TTFT/TPOT/goodput/queue-depth
objectives with multi-window burn-rate alerting and feeds the
``should_shed()`` admission hook, and ``ContinuousBatcher.introspect``
(rendered by ``tools/serving_top.py``) is the live view.

The fleet plane (``serving/fleet.py``, docs/serving.md "Fleet"):
``FleetRouter`` fronts N engines behind one submit/step/merge surface
— prefix-affinity placement over each engine's hash-chain prefix
index, SLO-shed deprioritization with a structured fleet-wide refusal,
kill/replace failover that recovers a dead engine's work via drain
snapshots (or prompt+generated replay) with token-identical streams
and trace continuity across engines, bounded hedging for stalled
engines, and elastic ``add_engine`` / ``remove_engine`` membership.
``add_engine(role=...)`` splits the fleet into disaggregated
``prefill`` / ``decode`` seats (DistServe-style): prefill-complete
streams move over a manifest-verified KV-block handoff
(``KVCache.export_blocks`` / ``import_blocks``) with retries, crash
replay, orphan scrub, and a colocated-fallback latch behind it — zero
dropped requests on every failure rung.
"""

from apex_tpu.serving.decode import (
    DecodeStep,
    StepOut,
    greedy_sampling,
    make_decode_step,
)
from apex_tpu.serving.kv_cache import (
    KVCache,
    KVCacheState,
    PoolExhausted,
    PrefixMatch,
    TRASH_BLOCK,
    append_kv,
    append_kv_chunk,
    append_kv_prefill,
    apply_copies,
    bucket,
    gather_kv,
    scrub_blocks,
)
from apex_tpu.serving.resilience import (
    SnapshotError,
    WeightSwapError,
    latest_snapshot,
    load_snapshot,
    merge_results,
    params_digest,
    params_fingerprint,
    params_signature,
    resume_requests,
    save_snapshot,
    swap_weights,
    validate_snapshot,
)
from apex_tpu.serving.scheduler import (
    ContinuousBatcher,
    Request,
    RequestResult,
    serve_loop,
    static_batch_generate,
)
from apex_tpu.serving.tracing import (
    RequestTrace,
    RequestTracer,
)

# imported LAST: fleet.py consumes the scheduler/resilience/tracing
# modules above at import time (the router fronts all of them)
from apex_tpu.serving.fleet import (  # noqa: E402
    ENGINE_ROLES,
    ENGINE_STATES,
    EngineHandle,
    FleetRouter,
    fleet_serve_loop,
)

__all__ = [
    "ContinuousBatcher",
    "ENGINE_ROLES",
    "ENGINE_STATES",
    "EngineHandle",
    "FleetRouter",
    "DecodeStep",
    "KVCache",
    "KVCacheState",
    "PoolExhausted",
    "PrefixMatch",
    "Request",
    "RequestResult",
    "RequestTrace",
    "RequestTracer",
    "SnapshotError",
    "StepOut",
    "TRASH_BLOCK",
    "WeightSwapError",
    "append_kv",
    "append_kv_chunk",
    "append_kv_prefill",
    "apply_copies",
    "bucket",
    "fleet_serve_loop",
    "gather_kv",
    "greedy_sampling",
    "latest_snapshot",
    "load_snapshot",
    "make_decode_step",
    "merge_results",
    "params_digest",
    "params_fingerprint",
    "params_signature",
    "resume_requests",
    "save_snapshot",
    "scrub_blocks",
    "serve_loop",
    "static_batch_generate",
    "swap_weights",
    "validate_snapshot",
]
