"""Telemetry subsystem: metrics registry + step timeline + cost model
+ compile tracker + device-memory ledger + fleet aggregation + crash
flight recorder.

The observability layer the rest of the runtime reports through
(docs/observability.md). The parts:

- :mod:`~apex_tpu.telemetry.metrics` — process-global registry of
  counters / gauges / fixed-bucket histograms with labeled series,
  ``snapshot()`` as one JSON-able dict, structured events, and
  pluggable sinks (in-memory, JSONL riding the records atomic-claim
  writer, stdout line protocol).
- :mod:`~apex_tpu.telemetry.timeline` — :class:`StepTimeline`: ring-
  buffered per-phase host-loop spans (data wait, H2D, step,
  checkpoint, collective) with Chrome-trace/perfetto export; the one
  spine the legacy ``pipeline_parallel.Timers`` and
  ``profiler.annotate`` now publish into, and ``timeline.span``: the
  one span primitive, a profiler ``TraceAnnotation`` always and a ring
  span when a timeline is enabled.
- :mod:`~apex_tpu.telemetry.cost` — static FLOPs/bytes from
  ``jit(...).lower().compile().cost_analysis()`` and the MFU / HBM-
  bandwidth estimates bench records carry (``None`` **with a reason**
  when the backend has no cost model or the chip no peak entry).
- :mod:`~apex_tpu.telemetry.compiled` — the compile plane: XLA
  backend-compile timing via the ``jax.monitoring`` bridge
  (``compile_ms``/``compile_count{fn=}`` + ``compile`` spans),
  re-trace detection (``recompile`` events carrying a signature diff),
  and recompile-storm escalation.
- :mod:`~apex_tpu.telemetry.devmem` — the memory plane: normalized
  ``compiled.memory_analysis()`` next to the cost model, plus a polled
  ``devmem_*`` gauge set with watermark tracking that degrades to an
  explicit null WITH ``devmem_reason`` on backends without stats.
- :mod:`~apex_tpu.telemetry.fleet` — cross-host snapshot aggregation
  over the guard's ``Collective`` abstraction (counters summed, gauges
  per-host, histograms bucket-merged, timelines side by side) with
  EWMA straggler detection (``fleet_straggler`` events + gauges),
  barrier-midpoint clock-offset estimation, and
  ``export_fleet_trace`` — every host's timeline merged onto one
  offset-corrected perfetto trace, one process track per host.
- :mod:`~apex_tpu.telemetry.comms` — the comms plane:
  ``instrument(collective)`` traces every ``Collective`` op
  (``collective_ops/bytes/ms``, timeline spans, the measured-vs-
  analytic wire bandwidth ledger, ``collective_slow`` EWMA
  escalation); disabled means the raw collective object, untouched.
- :mod:`~apex_tpu.telemetry.sharding` — compiled executables'
  input/output shardings, mesh axes, and per-device buffer bytes
  normalized to a fixed-key dict (``sharding_reason`` nulls on
  meshless backends) + ``sharding_devices{fn=}`` gauges.
- :mod:`~apex_tpu.telemetry.moe` — the MoE workload plane:
  ``publish_moe_step`` lands each training step's in-jit expert
  histogram as ``moe_expert_load{expert=}`` / ``moe_aux_loss`` /
  ``moe_dropped_tokens`` gauges and runs the ``moe_imbalance`` EWMA
  latch (event + flight bundle embedding the load histogram);
  ``fleet_expert_load`` folds merged snapshots into fleet totals.
- :mod:`~apex_tpu.telemetry.goodput` — the run ledger:
  :class:`GoodputLedger` attributes every second of run wall-clock to
  a cause bucket (productive / compile / checkpoint / data_wait /
  rollback / rework / drain / straggler_wait + published
  ``unattributed`` residual), survives restarts by riding the
  checkpoint ``extra`` payload, and runs the :class:`StepSeries`
  anomaly plane (``loss_spike`` / ``throughput_regression`` flight
  triggers).
- :mod:`~apex_tpu.telemetry.flight` — the crash flight recorder:
  bounded rings of recent events / timeline spans / state digests,
  dumped as a self-contained ``flightrec_*.json`` postmortem bundle on
  watchdog escalation, replica divergence, preemption shutdown, or an
  exception escaping the fused step (keep-last-k pruned).

Who publishes here (the instrumentation pass):

- ``optimizers.train_step.make_train_step(..., telemetry=tl)`` — the
  host-side ``"step"`` phase; zero overhead (same object) when None.
- ``resilience``: watchdog skip/escalation counters, guard divergence
  repairs, checkpoint save/restore latency histograms.
- ``runtime.PrefetchLoader``: queue depth, device_put retries, worker
  deaths, degrade flag (+ ``data_wait`` spans when the global
  timeline is on).
- ``records.latest_record``: corrupt/unreadable record files skipped.

Everything is host-side; nothing here adds arguments to, or changes
one byte of, a jitted program.
"""

from __future__ import annotations

from typing import Any, Dict

from apex_tpu.telemetry import (
    comms,
    compiled,
    cost,
    devmem,
    fleet,
    flight,
    goodput,
    metrics,
    moe,
    sharding,
    slo,
    timeline,
)
from apex_tpu.telemetry.comms import CommsTracer, InstrumentedCollective
from apex_tpu.telemetry.compiled import CompileTracker
from apex_tpu.telemetry.devmem import DeviceMemoryLedger
from apex_tpu.telemetry.fleet import (
    FleetAggregator,
    gather_snapshots,
    merge_snapshots,
)
from apex_tpu.telemetry.flight import FlightRecorder
from apex_tpu.telemetry.goodput import GoodputLedger, StepSeries
from apex_tpu.telemetry.metrics import (
    Counter,
    Gauge,
    Histogram,
    InMemorySink,
    JsonlSink,
    LATENCY_MS_BUCKETS,
    MetricsRegistry,
    PAYLOAD_BYTES_BUCKETS,
    StdoutSink,
    TOKEN_COUNT_BUCKETS,
    registry,
    to_prometheus_text,
)
from apex_tpu.telemetry.slo import (
    SLOMonitor,
    SLOTarget,
    SlidingWindowQuantile,
)
from apex_tpu.telemetry.timeline import (
    PHASES,
    Span,
    StepTimeline,
    disable,
    enable,
    get_timeline,
    global_enabled,
)


def snapshot() -> Dict[str, Any]:
    """The process-global registry's snapshot (one JSON-able dict)."""
    return metrics.registry().snapshot()


def snapshot_detail() -> Dict[str, Any]:
    """The standard ``detail.telemetry`` block bench records carry:
    the registry snapshot, the global timeline's per-phase breakdown,
    and an ``mfu`` field that is a value or an explicit null with a
    reason — never absent, never silently null."""
    reg = metrics.registry()
    snap = reg.snapshot()
    tl = timeline.get_timeline()
    mfu = snap.get("gauges", {}).get("mfu")
    out: Dict[str, Any] = {
        "registry": snap,
        "step_timeline": tl.summary() if tl.enabled else None,
        "mfu": mfu,
    }
    if mfu is None:
        out["mfu_reason"] = (reg.get_info("mfu_reason")
                             or "no step cost published in this process")
    # devmem rides the same value-or-null-WITH-reason contract as mfu:
    # a poll on a stats-bearing backend filled the gauges; anything
    # else carries the reason the section is null
    gauges = snap.get("gauges", {})
    if gauges.get("devmem_bytes_in_use") is not None:
        out["devmem"] = {
            k: gauges.get(f"devmem_{k}")
            for k in ("bytes_in_use", "peak_bytes", "bytes_limit",
                      "watermark_bytes")}
    else:
        out["devmem"] = None
        out["devmem_reason"] = (
            reg.get_info("devmem_reason")
            or "no device-memory poll in this process")
    # sharding rides the same contract: the per-fn introspection blobs
    # publish_shardings deposited, or an explicit null with the reason
    shardings = reg.get_info("sharding")
    if shardings:
        out["sharding"] = shardings
    else:
        out["sharding"] = None
        out["sharding_reason"] = (
            "no sharding introspection published in this process "
            "(telemetry.sharding.publish_shardings)")
    # the planner's chosen layout, when one was published
    plan = reg.get_info("layout_plan")
    if plan:
        out["layout_plan"] = plan
    else:
        out["layout_plan"] = None
        out["layout_plan_reason"] = (
            "no layout plan published in this process "
            "(mesh.planner.publish_plan)")
    # the run ledger: full attribution table when armed, an explicit
    # null with the reason when not (same contract as mfu/devmem)
    led = goodput.get_ledger()
    if led is not None:
        out["goodput"] = led.summary()
    else:
        out["goodput"] = None
        out["goodput_reason"] = (
            "goodput ledger not armed in this process "
            "(telemetry.goodput.enable)")
    return out


def reset() -> None:
    """Fresh registry + disabled global timeline + disarmed flight
    recorder / compile tracker / devmem ledger / comms tracer
    (tests)."""
    flight.disable()
    compiled.disable()
    devmem.disable()
    comms.disable()
    goodput.disable()
    moe.reset()
    metrics.reset()
    timeline.disable()


__all__ = [
    "CommsTracer",
    "CompileTracker",
    "Counter",
    "DeviceMemoryLedger",
    "FleetAggregator",
    "FlightRecorder",
    "Gauge",
    "GoodputLedger",
    "Histogram",
    "InMemorySink",
    "InstrumentedCollective",
    "JsonlSink",
    "LATENCY_MS_BUCKETS",
    "MetricsRegistry",
    "PAYLOAD_BYTES_BUCKETS",
    "PHASES",
    "SLOMonitor",
    "SLOTarget",
    "SlidingWindowQuantile",
    "Span",
    "StdoutSink",
    "StepSeries",
    "StepTimeline",
    "TOKEN_COUNT_BUCKETS",
    "comms",
    "compiled",
    "cost",
    "devmem",
    "disable",
    "enable",
    "fleet",
    "flight",
    "gather_snapshots",
    "get_timeline",
    "global_enabled",
    "goodput",
    "merge_snapshots",
    "metrics",
    "moe",
    "registry",
    "reset",
    "sharding",
    "slo",
    "snapshot",
    "snapshot_detail",
    "timeline",
    "to_prometheus_text",
]
