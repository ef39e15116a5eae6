"""Fault-tolerance layer over the fused train step.

Production JAX training lives or dies on crash/preemption/NaN
recovery (TorchTitan makes recoverable distributed checkpointing a
first-class pillar). This package makes recovery a native subsystem:

- :mod:`~apex_tpu.resilience.checkpoint` — atomic, self-validating,
  keep-last-k checkpoints of the full train state over the flat host
  buffers; ``latest_valid()`` auto-resume that skips corruption.
- :mod:`~apex_tpu.resilience.watchdog` — ``NonfiniteWatchdog``:
  consecutive-skip counting, per-parameter NaN localization, and
  rollback with a re-initialized loss scale.
- :mod:`~apex_tpu.resilience.retry` — deadline-aware exponential
  backoff with jitter, applied to the prefetch pipeline's device
  transfers and ``records`` disk writes.
- :mod:`~apex_tpu.resilience.faults` — deterministic fault injection
  (context manager + ``APEX_TPU_FAULTS`` env knob) driving the
  kill-and-resume and fault-matrix tests.
- :mod:`~apex_tpu.resilience.guard` — the DISTRIBUTED tier:
  ``ConsistencyGuard`` detects cross-replica state divergence via
  bitwise per-leaf fingerprints all-gathered over the replica set,
  localizes it to (parameter leaf, replica), and repairs it by
  broadcasting the agreeing majority's state; ``PreemptionHandler`` +
  ``graceful_shutdown`` turn SIGTERM into a cross-host-agreed priority
  final checkpoint. ``checkpoint.py``'s quorum mode gives the fleet
  multi-host checkpoints a partial host-set can never corrupt.
- :mod:`~apex_tpu.resilience.elastic` — ELASTIC resharding:
  ``ElasticCheckpointManager`` writes quorum checkpoints as
  logically-indexed range shards and restores them on ANY host count —
  ``ElasticRestorePlanner`` re-partitions the committed ranges onto
  the live world, missing ranges travel over the guard's
  ``Collective``, and the reassembled state is verified bitwise
  against the layout manifest's per-leaf fingerprint.

See docs/resilience.md for the recovery story end to end.
"""

from apex_tpu.resilience import faults
from apex_tpu.resilience.checkpoint import (
    CheckpointError,
    CheckpointManager,
    RestoredState,
)
from apex_tpu.resilience.elastic import (
    ElasticCheckpointManager,
    ElasticLayoutError,
    ElasticRestoredState,
    ElasticRestoreError,
    ElasticRestorePlanner,
    partition_ranges,
)
from apex_tpu.resilience.faults import FaultError, FaultInjector, SimulatedCrash
from apex_tpu.resilience.guard import (
    Collective,
    ConsistencyGuard,
    DivergenceError,
    DivergenceReport,
    KVStoreCollective,
    LocalCollective,
    NullCollective,
    PreemptionHandler,
    ProcessCollective,
    compare_fingerprints,
    graceful_shutdown,
    install_preemption_handler,
    state_fingerprint,
)
from apex_tpu.resilience.retry import (
    NON_RETRYABLE,
    backoff_delays,
    retry,
    retry_call,
)
from apex_tpu.resilience.watchdog import (
    NonfiniteWatchdog,
    RollbackLimitExceeded,
    RollbackUnavailable,
    leaf_names,
    localize_nonfinite,
)

__all__ = [
    "CheckpointError",
    "CheckpointManager",
    "Collective",
    "ConsistencyGuard",
    "DivergenceError",
    "DivergenceReport",
    "ElasticCheckpointManager",
    "ElasticLayoutError",
    "ElasticRestoreError",
    "ElasticRestoredState",
    "ElasticRestorePlanner",
    "FaultError",
    "FaultInjector",
    "KVStoreCollective",
    "LocalCollective",
    "NON_RETRYABLE",
    "NonfiniteWatchdog",
    "NullCollective",
    "PreemptionHandler",
    "ProcessCollective",
    "RestoredState",
    "RollbackLimitExceeded",
    "RollbackUnavailable",
    "SimulatedCrash",
    "backoff_delays",
    "compare_fingerprints",
    "faults",
    "graceful_shutdown",
    "install_preemption_handler",
    "leaf_names",
    "localize_nonfinite",
    "partition_ranges",
    "retry",
    "retry_call",
    "state_fingerprint",
]
