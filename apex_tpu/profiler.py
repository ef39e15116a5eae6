"""Tracing / profiling hooks (SURVEY.md §5 "Tracing / profiling").

The reference marks hot regions with NVTX ranges behind ``prof`` flags
(ref: apex/parallel/distributed.py:360-361,403-404,517-518,556-557;
examples/imagenet/main_amp.py:401 ``--prof``). The TPU equivalents:

- ``profiler.range`` / :func:`mark_range` — ``jax.named_scope``: names
  the enclosing ops in HLO metadata so they show up in XLA/perfetto
  traces exactly where nvtx ranges would in nsight. (``range`` is
  served via module ``__getattr__`` for nvtx-name parity; it is never
  a module-level binding, so no code in this module — or star-import
  of it — can shadow the ``range`` builtin.)
- :func:`start_trace` / :func:`stop_trace` / :func:`trace` —
  ``jax.profiler`` capture to a TensorBoard-loadable directory
  (replaces ``torch.cuda.profiler.start/stop`` + nsys).
- :func:`annotate` — named_scope as a decorator; each call is ALSO a
  host span opened through ``telemetry.timeline.span``, so one
  decorator feeds the HLO metadata, the profiler's host plane and,
  when the global telemetry timeline is enabled, the
  :class:`~apex_tpu.telemetry.StepTimeline` spine.
- Host-side step timing lives in ``apex_tpu.telemetry.timeline``
  (:class:`StepTimeline`); the legacy
  :class:`apex_tpu.transformer.pipeline_parallel.Timers` publishes
  into the same spine (see docs/observability.md).
"""

from __future__ import annotations

import contextlib
import functools
from typing import Iterator, Optional

import jax

mark_range = jax.named_scope


def __getattr__(name: str):
    # nvtx-name parity: ``profiler.range`` works, but ``range`` never
    # exists in the module dict — intra-module code and star-imports
    # cannot pick up a shadowed builtin (advisor finding, round 1;
    # regression test: tests/test_profiler.py)
    if name == "range":
        return jax.named_scope
    raise AttributeError(
        f"module {__name__!r} has no attribute {name!r}")


def start_trace(log_dir: str = "/tmp/apex_tpu_trace") -> None:
    """Begin a profiler capture (ref: --prof windows around iterations)."""
    jax.profiler.start_trace(log_dir)


def stop_trace() -> None:
    jax.profiler.stop_trace()


@contextlib.contextmanager
def trace(log_dir: str = "/tmp/apex_tpu_trace",
          enabled: bool = True) -> Iterator[None]:
    """``with profiler.trace(...):`` capture window; ``enabled=False``
    makes it a no-op so callers can keep the reference's prof-flag
    pattern (``if args.prof and i == start_iter: ...``) inline."""
    if not enabled:
        yield
        return
    start_trace(log_dir)
    try:
        yield
    finally:
        stop_trace()


def annotate(name: Optional[str] = None):
    """Decorator form: name a function's ops in traces (ref:
    nvtx.range_push/pop pairs around functions) AND record each call
    as a host span through :func:`apex_tpu.telemetry.timeline.span`:
    on the profiler's clock whenever a trace is being captured, and in
    ``export_trace()`` output next to the step phases when the global
    telemetry timeline is on."""
    def wrap(fn):
        from apex_tpu.telemetry import timeline as _timeline

        scoped = jax.named_scope(name or fn.__qualname__)(fn)
        span_name = name or fn.__qualname__

        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with _timeline.span(span_name, category="annotate"):
                return scoped(*args, **kwargs)
        return inner
    return wrap


def optimizer_step_cache_stats() -> dict:
    """Hit/miss counters of the fused train-step compile cache
    (optimizers/train_step.py): ``factory_*`` are `make_train_step`
    lookups, ``layout_*`` are distinct static FlatSpace layouts (each
    layout miss paid one XLA compile). The observability hook for the
    donation-aware step path — a training loop that keeps missing here
    is re-compiling its hot path every step."""
    from apex_tpu.optimizers.train_step import step_cache_stats

    return step_cache_stats()


# ``range`` stays importable as an attribute for nvtx-name parity
# (served by __getattr__ above), but is deliberately NOT in __all__:
# star-importing this module must not shadow the ``range`` builtin in
# user code (advisor finding, round 1).
__all__ = ["mark_range", "start_trace", "stop_trace", "trace", "annotate",
           "optimizer_step_cache_stats"]
