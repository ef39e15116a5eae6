"""Step timeline: per-phase host-loop timing + Chrome-trace export.

Answers "where did step time go" for the training host loop the way
the reference's NVTX ranges + nsight answer it for kernels (ref
apex/parallel/distributed.py:360-561 ``prof`` windows): every phase of
every step — data wait, H2D transfer, the fused step dispatch,
checkpoint writes, collectives — lands in a ring buffer as a
:class:`Span`, and :meth:`StepTimeline.export_trace` emits the whole
window as Chrome-trace / perfetto JSON (load it at ``chrome://tracing``
or ui.perfetto.dev).

This is the ONE spine the previously-duplicated host timers now ride,
and :func:`span` is the one way the program opens a span: it always
enters a ``jax.profiler.TraceAnnotation`` (so the span lies on the
device trace's clock whenever a profiler is recording, and costs that
annotation alone when none is), and records into the ring as well when
the timeline it is bound to is enabled.

- ``transformer.pipeline_parallel.Timers`` (the reference's
  ``_Timers`` port) publishes each stop() into the global timeline —
  new code should use :class:`StepTimeline` directly (see
  docs/transformer.md deprecation note);
- :meth:`StepTimeline.phase`, ``profiler.annotate`` and the serving
  engine's ``apex.serve.*`` spans are all calls of :func:`span`;
- the fused train step takes a ``telemetry=`` timeline and times each
  dispatch under phase ``"step"`` (host-side only — the jitted
  program is byte-identical with telemetry on or off).

Overhead discipline: a **disabled** timeline records nothing and every
entry point returns immediately (the ``make_train_step`` hook returns
the *same* step object, so the disabled path is exactly the
un-instrumented path — ``tools/check_telemetry.sh`` holds this to
<1%). An enabled one costs one ``perf_counter`` pair + a deque append
per span. ``sync=True`` additionally blocks on the step's outputs
before stopping the clock — that's the wall/device-sync distinction:
without it the "step" phase measures dispatch, with it device
execution (and kills async pipelining, so it's for profiling windows,
not production loops).
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, Iterable, NamedTuple, Optional

import jax
from jax.profiler import TraceAnnotation

# canonical phase names the instrumented layers use; arbitrary names
# are fine — these exist so dashboards agree on spelling
PHASES = ("data_wait", "h2d", "step", "checkpoint", "collective")


class Span(NamedTuple):
    """One timed region: ``t0`` is absolute ``perf_counter`` seconds,
    ``dur`` seconds, ``step`` the host-loop step index it happened in
    (-1 = outside any step scope); ``args`` are extra JSON-able
    key/values the trace export folds into the event (the comms plane
    attributes payload/wire bytes to its ``collective:*`` spans)."""

    name: str
    t0: float
    dur: float
    step: int
    category: str
    args: Optional[Dict[str, Any]] = None


class StepTimeline:
    """Ring-buffered span recorder for the training host loop.

    ``capacity`` bounds memory: the newest ``capacity`` spans are kept,
    older ones fall off (``summary()`` reports how many were dropped).
    All methods are thread-safe; clock is ``time.perf_counter``.
    """

    def __init__(self, capacity: int = 4096, *, enabled: bool = True,
                 sync: bool = False,
                 clock: Callable[[], float] = time.perf_counter):
        self.enabled = bool(enabled)
        self.sync = bool(sync)
        self.capacity = int(capacity)
        self.clock = clock
        self._lock = threading.Lock()
        self._spans: "deque[Span]" = deque(maxlen=self.capacity)
        self._recorded = 0
        self._dropped_dur = 0.0
        self._dropped_published = 0
        self._origin = clock()
        self._step = -1
        self._step_t0: Optional[float] = None

    # -- recording ---------------------------------------------------------

    def record_span(self, name: str, t0: float, dur: float, *,
                    category: str = "phase",
                    step: Optional[int] = None,
                    args: Optional[Dict[str, Any]] = None) -> None:
        if not self.enabled:
            return
        with self._lock:
            span = Span(
                str(name), float(t0), float(dur),
                self._step if step is None else int(step), str(category),
                dict(args) if args else None)
            if (self._spans.maxlen is not None
                    and len(self._spans) == self._spans.maxlen):
                # ring wraparound: the evicted span's time would vanish
                # from any later pull-based accounting — total it so
                # summary()/publish() can surface the loss (a zero-
                # capacity ring evicts the incoming span itself)
                self._dropped_dur += (self._spans[0].dur
                                      if self._spans else span.dur)
            self._spans.append(span)
            self._recorded += 1
        obs = _SPAN_OBSERVER
        if obs is not None:
            try:
                obs(span)
            except Exception:  # noqa: BLE001 — observers never take down the loop
                pass

    def phase(self, name: str, *, sync_on: Any = None,
              category: str = "phase"):
        """``with tl.phase("h2d"): ...`` — :func:`span` bound to this
        timeline: the block lands in a profiler trace when one is
        recording and in the ring when this timeline is enabled.
        ``sync_on`` blocks on a jax value before the clock stops, so
        the span covers device completion, not just dispatch."""
        return span(name, category=category, timeline=self,
                    sync_on=sync_on)

    # -- step scopes -------------------------------------------------------

    def begin_step(self) -> int:
        """Open a host-loop step; spans recorded until ``end_step``
        carry its index. Returns the step index."""
        if not self.enabled:
            return self._step
        with self._lock:
            self._step += 1
            self._step_t0 = self.clock()
        return self._step

    def end_step(self) -> None:
        """Close the open step, recording its whole wall span as
        ``host_step`` (category ``step``)."""
        if not self.enabled:
            return
        with self._lock:
            t0, self._step_t0 = self._step_t0, None
        if t0 is not None:
            self.record_span("host_step", t0, self.clock() - t0,
                             category="step")

    @contextlib.contextmanager
    def step_scope(self):
        """``with tl.step_scope(): ...`` — begin_step/end_step pair."""
        self.begin_step()
        try:
            yield self._step
        finally:
            self.end_step()

    def wrap_iter(self, batches: Iterable,
                  name: str = "data_wait") -> Iterable:
        """Time each ``next()`` of ``batches`` as a ``data_wait`` span
        — wrap your (Prefetch)loader so stalls show in the timeline."""
        it = iter(batches)
        while True:
            t0 = self.clock()
            try:
                b = next(it)
            except StopIteration:
                return
            self.record_span(name, t0, self.clock() - t0)
            yield b

    # -- reading -----------------------------------------------------------

    @property
    def origin(self) -> float:
        """The local clock value ``export_trace``'s ``ts=0`` maps to —
        what ``fleet.export_fleet_trace`` shifts against when it moves
        every host's events onto the shared barrier instant."""
        with self._lock:
            return self._origin

    def spans(self) -> list:
        with self._lock:
            return list(self._spans)

    @property
    def dropped_seconds(self) -> float:
        """Total duration of spans evicted by ring wraparound — the
        time a pull-based consumer can no longer see (the goodput
        ledger surfaces it as ``timeline_dropped_span_seconds``)."""
        with self._lock:
            return self._dropped_dur

    def reset(self) -> None:
        with self._lock:
            self._spans.clear()
            self._recorded = 0
            self._dropped_dur = 0.0
            self._dropped_published = 0
            self._step = -1
            self._step_t0 = None
            self._origin = self.clock()

    def summary(self) -> Dict[str, Any]:
        """Per-phase aggregate over the retained window: count,
        total/mean/max/last ms — the JSON-able phase breakdown bench
        records carry."""
        spans = self.spans()
        phases: Dict[str, Dict[str, float]] = {}
        for s in spans:
            p = phases.setdefault(s.name, {
                "count": 0, "total_ms": 0.0, "max_ms": 0.0, "last_ms": 0.0})
            ms = s.dur * 1e3
            p["count"] += 1
            p["total_ms"] += ms
            p["max_ms"] = max(p["max_ms"], ms)
            p["last_ms"] = ms
        for p in phases.values():
            p["mean_ms"] = p["total_ms"] / p["count"]
            for k in ("total_ms", "mean_ms", "max_ms", "last_ms"):
                p[k] = round(p[k], 4)
        with self._lock:
            dropped = self._recorded - len(spans)
            dropped_s = self._dropped_dur
            steps = self._step + 1
        return {"enabled": self.enabled, "steps": steps,
                "spans": len(spans), "dropped_spans": dropped,
                "dropped_span_seconds": round(dropped_s, 6),
                "phases": phases}

    def export_trace(self, path: Optional[str] = None, *,
                     last_steps: Optional[int] = None) -> Dict[str, Any]:
        """The retained window as Chrome-trace JSON (the "JSON Array
        Format" chrome://tracing and ui.perfetto.dev load): complete
        ``"ph": "X"`` events with microsecond ``ts``/``dur`` relative
        to the timeline origin, one tid per category. Writes to
        ``path`` when given; always returns the dict.

        ``last_steps=N`` slices to the newest ``N`` host-loop steps —
        the flight recorder's bundle window. Spans recorded outside any
        step scope (``step == -1``) are kept: they cannot be dated by
        step, and the ring already bounds them."""
        spans = self.spans()
        if last_steps is not None and spans:
            newest = max(s.step for s in spans)
            cutoff = newest - int(last_steps) + 1
            spans = [s for s in spans if s.step < 0 or s.step >= cutoff]
        pid = os.getpid()
        tids: Dict[str, int] = {}
        events = []
        for s in spans:
            tid = tids.setdefault(s.category, len(tids))
            ev_args: Dict[str, Any] = {"step": s.step}
            if s.args:
                ev_args.update(s.args)
            events.append({
                "name": s.name,
                "cat": s.category,
                "ph": "X",
                "ts": round((s.t0 - self._origin) * 1e6, 3),
                "dur": round(s.dur * 1e6, 3),
                "pid": pid,
                "tid": tid,
                "args": ev_args,
            })
        # thread-name metadata makes the perfetto track labels readable
        for cat, tid in tids.items():
            events.append({
                "name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
                "args": {"name": cat},
            })
        trace = {"traceEvents": events, "displayTimeUnit": "ms"}
        if path is not None:
            tmp = f"{path}.tmp-{pid}"
            with open(tmp, "w") as f:
                json.dump(trace, f)
            os.replace(tmp, path)
        return trace

    def publish(self, registry=None) -> Dict[str, Any]:
        """Push the per-phase means into ``timeline_phase_ms`` gauges
        on the metrics registry; returns the summary."""
        from apex_tpu.telemetry import metrics as _metrics

        reg = registry if registry is not None else _metrics.registry()
        summ = self.summary()
        g = reg.gauge("timeline_phase_ms",
                      "mean host-loop phase duration over the window")
        for name, p in summ["phases"].items():
            g.set(p["mean_ms"], phase=name)
        # ring-wraparound visibility: count evictions lazily here (a
        # per-span counter inc would violate the hot-path budget)
        with self._lock:
            delta = (self._recorded - len(self._spans)
                     - self._dropped_published)
            if delta > 0:
                self._dropped_published += delta
        if delta > 0:
            reg.counter(
                "timeline_dropped_spans_total",
                "spans evicted by timeline ring wraparound").inc(delta)
        return summ


# ---------------------------------------------------------------------------
# The process-global timeline (the spine Timers/annotate/loaders ride)
# ---------------------------------------------------------------------------

_GLOBAL: Optional[StepTimeline] = None
_ENV = "APEX_TPU_TELEMETRY"

# one push-based listener every StepTimeline (global AND private
# instances, e.g. the train step's) feeds each recorded span through —
# how the goodput ledger attributes time without polling the ring.
# Checked as a single module-global read per span; None means nobody
# is listening.
_SPAN_OBSERVER: Optional[Callable[[Span], None]] = None


def set_span_observer(cb: Optional[Callable[[Span], None]]) -> None:
    """Install (or clear, with None) the process-wide span observer.
    The callback runs on the recording thread for every span of every
    enabled timeline; exceptions are swallowed — it must be cheap."""
    global _SPAN_OBSERVER
    _SPAN_OBSERVER = cb


def _env_enabled() -> bool:
    return os.environ.get(_ENV, "").strip().lower() in (
        "1", "true", "yes", "on")


def get_timeline() -> StepTimeline:
    """The process-global timeline. Created on first use — DISABLED
    unless ``APEX_TPU_TELEMETRY`` is truthy or :func:`enable` ran."""
    global _GLOBAL
    if _GLOBAL is None:
        _GLOBAL = StepTimeline(enabled=_env_enabled())
    return _GLOBAL


def enable(capacity: int = 4096, *, sync: bool = False) -> StepTimeline:
    """Turn the global timeline on (fresh ring buffer); returns it."""
    global _GLOBAL
    _GLOBAL = StepTimeline(capacity=capacity, enabled=True, sync=sync)
    return _GLOBAL


def disable() -> None:
    global _GLOBAL
    _GLOBAL = StepTimeline(enabled=False)


def global_enabled() -> bool:
    """Cheap hot-path check: is anything listening?"""
    tl = _GLOBAL
    if tl is None:
        return _env_enabled() and get_timeline().enabled
    return tl.enabled


class _RingSpan:
    """:func:`span` with a timeline listening: the profiler annotation
    plus one ``record_span`` at exit."""

    __slots__ = ("_annotation", "_name", "_category", "_timeline",
                 "_sync_on", "_t0")

    def __init__(self, name, category, timeline, sync_on):
        self._annotation = TraceAnnotation(name)
        self._name = name
        self._category = category
        self._timeline = timeline
        self._sync_on = sync_on

    def __enter__(self):
        self._t0 = self._timeline.clock()
        self._annotation.__enter__()
        return self

    def __exit__(self, *exc):
        try:
            if self._sync_on is not None:
                jax.block_until_ready(self._sync_on)
        finally:
            # a failing sync still closes the annotation (the profiler's
            # span stack stays balanced) and leaves its span in the ring
            self._annotation.__exit__(*exc)
            tl = self._timeline
            tl.record_span(self._name, self._t0, tl.clock() - self._t0,
                           category=self._category)
        return False


def span(name: str, *, category: str = "phase",
         timeline: Optional[StepTimeline] = None, sync_on: Any = None,
         ring: bool = True):
    """``with span("apex.serve.admit"): ...`` — the program's one span
    primitive. The block is always a ``jax.profiler.TraceAnnotation``
    named ``name`` (pass a constant string: the name is the event's
    name in the trace, where readers match it), which is the whole
    cost while no profiler records. When ``timeline`` (the global one
    by default) is enabled the block is also recorded into its ring
    under the same name, as :meth:`StepTimeline.phase` always did;
    ``sync_on`` then blocks on a jax value before the clock stops.
    ``ring=False`` keeps a span finer than a phase (the serving
    engine's ``apex.serve.*``) out of the ring: the ring holds 4096
    spans and feeds a gauge per name, a profiler trace holds them all."""
    if not ring:
        return TraceAnnotation(name)
    tl = timeline if timeline is not None else get_timeline()
    if not tl.enabled:
        return TraceAnnotation(name)
    return _RingSpan(name, category, tl, sync_on)


def record_global_span(name: str, t0: float, dur: float, *,
                       category: str = "phase",
                       args: Optional[Dict[str, Any]] = None) -> None:
    """Record into the global timeline iff it is enabled (no-op —
    not even a timeline construction — otherwise)."""
    tl = _GLOBAL
    if tl is not None and tl.enabled:
        tl.record_span(name, t0, dur, category=category, args=args)
    elif tl is None and _env_enabled():
        get_timeline().record_span(name, t0, dur, category=category,
                                   args=args)


__all__ = [
    "PHASES",
    "Span",
    "StepTimeline",
    "disable",
    "enable",
    "get_timeline",
    "global_enabled",
    "record_global_span",
    "set_span_observer",
    "span",
]
