"""Continuous-batching scheduler: admission control, per-step join of
prefills and decodes, eviction, and end-to-end telemetry.

The serving tier's control plane (ROADMAP item 1; TorchTitan's
production framing — the scheduler is a first-class, observable
subsystem, not a demo loop). Every engine ``step()``:

1. **Admits** queued requests against the KV pool, reusing published
   prompt-prefix blocks by reference (the prefix cache,
   serving/kv_cache.py): matched tokens skip prefill entirely; the
   private remainder is reserved — the FULL span (prompt +
   max_new_tokens) for short prompts, or STAGED per-chunk for long
   ones (chunked prefill), with the decode span reserved together
   with the last chunk so a request that reaches DECODING still can
   never die of pool exhaustion mid-decode. A request larger than the
   whole pool is rejected (``serving_request_error``); a transiently
   full pool defers admission (the request waits, nothing breaks).
2. **Prefills**: fresh short prompts as one bucketed monolithic batch
   (batch and seq padded to powers of two — the compile-count bound),
   emitting each request's FIRST token from the same program that
   writes the cache (TTFT is one dispatch after admission). Long or
   prefix-resumed prompts live in the ``PREFILLING`` state and
   advance ONE bucketed chunk per step under the per-step
   ``prefill_token_budget`` — a 4k-token prompt never stalls the
   step's decode dispatch behind one monolithic prefill
   (Sarathi-style chunked prefill, docs/serving.md).
3. **Decodes** every in-flight sequence as one bucketed batch joined
   with the step's new arrivals — continuous batching: a finishing
   sequence's slot (and blocks) are reused by the next admission on
   the very next step, no static-batch barrier. Token selection
   (greedy or fused temperature/top-k/top-p sampling) happens inside
   the decode program (serving/decode.py).
4. **Evicts/finishes**: sequences hitting ``max_new_tokens`` or their
   EOS free their block references immediately (shared prefix blocks
   stay resident in the prefix cache) and land in :meth:`drain`.

Telemetry (the PR-4/5 spine, docs/serving.md metric table):
``serving_queue_depth`` / ``serving_batch_size`` /
``serving_kv_blocks_in_use`` gauges per step, per-request TTFT/TPOT
latency histograms, the step's own ``apex.serve.*`` spans (on the
profiler's clock whenever a trace is captured: docs/serving.md "The
engine step's spans") and one ``prefill`` / ``prefill_chunk`` /
``decode`` timeline span per dispatch (category ``serving``),
``serving_requests{outcome=}`` / ``serving_tokens``
counters, and ``serving_request_error`` / ``serving_pool_exhausted``
structured events that double as flight-recorder triggers — a crash
mid-serve leaves a postmortem bundle naming the request.

Resilience (serving/resilience.py, docs/serving.md "Failure modes &
recovery") — the engine degrades per-REQUEST, never per-process:

- **deadlines**: ``Request.deadline_ms`` is a TTL from submission;
  expired requests (queued or in-flight) reap at the top of every
  step, BEFORE admission and decode, with outcome
  ``deadline_exceeded`` (counter + event of the same name).
- **quarantine**: a decode dispatch that raises is retried by binary
  split — halves that succeed keep their tokens, offenders bottom out
  as singletons and finish with outcome ``error``. Nonfinite logits
  localize directly via the decode program's in-jit per-lane finite
  flag. Either way the ``serving_quarantine`` trigger fires and the
  engine keeps serving; quarantined sequences' pool blocks are
  scrubbed before reuse (a NaN row must not haunt the next tenant).
- **preemption drain**: with a ``preemption`` handler attached,
  ``should_stop()`` flips the engine to drain mode — no new
  admissions; with a ``snapshot_dir``, every queued + in-flight
  request persists to an atomic serving snapshot a fresh engine
  resumes from (``resilience.resume_requests``); without one,
  in-flight work finishes and the queue errors out loudly.
- **weight hot-swap**: ``resilience.swap_weights`` stages validated
  params; the engine installs them here, at a step boundary between
  decode dispatches, so no request is dropped
  (``serving_weight_swap`` event with old/new digests).

Degradation paths are deterministically drillable via
``APEX_TPU_FAULTS`` (resilience/faults.py):

- ``serving_pool_exhausted=<steps>``: admission at those engine steps
  behaves as if the pool were empty — load sheds to the queue,
  in-flight decodes keep running, one event + bundle fire.
- ``decode_step_exception=<steps>``: the decode dispatch raises at
  those engine steps — a step-level fault fails every binary-split
  retry too, so the whole batch quarantines (blocks freed, bundle
  dumped) and the engine keeps serving the queue. ``io:decode_step``
  injects by CALL index instead: one transient index is absorbed by
  the retry with zero quarantines.
- ``decode_nonfinite=<steps>`` (+ ``decode_nonfinite_lane``): one
  lane's cached K/V is poisoned with NaN — only that sequence
  quarantines; the rest of the batch keeps its tokens.
- ``prefill_chunk_exception=<idx>``: the chunk-prefill dispatch
  number ``idx`` raises — the binary-split retries re-check the SAME
  dispatch index, so the whole chunk batch quarantines (private
  blocks scrubbed+freed, shared prefix references released) and the
  engine keeps serving. ``io:prefill_chunk`` injects by CALL index
  instead: one transient index is absorbed by the retry with zero
  quarantines.

Request plane (serving/tracing.py + telemetry/slo.py,
docs/observability.md "Request plane"): pass ``tracer=RequestTracer()``
and every request gets a :class:`~apex_tpu.serving.tracing.RequestTrace`
— trace id minted at :meth:`ContinuousBatcher.submit`, spans/marks at
every state transition (queued, admitted, prefill, each
``prefill_chunk[i]``, a coalesced decode window, retry/quarantine/
drain/finish), perfetto export one track per request, and the trace id
persisted in drain snapshots so a resumed engine continues the SAME
trace. Pass ``slo=SLOMonitor(...)`` and the engine feeds it per-request
TTFT/TPOT/goodput and per-step queue depth, publishes burn-rate gauges
via ``slo.tick()``, and consults ``slo.should_shed()`` at admission —
a latched burn-rate alert sheds load to the queue
(``serving_slo_shed``) exactly like a transiently exhausted pool.
:meth:`ContinuousBatcher.introspect` is the live view over all of it.
Both default to None: the unarmed engine pays one attribute check per
hook site (the ``disabled is step`` discipline).
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from apex_tpu.serving.decode import (DecodeStep, host_tokens,
                                     make_decode_step)
from apex_tpu.ops.kv_gather import live_blocks
from apex_tpu.serving.kv_cache import KVCache, PoolExhausted, bucket
from apex_tpu.telemetry import timeline as _timeline
from apex_tpu.telemetry.metrics import TOKEN_COUNT_BUCKETS

_NO_SPAN = contextlib.nullcontext()


@dataclasses.dataclass
class Request:
    """One generation request. ``deadline_ms`` is a TTL measured from
    submission: a request still queued, prefilling, or decoding when
    it elapses is reaped with outcome ``deadline_exceeded`` (its
    generated-so-far tokens are returned; its private blocks free
    immediately, shared prefix references are released). ``None``
    means no deadline.

    Sampling knobs (fused in-program, serving/decode.py):
    ``temperature == 0`` is greedy argmax — bitwise the pre-sampling
    behavior; ``temperature > 0`` draws from the softmax at that
    temperature, restricted to the top ``top_k`` logits (0 = off) and
    the top-``top_p`` nucleus (1.0 = off). ``seed`` keys the
    counter-based per-request PRNG — the stream is a pure function of
    ``(seed, token index)``, so a drain/resume replay regenerates it
    token for token.

    ``trace_id`` is the request plane's identity: normally None (the
    engine's tracer mints one at ``submit()``); a resumed drain
    snapshot carries the ORIGINAL id back (with ``resumed_from``
    naming the snapshot) so the continued trace is the same trace."""

    id: Any
    prompt: Sequence[int]
    max_new_tokens: int = 16
    eos_id: Optional[int] = None
    deadline_ms: Optional[float] = None
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0
    trace_id: Optional[str] = None
    resumed_from: Optional[str] = None

    def __post_init__(self):
        self.prompt = np.asarray(self.prompt, np.int32).ravel()
        if len(self.prompt) < 1:
            raise ValueError(f"request {self.id!r}: empty prompt")
        if self.max_new_tokens < 1:
            raise ValueError(
                f"request {self.id!r}: max_new_tokens must be >= 1")
        if self.deadline_ms is not None and self.deadline_ms <= 0:
            raise ValueError(
                f"request {self.id!r}: deadline_ms must be > 0 or None")
        if self.temperature < 0:
            raise ValueError(
                f"request {self.id!r}: temperature must be >= 0")
        if self.top_k < 0:
            raise ValueError(f"request {self.id!r}: top_k must be >= 0")
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError(
                f"request {self.id!r}: top_p must be in (0, 1]")


@dataclasses.dataclass
class RequestResult:
    """A finished request: generated tokens + the latency the serving
    bench reports (TTFT = submit -> first token; TPOT = mean
    inter-token interval after the first).

    ``reason`` is the machine-readable code for every NON-normal
    terminal outcome — routers (and the fleet's handoff path,
    serving/fleet.py) must branch on this field, never string-match
    ``error``:

    - ``"draining"`` — submit on a draining engine, or preempted with
      no snapshot (refusal, no work done)
    - ``"shedding"`` — fleet-wide SLO shed (serving/fleet.py)
    - ``"oversized"`` — the request can never fit the pool
    - ``"handoff_degraded"`` — refused while the fleet's
      colocated-fallback latch is closed (serving/fleet.py)
    - ``"deadline_queued"`` / ``"deadline_prefilling"`` /
      ``"deadline_in_flight"`` — TTL reaps, by the state the request
      died in (``finish_reason == "deadline_exceeded"``)
    - ``"quarantined"`` — per-request fault isolation
      (``finish_reason == "error"``)

    None for the normal outcomes (``length`` / ``eos``)."""

    id: Any
    tokens: List[int]
    ttft_s: Optional[float]
    tpot_s: Optional[float]
    # "length" | "eos" | "error" | "deadline_exceeded"
    finish_reason: str
    error: Optional[str] = None
    # structured terminal-outcome code (docstring); None when normal
    reason: Optional[str] = None


@dataclasses.dataclass
class _InFlight:
    req: Request
    seq_id: Any
    generated: List[int]
    t_submit: float
    t_first: Optional[float] = None
    t_last: Optional[float] = None
    # chunked-prefill progress: prompt tokens already resident in the
    # cache (prefix-cache matches count — prefill resumes after them);
    # a request is PREFILLING while prefilled < len(prompt)
    prefilled: int = 0
    stalls: int = 0

    @property
    def position(self) -> int:
        """0-based position of the NEXT cache append: the last
        generated token's slot (prompt is already cached)."""
        return len(self.req.prompt) + len(self.generated) - 1


class ContinuousBatcher:
    """The continuous-batching engine (module docstring).

    ``max_batch`` bounds in-flight sequences; ``max_prefill_batch``
    bounds how many admissions one step prefills together (prefill
    cost scales with batch x seq — decode keeps running next step
    either way). ``min_width_bucket`` / ``min_seq_bucket`` floor the
    shape buckets so short bursts don't mint tiny one-off programs.
    Decode batches always pad to ``max_batch``: ONE decode program per
    table-width bucket, the compile-count bound check_serving.sh pins.
    """

    def __init__(self, model, params, cache: KVCache, *,
                 max_batch: int = 8, max_prefill_batch: int = 4,
                 min_width_bucket: int = 4, min_seq_bucket: int = 16,
                 prefill_chunk: Optional[int] = None,
                 prefill_token_budget: Optional[int] = None,
                 prefill_interval: int = 1,
                 registry=None, timeline=None,
                 clock: Callable[[], float] = time.perf_counter,
                 step_fn: Optional[DecodeStep] = None,
                 preemption=None, snapshot_dir: Optional[str] = None,
                 tracer=None, slo=None):
        from apex_tpu import telemetry

        self.params = params
        self.cache = cache
        self.step_fn = (step_fn if step_fn is not None
                        else make_decode_step(model, cache))
        self.max_batch = int(max_batch)
        self.max_prefill_batch = int(max_prefill_batch)
        self.min_width_bucket = int(min_width_bucket)
        self.min_seq_bucket = int(min_seq_bucket)
        # a model with window layers (docs/serving.md "Window layers"):
        # every dispatch over the cache carries a second table a lane,
        # the tail its window layers gather through
        config = getattr(model, "config", None)
        self.attention_window = getattr(config, "attention_window", None)
        # how many of the pool's layers are window layers: those a
        # layer pattern names, or all of them where the model has a
        # window and no pattern
        kinds = [kind for kind, _ in getattr(config, "layers", ())]
        self._window_layers = (
            kinds.count("window") if kinds
            else cache.num_layers * (self.attention_window is not None))
        # chunked prefill (docs/serving.md): prompts longer than
        # `prefill_chunk` advance one bucketed chunk per engine step,
        # co-scheduled with the decode dispatch, instead of one
        # monolithic prefill; `prefill_token_budget` caps the prefill
        # tokens one step may spend (default: a full chunk batch).
        # None = monolithic prefill (the pre-chunking behavior);
        # prefix-cache resumes ride the chunk program either way.
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1 or None")
        self.prefill_chunk = (int(prefill_chunk)
                              if prefill_chunk is not None else None)
        self.prefill_token_budget = (
            int(prefill_token_budget) if prefill_token_budget is not None
            else (self.prefill_chunk * self.max_prefill_batch
                  if self.prefill_chunk else None))
        # the prefill/decode interleave ratio (the Sarathi TTFT/TPOT
        # dial): with k > 1, chunk dispatches run only every k-th step
        # WHILE decodes are in flight — each skipped step is a pure
        # decode step, bounding the chunking tax on TPOT at the price
        # of slower long-prompt TTFT. With no decodes running, chunks
        # advance every step regardless (throttling an idle engine
        # buys nothing).
        if prefill_interval < 1:
            raise ValueError("prefill_interval must be >= 1")
        self.prefill_interval = int(prefill_interval)
        self.clock = clock
        self._registry = (registry if registry is not None
                          else telemetry.registry())
        self._timeline = timeline
        # guards queue mutation + pool reservation (submit() may run on
        # a client thread while the engine thread admits), the finished
        # list, and the staged weight swap — the engine-owned state
        # (running, cache pools) stays single-threaded
        self._lock = threading.Lock()
        self.queue: "deque[Tuple[Request, float]]" = deque()
        self.running: List[_InFlight] = []
        # PREFILLING: admitted, cache partially written, no first
        # token yet — advanced chunk-by-chunk in _prefill_chunks
        self.prefilling: List[_InFlight] = []
        self.finished: List[RequestResult] = []
        self.step_idx = 0
        self._seq_counter = 0
        self._chunk_dispatches = 0        # prefill_chunk_exception idx
        self._pending_copies: Dict[Any, List[Tuple[int, int, int]]] = {}
        self._pool_exhausted_dumped = False
        # positions gathered so far, a layer of each kind (host side):
        # those its tables address, and those of them in blocks that
        # hold a lane's keys (ops/kv_gather.py live_blocks: what its
        # kernel copies; XLA's gather, where that runs, copies them all)
        self.gathered = {"full": 0, "window": 0,
                         "full_live": 0, "window_live": 0}
        # what the live sequences hold at the end of a step, summed
        # over the steps so far (host side): blocks times the pool's
        # layers, and those of them that lie wholly behind a window
        # layer's window, which no later query reads (ROADMAP.md R3)
        self.held = {"block_layers": 0, "behind_window": 0}
        if cache.state_slots:
            # a model with recurrent layers (docs/serving.md "Recurrent
            # state"), at the same steps' ends: the state slots in
            # use, the bytes they hold, and the bytes of the K/V blocks
            # the live sequences hold
            self.held.update(state_slots=0, state_bytes=0, kv_bytes=0)
            self._slot_bytes = cache.slot_bytes()
            self._block_bytes = cache.block_bytes()
        # resilience plane (serving/resilience.py)
        self.preemption = preemption          # guard.PreemptionHandler
        self.snapshot_dir = snapshot_dir
        self.draining = False
        self.drained_snapshot: Optional[str] = None
        self._pending_swap = None             # (params, info) staged
        self._snapshot_count = 0
        self._swap_count = 0
        # request plane (serving/tracing.py, telemetry/slo.py): both
        # optional — an unarmed engine pays one attribute check per
        # hook site (the `disabled is step` discipline)
        self.tracer = tracer                  # tracing.RequestTracer
        self.slo = slo                        # slo.SLOMonitor
        self._shed_active = False
        if slo is not None:
            slo.attach(
                trace_provider=(tracer.trace_dicts
                                if tracer is not None else None),
                introspect_provider=self.introspect)

    # -- telemetry helpers ---------------------------------------------------

    def _tl(self):
        if self._timeline is not None:
            return self._timeline
        return _timeline.get_timeline()

    def _span(self, name: str):
        """One of the engine's ``apex.serve.*`` spans (the table in
        docs/serving.md): on the profiler's clock whenever a trace is
        being captured, and nowhere else."""
        return _timeline.span(name, ring=False)

    def _ring_dispatch(self, name: str):
        """The timeline ring's one span per dispatch, around the jitted
        call and the wait for it; nothing at all while no timeline is
        on."""
        tl = self._tl()
        if not tl.enabled:
            return _NO_SPAN
        return _timeline.span(name, category="serving", timeline=tl)

    def _publish_gauges(self) -> None:
        r = self._registry
        r.gauge("serving_queue_depth",
                "requests waiting for admission").set(len(self.queue))
        r.gauge("serving_batch_size",
                "in-flight sequences this engine step").set(
            len(self.running))
        r.gauge("serving_kv_blocks_in_use",
                "KV pool blocks held by in-flight sequences").set(
            self.cache.blocks_in_use)
        stats = self.cache.prefix_stats()
        r.gauge("serving_prefix_blocks_shared",
                "KV blocks referenced by >= 2 sequences").set(
            stats["shared_blocks"])
        r.gauge("serving_prefix_cached_blocks",
                "zero-ref prefix-cache blocks resident (reclaimable)"
                ).set(stats["cached_blocks"])
        r.gauge("serving_prefilling",
                "admitted sequences still prefilling (chunked)").set(
            len(self.prefilling))

    def _push_result(self, res: RequestResult) -> None:
        # the single completion chokepoint: every outcome — length/
        # eos, quarantine, deadline, rejection — lands here, so the
        # request plane closes traces and feeds the SLO window here
        tr = self.tracer
        if tr is not None and tr.enabled:
            tr.finish(res.id, res.finish_reason, t=self.clock(),
                      error=res.error)
        if self.slo is not None:
            self.slo.observe_request(
                res.id, ttft_s=res.ttft_s, tpot_s=res.tpot_s,
                ok=res.finish_reason in ("length", "eos"),
                t=self.clock())
        with self._lock:
            self.finished.append(res)

    def _finish(self, fl: _InFlight, reason: str,
                error: Optional[str] = None, *, dirty: bool = False,
                clean_blocks: Sequence[int] = (),
                reason_code: Optional[str] = None) -> None:
        self._pending_copies.pop(fl.seq_id, None)
        self.cache.free(fl.seq_id, dirty=dirty, clean_blocks=clean_blocks)
        n = len(fl.generated)
        ttft = (fl.t_first - fl.t_submit) if fl.t_first is not None else None
        tpot = None
        if n > 1 and fl.t_first is not None and fl.t_last is not None:
            tpot = (fl.t_last - fl.t_first) / (n - 1)
        r = self._registry
        r.counter("serving_requests",
                  "finished requests by outcome").inc(outcome=reason)
        r.counter("serving_tokens", "generated tokens").inc(n)
        if ttft is not None:
            r.histogram("serving_ttft_seconds",
                        "submit -> first generated token").observe(ttft)
        if tpot is not None:
            r.histogram("serving_tpot_seconds",
                        "mean inter-token interval after the first"
                        ).observe(tpot)
        self._push_result(RequestResult(
            id=fl.req.id, tokens=list(fl.generated), ttft_s=ttft,
            tpot_s=tpot, finish_reason=reason, error=error,
            reason=reason_code))

    def _reject(self, req: Request, msg: str, *,
                reason: str = "oversized") -> None:
        ev = self._registry.event("serving_request_error",
                                  request=str(req.id), error=msg,
                                  reason=reason)
        from apex_tpu.telemetry import flight as _flight

        _flight.notify("serving_request_error",
                       error=RuntimeError(msg), fleet=False,
                       extra={"request": str(req.id), "reason": reason,
                              "event": ev})
        self._push_result(RequestResult(
            id=req.id, tokens=[], ttft_s=None, tpot_s=None,
            finish_reason="error", error=msg, reason=reason))

    # -- API -----------------------------------------------------------------

    def _chunk_buckets(self) -> List[int]:
        """The chunk-program seq buckets warmup mints: powers of two
        from the bucket floor up to the full chunk (the final partial
        chunk of a prompt buckets below ``prefill_chunk``)."""
        top = bucket(self.prefill_chunk or self.min_seq_bucket)
        lo = min(self.min_seq_bucket, top)
        out = []
        s = lo
        while s <= top:
            out.append(s)
            s *= 2
        return out

    def warmup(self, state, seq_buckets: Optional[Sequence[int]] = None,
               width_buckets: Optional[Sequence[int]] = None,
               chunk_buckets: Optional[Sequence[int]] = None):
        """Compile the engine's programs off the hot path: the decode
        program per table-width bucket, the prefill programs for
        every admission batch bucket x seq bucket (admissions trickle,
        so batches of 1, 2, ... each mint a program), and the
        chunk-resume programs per batch bucket x chunk bucket (chunked
        prefill + prefix-cache resumes both ride them — pass
        ``chunk_buckets`` covering the resume remainders you expect
        when chunking is off but prefix sharing is on). Every write
        lands in the trash block; returns the threaded cache state.
        Serving latency after warmup never includes an XLA compile —
        and the compile tracker sees zero ``recompile`` events from
        the hot loop (tools/check_serving.sh): chunking adds one
        program per (batch bucket, chunk bucket, width), not a
        storm."""
        import jax

        seqs = sorted(set(seq_buckets or [self.min_seq_bucket]))
        widths = sorted(set(width_buckets or [self.min_width_bucket]))
        chunks = sorted(set(chunk_buckets
                            if chunk_buckets is not None
                            else (self._chunk_buckets()
                                  if self.prefill_chunk else seqs)))
        batches = []
        b = 1
        while b < self.max_prefill_batch:
            batches.append(b)
            b *= 2
        batches.append(bucket(self.max_prefill_batch))
        out = None

        def window(nb, w):
            if self.attention_window is None:
                return {}
            ww = self.cache.window_width(self.attention_window, w)
            return {"window": (np.zeros((nb, ww), np.int32),
                               np.zeros((nb,), np.int32))}

        for w in widths:
            out = self.step_fn.decode(
                self.params, state, np.zeros(self.max_batch, np.int32),
                np.zeros(self.max_batch, np.int32),
                np.zeros((self.max_batch, w), np.int32),
                **window(self.max_batch, w))
            state = out.cache
            for nb in batches:
                for s in seqs:
                    out = self.step_fn.prefill(
                        self.params, state, np.zeros((nb, s), np.int32),
                        np.zeros((nb,), np.int32),
                        np.zeros((nb, w), np.int32))
                    state = out.cache
                for s in chunks:
                    out = self.step_fn.prefill_chunk(
                        self.params, state, np.zeros((nb, s), np.int32),
                        np.zeros((nb,), np.int32),
                        np.zeros((nb,), np.int32),
                        np.zeros((nb, w), np.int32), **window(nb, w))
                    state = out.cache
        if out is not None:
            jax.block_until_ready(out.next_token)
        return state

    def submit(self, request: Request) -> None:
        """Enqueue one request (thread-safe: clients may submit while
        the engine thread is admitting). A draining engine refuses
        loudly — its snapshot is already committed, so a late request
        must go to the resumed engine, never silently vanish.

        The request plane starts here: with a tracer attached, the
        trace id is minted now (or CONTINUED, when a resumed snapshot
        already set ``request.trace_id`` — the trace then carries a
        ``resumed_from`` mark naming the snapshot)."""
        now = self.clock()
        tr = self.tracer
        if tr is not None and tr.enabled:
            request.trace_id = tr.begin(
                request.id, t_submit=now, trace_id=request.trace_id,
                resumed_from=request.resumed_from)
        if self.draining:
            self._push_result(RequestResult(
                id=request.id, tokens=[], ttft_s=None, tpot_s=None,
                finish_reason="error",
                error="engine draining (preemption): resubmit to the "
                      "resumed engine",
                reason="draining"))
            return
        with self._lock:
            self.queue.append((request, now))

    def take_queued(self, max_n: Optional[int] = None
                    ) -> List[Tuple[Request, float]]:
        """Withdraw up to ``max_n`` queued (NOT yet admitted) requests
        from the tail of the queue — newest first, so the oldest
        arrivals keep their admission order — and return them as
        ``[(request, t_submit)]``. The engine forgets them entirely
        (no result, no trace transition: the caller owns both now).
        The fleet router's bounded-hedge hook: work a stalled engine
        hasn't started can move to a healthy peer; in-flight work
        stays put (serving/fleet.py)."""
        out: List[Tuple[Request, float]] = []
        with self._lock:
            while self.queue and (max_n is None or len(out) < max_n):
                out.append(self.queue.pop())
        return out

    # -- disaggregated handoff hooks (serving/fleet.py) ----------------------

    def take_prefilled(self, max_n: Optional[int] = None
                       ) -> List[_InFlight]:
        """Surrender up to ``max_n`` prefill-COMPLETE in-flight
        sequences (prompt fully cached, first token sampled, decode
        not started here) — the prefill side of a disaggregated
        handoff (serving/fleet.py). The engine forgets each request
        (no result, no trace transition: the caller owns both now)
        but its KV reservation STAYS allocated: the caller must export
        the blocks and then ``cache.free`` the sequence — on success
        AND on failure — or the pool leaks. Engine-thread only, like
        ``step``."""
        out: List[_InFlight] = []
        keep: List[_InFlight] = []
        for f in self.running:
            if ((max_n is None or len(out) < max_n)
                    and f.prefilled >= len(f.req.prompt)
                    and f.generated):
                out.append(f)
            else:
                keep.append(f)
        self.running = keep
        for f in out:
            self._pending_copies.pop(f.seq_id, None)
        return out

    def install_prefilled(self, state, req: Request,
                          generated: Sequence[int], payloads, *,
                          t_submit: float,
                          t_first: Optional[float] = None,
                          t_last: Optional[float] = None,
                          recurrent=None):
        """Adopt a handed-off, prefill-complete request: reserve its
        FULL decode span (prompt + max_new — the can-never-die-
        mid-decode invariant holds from the first local step), install
        the already-VERIFIED KV payloads (one a pool) into the fresh
        blocks (``KVCache.import_blocks``; verification is the caller's job,
        before this is called), publish the prompt blocks into the
        local prefix index, and join ``running`` directly — no queue,
        no prefill. ``t_submit``/``t_first``/``t_last`` carry the
        SOURCE engine's timestamps so TTFT/TPOT stay end-to-end
        truthful. ``recurrent`` is the source's
        ``KVCache.export_state`` for a model with recurrent layers,
        installed into the sequence's fresh state slot (required
        there: keys alone do not continue such a sequence). Raises
        :class:`PoolExhausted` (reserving nothing)
        when the local pool cannot hold the span; returns the new
        device state. Engine-thread only, like ``step``."""
        total = len(req.prompt) + req.max_new_tokens
        with self._lock:
            self._seq_counter += 1
            seq_id = ("h", self._seq_counter, req.id)
        self.cache.allocate(seq_id, total)
        try:
            state = self.cache.import_blocks(state, seq_id, payloads)
            state = self.cache.import_state(state, seq_id, recurrent)
        except Exception:
            self.cache.free(seq_id)
            raise
        fl = _InFlight(req=req, seq_id=seq_id,
                       generated=[int(t) for t in generated],
                       t_submit=t_submit, t_first=t_first,
                       t_last=(t_last if t_last is not None else t_first),
                       prefilled=len(req.prompt))
        self.running.append(fl)
        self.cache.publish_prefix(seq_id, req.prompt)
        tr = self.tracer
        if tr is not None and tr.enabled:
            tr.decoding(req.id)
        return state

    def idle(self) -> bool:
        with self._lock:
            return (not self.queue and not self.running
                    and not self.prefilling)

    def drain(self) -> List[RequestResult]:
        with self._lock:
            out, self.finished = self.finished, []
        return out

    def introspect(self) -> Dict[str, Any]:
        """One JSON-able snapshot of the live engine — what
        ``tools/serving_top.py`` renders and ``slo_violation`` bundles
        embed: every queued / prefilling / decoding request (state,
        age, deadline headroom, block-table size, chunk progress,
        generated count, trace id), pool + prefix-cache occupancy,
        and the SLO window summary. Host-side reads only — safe to
        call from any thread between (or during) engine steps."""
        now = self.clock()
        with self._lock:
            queued = list(self.queue)
        prefilling = list(self.prefilling)
        running = list(self.running)

        def entry(req: Request, state: str, t_submit: float,
                  fl: Optional[_InFlight] = None) -> Dict[str, Any]:
            age = now - t_submit
            left = (req.deadline_ms - age * 1e3
                    if req.deadline_ms is not None else None)
            out = {"id": str(req.id), "trace_id": req.trace_id,
                   "state": state, "age_s": round(age, 6),
                   "deadline_ms": req.deadline_ms,
                   "deadline_left_ms": (round(left, 3)
                                        if left is not None else None),
                   "prompt_tokens": int(len(req.prompt)),
                   "max_new_tokens": int(req.max_new_tokens),
                   "prefilled": 0, "generated": 0, "blocks": 0}
            if fl is not None:
                out["prefilled"] = int(fl.prefilled)
                out["generated"] = len(fl.generated)
                try:
                    out["blocks"] = len(self.cache.table(fl.seq_id))
                except KeyError:
                    pass
            return out

        requests = ([entry(req, "queued", t) for req, t in queued]
                    + [entry(f.req, "prefilling", f.t_submit, f)
                       for f in prefilling]
                    + [entry(f.req, "decoding", f.t_submit, f)
                       for f in running])
        return {
            "step": self.step_idx,
            "draining": self.draining,
            "queue_depth": len(queued),
            "in_flight": len(running),
            "prefilling": len(prefilling),
            "requests": requests,
            "pool": {
                "num_blocks": self.cache.num_blocks,
                "block_size": self.cache.block_size,
                "blocks_in_use": self.cache.blocks_in_use,
                "free_blocks": self.cache.free_blocks,
                "prefix": self.cache.prefix_stats(),
            },
            "slo": (self.slo.summary()
                    if self.slo is not None else None),
            "traces": (self.tracer.summary()
                       if self.tracer is not None else None),
        }

    # -- resilience plane (serving/resilience.py) ----------------------------

    def _snapshot_entries(self) -> List[Dict[str, Any]]:
        """Every queued + prefilling + in-flight request as JSON-ready
        entries (the drain snapshot payload): prompt, generated-so-far
        tokens, the admission-relevant knobs, and the per-request RNG
        state (sampling knobs + seed — the sampled stream is a pure
        function of ``(seed, token index)``, so the resumed engine
        replays it token for token). Queue order, then prefilling,
        then running — the resumed engine re-admits in the same
        order."""
        def entry(req: Request, generated: List[int],
                  state: str) -> Dict[str, Any]:
            return {"id": req.id,
                    "prompt": [int(t) for t in req.prompt],
                    "max_new_tokens": int(req.max_new_tokens),
                    "eos_id": req.eos_id,
                    "deadline_ms": req.deadline_ms,
                    "temperature": float(req.temperature),
                    "top_k": int(req.top_k),
                    "top_p": float(req.top_p),
                    "seed": int(req.seed),
                    "trace_id": req.trace_id,
                    "generated": generated, "state": state}

        out: List[Dict[str, Any]] = []
        with self._lock:
            queued = list(self.queue)
        for req, _ in queued:
            out.append(entry(req, [], "queued"))
        for f in self.prefilling:
            # no first token yet: the resumed engine re-prefills the
            # whole prompt (chunk progress is cache state, not tokens)
            out.append(entry(f.req, [], "prefilling"))
        for f in self.running:
            out.append(entry(f.req, [int(t) for t in f.generated],
                             "in_flight"))
        return out

    def _stage_params(self, params, info: Dict[str, Any]) -> None:
        """Stage a validated weight swap (``resilience.swap_weights``);
        the engine installs it at the top of its next step — between
        decode dispatches, so no request is dropped."""
        with self._lock:
            self._pending_swap = (params, info)

    def _install_pending_params(self, idx: int) -> None:
        with self._lock:
            pend, self._pending_swap = self._pending_swap, None
        if pend is None:
            return
        params, info = pend
        self.params = params
        r = self._registry
        r.counter("serving_weight_swaps",
                  "live weight hot-swaps installed").inc()
        r.event("serving_weight_swap", step=idx,
                old_digest=info["old_digest"],
                new_digest=info["new_digest"])

    def _reap_deadlines(self, idx: int, now: float) -> List[Any]:
        """Reap every queued + prefilling + in-flight request whose
        TTL elapsed — BEFORE admission, chunking, and decode, so an
        expired request never buys a prefill chunk or a decode slot.
        A mid-``PREFILLING`` reap releases only the request's private
        blocks (shared prefix references are just decremented —
        refcounted free). Returns the reaped ids."""
        def expired(req: Request, t_submit: float) -> bool:
            return (req.deadline_ms is not None
                    and (now - t_submit) * 1000.0 >= req.deadline_ms)

        expired_q: List[Tuple[Request, float]] = []
        with self._lock:
            if any(expired(req, t) for req, t in self.queue):
                keep: "deque[Tuple[Request, float]]" = deque()
                for req, t in self.queue:
                    (expired_q if expired(req, t) else keep).append(
                        (req, t))
                self.queue = keep
        expired_pre = [f for f in self.prefilling
                       if expired(f.req, f.t_submit)]
        expired_run = [f for f in self.running
                       if expired(f.req, f.t_submit)]
        if not expired_q and not expired_run and not expired_pre:
            return []
        r = self._registry
        ids: List[Any] = []
        for req, _ in expired_q:
            r.counter("serving_deadline_exceeded",
                      "requests reaped past their TTL").inc(where="queued")
            self._push_result(RequestResult(
                id=req.id, tokens=[], ttft_s=None, tpot_s=None,
                finish_reason="deadline_exceeded",
                error=f"deadline {req.deadline_ms:g}ms elapsed before "
                      "admission",
                reason="deadline_queued"))
            ids.append(req.id)
        if expired_pre:
            gone = {id(f) for f in expired_pre}
            self.prefilling = [f for f in self.prefilling
                               if id(f) not in gone]
            for f in expired_pre:
                r.counter("serving_deadline_exceeded",
                          "requests reaped past their TTL").inc(
                    where="prefilling")
                self._finish(f, "deadline_exceeded",
                             error=f"deadline {f.req.deadline_ms:g}ms "
                                   "elapsed mid-prefill",
                             reason_code="deadline_prefilling")
                ids.append(f.req.id)
        if expired_run:
            gone = {id(f) for f in expired_run}
            self.running = [f for f in self.running
                            if id(f) not in gone]
            for f in expired_run:
                r.counter("serving_deadline_exceeded",
                          "requests reaped past their TTL").inc(
                    where="in_flight")
                self._finish(f, "deadline_exceeded",
                             error=f"deadline {f.req.deadline_ms:g}ms "
                                   "elapsed mid-decode",
                             reason_code="deadline_in_flight")
                ids.append(f.req.id)
        # flight-safe: the event rides the recorder's ring via the
        # registry sink — no bundle per expiry (deadlines are routine)
        r.event("serving_deadline_exceeded", step=idx,
                requests=[str(i) for i in ids])
        return ids

    def _scrub_pending(self, state):
        """Zero the pool rows of dirty blocks whose refcount reached
        zero since the last step (quarantined tenants of SHARED
        blocks — refcount zero -> scrub -> free list), then hand them
        back to the allocator. Runs at the top of every step, before
        admission can reuse them."""
        from apex_tpu.serving import kv_cache as _kv

        blocks = self.cache.take_pending_scrub()
        if not blocks:
            return state
        state = _kv.scrub_blocks(state, blocks)
        self.cache.scrub_done(blocks)
        self._registry.counter(
            "serving_blocks_scrubbed",
            "dirty blocks zeroed before reuse").inc(len(blocks))
        return state

    def _quarantine(self, state, quarantined, idx: int,
                    report: Dict[str, Any]):
        """Finish the named (flight, reason) pairs with outcome
        ``error`` — blocks scrubbed then freed, counters/events/bundle
        emitted — while the rest of the engine keeps serving. The
        ``serving_quarantine`` trigger replaces the old engine-fatal
        decode-exception path.

        A nonfinite lane APPENDED NaN K/V into its own blocks during
        the dispatch that exposed it; masked attention zeroes masked
        *scores*, not masked V rows (0 x NaN = NaN), so a freed block
        must never hand NaN to its next tenant. Blocks ONLY this
        sequence references (and nobody can match from the prefix
        index) are zeroed right here; its shared/published blocks are
        marked dirty instead — unpublished at once, and scrubbed when
        their refcount reaches zero (``_scrub_pending``)."""
        from apex_tpu.serving import kv_cache as _kv
        from apex_tpu.telemetry import flight as _flight

        excl = sorted({b for f, _ in quarantined
                       for b in self.cache.exclusive_blocks(f.seq_id)})
        state = _kv.scrub_blocks(state, excl)
        r = self._registry
        ids = [str(f.req.id) for f, _ in quarantined]
        reasons = [msg for _, msg in quarantined]
        gone = {id(f) for f, _ in quarantined}
        self.running = [f for f in self.running if id(f) not in gone]
        self.prefilling = [f for f in self.prefilling
                           if id(f) not in gone]
        traced = self.tracer is not None and self.tracer.enabled
        for f, msg in quarantined:
            kind = ("nonfinite" if "nonfinite" in msg else "exception")
            r.counter("serving_quarantined",
                      "sequences quarantined by per-request fault "
                      "isolation").inc(reason=kind)
            if traced:
                self.tracer.mark(f.req.id, "quarantine", self.clock(),
                                 reason=msg, step=idx)
            self._finish(f, "error", error=f"quarantined: {msg}",
                         dirty=True, clean_blocks=excl,
                         reason_code="quarantined")
            report["finished"].append(f.req.id)
        report.setdefault("quarantined", []).extend(
            f.req.id for f, _ in quarantined)
        ev = r.event("serving_quarantine", step=idx, requests=ids,
                     reasons=reasons, in_flight=len(self.running))
        _flight.notify("serving_quarantine", fleet=False,
                       extra={"step": idx, "requests": ids,
                              "reasons": reasons, "event": ev})
        return state

    def _enter_drain(self, idx: int, report: Dict[str, Any]) -> None:
        """Flip to drain mode on a preemption flag: no new admissions
        ever again on this engine. With a ``snapshot_dir``, queued +
        in-flight work persists to an atomic serving snapshot
        (in-flight blocks free; a fresh engine resumes the snapshot);
        without one (or on a failed save), in-flight work keeps
        decoding to completion and the queue errors out loudly —
        either way nothing is silently dropped."""
        from apex_tpu.telemetry import flight as _flight

        self.draining = True
        signum = getattr(self.preemption, "signum", None)
        n_queued = len(self.queue)
        n_running = len(self.running) + len(self.prefilling)
        path = None
        save_error: Optional[str] = None
        if self.snapshot_dir is not None:
            from apex_tpu.serving import resilience as _sresil

            try:
                path = _sresil.save_snapshot(
                    self, self.snapshot_dir, step=idx,
                    reason=f"preemption (signal {signum})")
            except Exception as e:  # noqa: BLE001 — degrade, don't drop
                save_error = f"{type(e).__name__}: {str(e)[:200]}"
        if path is not None:
            self.drained_snapshot = path
            tr = self.tracer
            if tr is not None and tr.enabled:
                # close every snapshotted trace here with outcome
                # `drained`; the resumed engine CONTINUES the same
                # trace id (the snapshot carries it) on its side
                t = self.clock()
                with self._lock:
                    queued_reqs = [req for req, _ in self.queue]
                for req in queued_reqs:
                    tr.drained(req.id, t, snapshot=path)
                for f in self.prefilling + self.running:
                    tr.drained(f.req.id, t, snapshot=path)
            for f in self.running:
                self.cache.free(f.seq_id)
            for f in self.prefilling:
                self.cache.free(f.seq_id)
            self.running = []
            self.prefilling = []
            self._pending_copies.clear()
            with self._lock:
                self.queue.clear()
        else:
            # finish mode: keep decoding the in-flight work; the queue
            # cannot be admitted any more, so fail it loudly
            with self._lock:
                dropped, self.queue = list(self.queue), deque()
            for req, _ in dropped:
                self._reject(req, (
                    "preempted before admission and no drain snapshot "
                    + (f"(save failed: {save_error})" if save_error
                       else "(no snapshot_dir configured)")),
                    reason="draining")
        report["drained"] = True
        report["snapshot"] = path
        r = self._registry
        r.counter("serving_drains", "preemption drains entered").inc(
            mode="snapshot" if path is not None else "finish")
        ev = r.event("serving_drain", step=idx, signum=signum,
                     snapshot=path, save_error=save_error,
                     queued=n_queued, in_flight=n_running)
        _flight.notify("serving_drain", fleet=False,
                       extra={"step": idx, "snapshot": path,
                              "save_error": save_error,
                              "queued": n_queued,
                              "in_flight": n_running, "event": ev})

    # -- one engine step -----------------------------------------------------

    def _admit(self, exhausted: bool) -> Tuple[List[_InFlight],
                                               List[_InFlight]]:
        """Pop queued requests into the engine; returns ``(direct,
        chunked)`` — ``direct`` prefills monolithically this step (a
        fresh short prompt: the pre-chunking program, bitwise
        unchanged), ``chunked`` enters ``PREFILLING`` (a long prompt
        under chunked prefill, or any prefix-cache resume).

        Reservation is prefix-aware and staged: matched prefix blocks
        are taken by REFERENCE (``serving_prefix_cache_hits``), and a
        chunked admission reserves only its first chunk's private
        blocks — ``_prefill_chunks`` extends the reservation chunk by
        chunk, taking the decode span with the final chunk."""
        if self.draining:
            return [], []                    # drain mode: queue frozen
        if self.slo is not None and self.slo.should_shed():
            # a latched burn-rate alert (telemetry/slo.py) sheds load
            # exactly like an exhausted pool: requests stay queued,
            # in-flight decodes keep running, admission resumes when
            # the short window recovers (only passes with work queued
            # count as shed)
            if self.queue:
                self._registry.counter(
                    "serving_slo_shed",
                    "admission passes shed by a latched SLO "
                    "burn-rate alert").inc()
                if not self._shed_active:
                    self._shed_active = True
                    self._registry.event("serving_slo_shed",
                                         slos=self.slo.alerting(),
                                         queued=len(self.queue))
            return [], []
        self._shed_active = False
        if any(f.stalls > 0 for f in self.prefilling):
            # a PREFILLING sequence is waiting on blocks: admitting new
            # work would steal the blocks it needs (and, after a
            # deadlock-breaking requeue, ping-pong the pool between the
            # two forever) — in-progress prompts drain first
            self._registry.counter(
                "serving_admission_deferred",
                "admissions deferred by a transiently full pool").inc()
            return [], []
        direct: List[_InFlight] = []
        chunked: List[_InFlight] = []
        rejects: List[Tuple[Request, str]] = []
        hits: List[int] = []
        deferred = False
        chunk = self.prefill_chunk
        # queue pop + pool reservation under ONE lock: a submit() on a
        # client thread can never interleave with the reservation
        with self._lock:
            while (self.queue
                   and (len(self.running) + len(self.prefilling)
                        + len(direct) + len(chunked) < self.max_batch)
                   and len(direct) + len(chunked) < self.max_prefill_batch):
                req, t_submit = self.queue[0]
                total = len(req.prompt) + req.max_new_tokens
                need = self.cache.blocks_for(total)
                if need > self.cache.num_blocks:
                    self.queue.popleft()
                    rejects.append((req, (
                        f"request needs {need} KV blocks, pool capacity "
                        f"is {self.cache.num_blocks} — can never be "
                        "admitted")))
                    continue
                if exhausted:
                    break                    # shed load: stay queued
                try:
                    self._seq_counter += 1
                    seq_id = ("s", self._seq_counter, req.id)
                    match = self.cache.allocate_prefix(
                        seq_id, req.prompt, total_len=total,
                        chunk=chunk)
                except PoolExhausted:
                    self._seq_counter -= 1
                    deferred = True
                    break                    # wait for blocks to free
                self.queue.popleft()
                fl = _InFlight(req=req, seq_id=seq_id, generated=[],
                               t_submit=t_submit,
                               prefilled=match.matched)
                hits.append(1 if match.matched > 0 else 0)
                if match.copies:
                    self._pending_copies[seq_id] = list(match.copies)
                if (match.matched == 0
                        and (chunk is None or len(req.prompt) <= chunk)):
                    direct.append(fl)
                else:
                    chunked.append(fl)
        if deferred:
            self._registry.counter(
                "serving_admission_deferred",
                "admissions deferred by a transiently full pool").inc()
        if hits:
            c = self._registry.counter(
                "serving_prefix_cache_hits",
                "admissions by prompt-prefix cache outcome")
            n_hit = sum(hits)
            if n_hit:
                c.inc(n_hit, outcome="hit")
            if len(hits) - n_hit:
                c.inc(len(hits) - n_hit, outcome="miss")
        for req, msg in rejects:
            self._reject(req, msg)
        tr = self.tracer
        if tr is not None and tr.enabled and (direct or chunked):
            now = self.clock()
            for fl in direct:
                tr.admitted(fl.req.id, now, mode="direct",
                            matched=fl.prefilled)
            for fl in chunked:
                tr.admitted(fl.req.id, now, mode="chunked",
                            matched=fl.prefilled)
        return direct, chunked

    def _window_tables(self, seq_ids, positions, width: int,
                       batch: int) -> Dict[str, Any]:
        """The ``window=`` argument of a dispatch over the cache: the
        tails of the lanes' tables, where the model has window layers
        (nothing otherwise, and the dispatch is what it always was).
        Counts the positions each kind of layer gathers in this
        dispatch, from the widths, the lanes and their ``positions``
        (``batch`` of them, 0 for a dummy lane) alone."""
        bs = self.cache.block_size

        def count(kind, lens, width):
            self.gathered[kind] += batch * width * bs
            self.gathered[kind + "_live"] += int(
                live_blocks(lens, bs, width).sum()) * bs

        count("full", positions, width)
        if self.attention_window is None:
            return {}
        ww = self.cache.window_width(self.attention_window, width)
        tables, first = self.cache.window_table_array(
            seq_ids, positions, self.attention_window, ww, batch=batch)
        count("window", positions - first * bs, ww)
        return {"window": (tables, first)}

    def _state_slots(self, seq_ids, batch: int) -> Dict[str, Any]:
        """The ``slots=`` argument of a dispatch: the lanes' state
        slots, where the model has recurrent layers (nothing
        otherwise, and the dispatch is what it always was); a dummy
        lane names the trash slot."""
        if not self.cache.state_slots:
            return {}
        return {"slots": self.cache.slot_array(seq_ids, batch=batch)}

    def _count_held(self) -> None:
        """Add this step's end to ``held``, from the sequences' lengths,
        the window and the layer pattern alone: a sequence whose next
        query stands at position ``t`` reads, in a window layer, no key
        before ``t - window + 1``, so the blocks that end there or
        earlier are held for nothing in each such layer. Where the
        model has recurrent layers, also the slots in use and what
        slots and blocks hold in bytes (host arithmetic: no device
        call)."""
        bs, window = self.cache.block_size, self.attention_window
        nexts = ([(f, f.position) for f in self.running]
                 + [(f, f.prefilled) for f in self.prefilling])
        held = 0
        for f, t in nexts:
            blocks = len(self.cache.table(f.seq_id))
            held += blocks
            if window is not None:
                self.held["behind_window"] += self._window_layers * min(
                    blocks, max(0, t - window + 1) // bs)
        self.held["block_layers"] += held * self.cache.num_layers
        if self.cache.state_slots:
            self.held["state_slots"] += len(nexts)
            self.held["state_bytes"] += len(nexts) * self._slot_bytes
            self.held["kv_bytes"] += held * self._block_bytes

    def _tables_for(self, flights: List[_InFlight], batch: int):
        widths = [len(self.cache.table(f.seq_id)) for f in flights]
        w = bucket(max(widths), self.min_width_bucket)
        return self.cache.table_array([f.seq_id for f in flights], w,
                                      batch=batch)

    def _sampling_for(self, flights: List[_InFlight], batch: int):
        """Per-lane sampling arrays (temps, top_ks, top_ps, seeds) for
        a padded batch — dummy lanes are greedy (temperature 0), so an
        all-greedy workload takes the in-program fast path."""
        temps = np.zeros(batch, np.float32)
        ks = np.zeros(batch, np.int32)
        ps = np.ones(batch, np.float32)
        seeds = np.zeros(batch, np.uint32)
        for i, f in enumerate(flights):
            temps[i] = f.req.temperature
            ks[i] = f.req.top_k
            ps[i] = f.req.top_p
            seeds[i] = np.uint32(f.req.seed & 0xFFFFFFFF)
        return temps, ks, ps, seeds

    def _prefill(self, admitted: List[_InFlight], state):
        """Prefill the admissions as one bucketed batch; returns
        ``(cache_state, finite)`` where ``finite[i]`` is the in-jit
        all-finite flag of lane ``i``'s first-token logits. Only
        finite lanes get their first token recorded — a nonfinite lane
        is quarantined by the caller before it joins ``running``."""
        with self._span("apex.serve.prefill"):
            with self._span("apex.serve.prefill.build"):
                b = bucket(len(admitted))
                s = bucket(max(len(f.req.prompt) for f in admitted),
                           self.min_seq_bucket)
                tokens = np.zeros((b, s), np.int32)
                lengths = np.zeros((b,), np.int32)
                for i, f in enumerate(admitted):
                    tokens[i, :len(f.req.prompt)] = f.req.prompt
                    lengths[i] = len(f.req.prompt)
                tables = self._tables_for(admitted, b)
                sampling = self._sampling_for(admitted, b)
                slots = self._state_slots([f.seq_id for f in admitted], b)
            t0 = self.clock()
            with self._ring_dispatch("prefill"):
                with self._span("apex.serve.prefill.dispatch"):
                    out = self.step_fn.prefill(
                        self.params, state, tokens, lengths, tables,
                        sampling=sampling, **slots)
                with self._span("apex.serve.prefill.wait"):
                    host = host_tokens(out)
            now = self.clock()
            with self._span("apex.serve.prefill.fetch"):
                ids, finite = host[0], host[1, :len(admitted)] != 0
            tr = self.tracer
            traced = tr is not None and tr.enabled
            for i, f in enumerate(admitted):
                if traced:
                    tr.span(f.req.id, "prefill", t0, now - t0,
                            tokens=len(f.req.prompt))
                if finite[i]:
                    f.generated.append(int(ids[i]))
                    f.prefilled = len(f.req.prompt)
                    f.t_first = f.t_last = now
                    self.cache.publish_prefix(f.seq_id, f.req.prompt)
                    if traced:
                        tr.mark(f.req.id, "first_token", now)
            return out.cache, finite

    # -- chunked prefill (the PREFILLING state) ------------------------------

    def _chunk_batch(self, state, batchees, cidx: int, b: int, s: int,
                     width: int):
        """ONE chunk-prefill dispatch over ``batchees`` = [(flight,
        chunk_len)], padded to the top-level (b, s, width) so
        binary-split retries reuse the same compiled program; returns
        ``(cache_state, token_ids, finite, now)``. The fault sites
        live here: ``prefill_chunk_exception=<idx>`` checks the
        TOP-LEVEL dispatch index ``cidx`` (retries re-check the same
        index, so the clause fails every sub-dispatch — the whole
        batch quarantines), ``io:prefill_chunk`` counts calls (one
        transient index is absorbed by the retry)."""
        from apex_tpu.resilience import faults

        with self._span("apex.serve.chunk"):
            with self._span("apex.serve.chunk.build"):
                tokens = np.zeros((b, s), np.int32)
                starts = np.zeros((b,), np.int32)
                lengths = np.zeros((b,), np.int32)
                for i, (f, cs) in enumerate(batchees):
                    tokens[i, :cs] = f.req.prompt[f.prefilled:f.prefilled + cs]
                    starts[i] = f.prefilled
                    lengths[i] = cs
                seqs = [f.seq_id for f, _ in batchees]
                tables = self.cache.table_array(seqs, width, batch=b)
                window = self._window_tables(seqs, starts, width, b)
                window.update(self._state_slots(seqs, b))
                sampling = self._sampling_for([f for f, _ in batchees], b)
            with self._ring_dispatch("prefill_chunk"):
                with self._span("apex.serve.chunk.dispatch"):
                    faults.maybe_prefill_chunk_exception(cidx)
                    faults.check("prefill_chunk")
                    out = self.step_fn.prefill_chunk(
                        self.params, state, tokens, starts, lengths, tables,
                        sampling=sampling, **window)
                with self._span("apex.serve.chunk.wait"):
                    host = host_tokens(out)
            now = self.clock()
            with self._span("apex.serve.chunk.fetch"):
                ids, finite = host[0], host[1, :len(batchees)] != 0
            return out.cache, ids, finite, now

    def _isolate_chunks(self, state, batchees, cidx: int, b: int,
                        s: int, width: int):
        """Chunk-prefill ``batchees`` with per-request fault isolation
        (the decode ``_isolate`` idiom on the chunk dispatch); returns
        ``(state, done, quarantined)`` — ``done`` is ``[(flight,
        chunk_len, token, t)]``, ``quarantined`` ``[(flight, msg)]``."""
        try:
            state, ids, finite, now = self._chunk_batch(
                state, batchees, cidx, b, s, width)
        except Exception as e:  # noqa: BLE001 — isolate, keep serving
            if len(batchees) == 1:
                msg = f"{type(e).__name__}: {str(e)[:200]}"
                return state, [], [(batchees[0][0], msg)]
            if self.tracer is not None and self.tracer.enabled:
                t = self.clock()
                for f, _ in batchees:
                    self.tracer.mark(f.req.id, "retry_split", t,
                                     batch=len(batchees),
                                     site="prefill_chunk")
            mid = len(batchees) // 2
            state, d_lo, q_lo = self._isolate_chunks(
                state, batchees[:mid], cidx, b, s, width)
            state, d_hi, q_hi = self._isolate_chunks(
                state, batchees[mid:], cidx, b, s, width)
            return state, d_lo + d_hi, q_lo + q_hi
        done, quarantined = [], []
        for i, (f, cs) in enumerate(batchees):
            if finite[i]:
                done.append((f, cs, int(ids[i]), now))
            else:
                quarantined.append((f, "nonfinite logits (prefill chunk)"))
        return state, done, quarantined

    def _prefill_chunks(self, state, idx: int, report: Dict[str, Any]):
        """Advance the PREFILLING sequences by one bucketed chunk each
        under the per-step token budget, co-scheduled with the step's
        decode dispatch (chunked prefill — the reason a 4k-token
        prompt cannot stall in-flight decodes). Reservation is staged:
        each chunk extends the block table just-in-time, and the FINAL
        chunk reserves the decode span (prompt + max_new), restoring
        the can-never-die-mid-decode invariant at the PREFILLING ->
        DECODING transition. A sequence that cannot extend stalls in
        place (``serving_prefill_stalled``); if nothing else is
        running or prefilling — nothing will ever free blocks — the
        head stalled sequence is requeued
        (``serving_prefill_requeued``) so the engine cannot
        deadlock."""
        from apex_tpu.serving import kv_cache as _kv

        if (self.prefill_interval > 1 and self.running
                and idx % self.prefill_interval):
            return state          # this step is decode-only (knob doc)
        chunk = self.prefill_chunk
        budget = self.prefill_token_budget
        r = self._registry
        batchees: List[Tuple[_InFlight, int]] = []
        stalled: List[_InFlight] = []
        used = 0
        for f in self.prefilling:
            if len(batchees) >= self.max_prefill_batch:
                break
            rem = len(f.req.prompt) - f.prefilled
            cs = rem if chunk is None else min(rem, chunk)
            if budget is not None and batchees and used + cs > budget:
                break
            final = f.prefilled + cs >= len(f.req.prompt)
            target = (len(f.req.prompt) + f.req.max_new_tokens
                      if final else f.prefilled + cs)
            try:
                self.cache.extend(f.seq_id, target)
            except PoolExhausted:
                f.stalls += 1
                stalled.append(f)
                r.counter("serving_prefill_stalled",
                          "chunk reservations deferred by a full "
                          "pool").inc()
                if (f.stalls == 1 and self.tracer is not None
                        and self.tracer.enabled):
                    self.tracer.mark(f.req.id, "prefill_stalled",
                                     prefilled=f.prefilled)
                continue
            f.stalls = 0
            batchees.append((f, cs))
            used += cs
        if not batchees:
            if stalled and not self.running:
                # nothing decodes, nothing prefills: no block will
                # ever free — requeue the head stalled sequence
                f = stalled[0]
                self.prefilling.remove(f)
                self._pending_copies.pop(f.seq_id, None)
                self.cache.free(f.seq_id)
                with self._lock:
                    self.queue.appendleft((f.req, f.t_submit))
                r.counter("serving_prefill_requeued",
                          "prefilling sequences returned to the queue "
                          "to break a reservation deadlock").inc()
                r.event("serving_prefill_requeued", step=idx,
                        request=str(f.req.id), prefilled=f.prefilled)
                if self.tracer is not None and self.tracer.enabled:
                    self.tracer.requeued(f.req.id, self.clock())
            return state
        # execute pending COW fork copies before the chunk gathers
        copies: List[Tuple[int, int, int]] = []
        for f, _ in batchees:
            c = self._pending_copies.pop(f.seq_id, None)
            if c:
                copies.extend(c)
        if copies:
            state = _kv.apply_copies(state, copies)
            for f, _ in batchees:
                self.cache.fork_copied(f.seq_id)
        cidx = self._chunk_dispatches
        self._chunk_dispatches += 1
        b = bucket(len(batchees))
        floor = min(self.min_seq_bucket,
                    bucket(chunk) if chunk else self.min_seq_bucket)
        s = bucket(max(cs for _, cs in batchees), floor)
        widths = [len(self.cache.table(f.seq_id)) for f, _ in batchees]
        width = bucket(max(widths), self.min_width_bucket)
        t0 = self.clock()
        state, done, quarantined = self._isolate_chunks(
            state, batchees, cidx, b, s, width)
        t1 = self.clock()
        tr = self.tracer
        traced = tr is not None and tr.enabled
        now_done: List[_InFlight] = []
        for f, cs, tok, now in done:
            f.prefilled += cs
            r.counter("serving_prefill_chunks",
                      "prefill chunks dispatched").inc()
            r.histogram("serving_prefill_chunk_tokens",
                        "prompt tokens per prefill chunk",
                        buckets=TOKEN_COUNT_BUCKETS).observe(cs)
            report.setdefault("prefilled", []).append(f.req.id)
            if traced:
                tr.chunk_span(f.req.id, t0, t1 - t0, tokens=cs)
            if f.prefilled >= len(f.req.prompt):
                f.generated.append(tok)
                f.t_first = f.t_last = now
                now_done.append(f)
                self.cache.publish_prefix(f.seq_id, f.req.prompt)
                if traced:
                    tr.mark(f.req.id, "first_token", now)
                    tr.decoding(f.req.id)
        if now_done:
            gone = {id(f) for f in now_done}
            self.prefilling = [f for f in self.prefilling
                               if id(f) not in gone]
            self.running.extend(now_done)
        if quarantined:
            state = self._quarantine(state, quarantined, idx, report)
        return state

    def _decode_batch(self, state, flights: List[_InFlight], idx: int,
                      width: int):
        """ONE decode dispatch over ``flights`` (padded to
        ``max_batch`` x the step's shared ``width`` bucket, so
        binary-split retries reuse the very same compiled program);
        returns ``(cache_state, token_ids, finite, now)``. The fault
        sites live here, so the split retries re-traverse them —
        step-indexed clauses fail every sub-dispatch, call-indexed
        ``io:decode_step`` faults are absorbed by the retry."""
        from apex_tpu.resilience import faults

        with self._span("apex.serve.decode"):
            with self._span("apex.serve.decode.build"):
                b = self.max_batch      # fixed: one program per width bucket
                tokens = np.zeros((b,), np.int32)
                positions = np.zeros((b,), np.int32)
                for i, f in enumerate(flights):
                    tokens[i] = f.generated[-1]
                    positions[i] = f.position
                seqs = [f.seq_id for f in flights]
                tables = self.cache.table_array(seqs, width, batch=b)
                window = self._window_tables(seqs, positions, width, b)
                window.update(self._state_slots(seqs, b))
                sampling = self._sampling_for(flights, b)
            with self._ring_dispatch("decode"):
                with self._span("apex.serve.decode.dispatch"):
                    faults.maybe_decode_exception(idx)
                    faults.check("decode_step")
                    out = self.step_fn.decode(
                        self.params, state, tokens, positions, tables,
                        sampling=sampling, **window)
                with self._span("apex.serve.decode.wait"):
                    host = host_tokens(out)
            now = self.clock()
            with self._span("apex.serve.decode.fetch"):
                ids, finite = host[0], host[1, :len(flights)] != 0
            return out.cache, ids, finite, now

    def _isolate(self, state, flights: List[_InFlight], idx: int,
                 width: int):
        """Decode ``flights`` with per-request fault isolation; returns
        ``(state, accepted, quarantined)`` — ``accepted`` is
        ``[(flight, token, t)]``, ``quarantined`` ``[(flight, msg)]``.

        A dispatch exception triggers the binary split (the watchdog's
        localization idiom on the batch axis): each half retries as its
        own dispatch — the fault sites raise BEFORE the jitted call, so
        the donated cache state is still live and, for a model with
        recurrent layers, NO lane's state has been advanced: the state
        update is not idempotent as the K/V append is, so nothing here
        may replay a dispatch that has run (a lane the failed dispatch
        never reached is advanced once, by its half's retry) — and
        offenders bottom out as singletons. Nonfinite logits need no split: the in-jit
        per-lane finite flag names them directly."""
        try:
            state, ids, finite, now = self._decode_batch(
                state, flights, idx, width)
        except Exception as e:  # noqa: BLE001 — isolate, keep serving
            if len(flights) == 1:
                msg = f"{type(e).__name__}: {str(e)[:200]}"
                return state, [], [(flights[0], msg)]
            if self.tracer is not None and self.tracer.enabled:
                t = self.clock()
                for f in flights:
                    self.tracer.mark(f.req.id, "retry_split", t,
                                     batch=len(flights), site="decode")
            mid = len(flights) // 2
            state, acc_lo, q_lo = self._isolate(state, flights[:mid],
                                                idx, width)
            state, acc_hi, q_hi = self._isolate(state, flights[mid:],
                                                idx, width)
            return state, acc_lo + acc_hi, q_lo + q_hi
        accepted, quarantined = [], []
        for i, f in enumerate(flights):
            if finite[i]:
                accepted.append((f, int(ids[i]), now))
            else:
                quarantined.append((f, "nonfinite logits"))
        return state, accepted, quarantined

    def _reap(self) -> List[Any]:
        done, keep = [], []
        for f in self.running:
            if (f.req.eos_id is not None
                    and f.generated[-1] == f.req.eos_id):
                self._finish(f, "eos")
                done.append(f.req.id)
            elif len(f.generated) >= f.req.max_new_tokens:
                self._finish(f, "length")
                done.append(f.req.id)
            else:
                keep.append(f)
        self.running = keep
        return done

    def step(self, state) -> Tuple[Any, Dict[str, Any]]:
        """One engine iteration over the donated cache ``state``;
        returns ``(new_state, report)`` — the report (admitted /
        decoded / finished ids, blocks in use, plus the resilience
        keys ``expired`` / ``quarantined`` / ``drained`` /
        ``snapshot``) is the golden-sequence surface tests assert
        against.

        Ordering is the resilience contract: staged weight swaps
        install FIRST (the step boundary between decode dispatches),
        deadline-expired requests reap BEFORE admission, chunking,
        and decode, the preemption flag is drained before any new
        work starts, pending block scrubs land before admission can
        reuse the blocks, and both the decode and the chunk-prefill
        dispatch run under per-request fault isolation."""
        with self._span("apex.serve.step"):
            return self._step(state)

    def _step(self, state) -> Tuple[Any, Dict[str, Any]]:
        from apex_tpu.resilience import faults
        from apex_tpu.telemetry import flight as _flight

        with self._span("apex.serve.housekeep"):
            idx = self.step_idx
            self.step_idx += 1
            self._install_pending_params(idx)
            faults.maybe_sigterm(idx)       # the preemption drill site
            report: Dict[str, Any] = {
                "step": idx,
                "admitted": [],
                "prefilled": [],
                "decoded": [],
                "finished": [],
                "expired": self._reap_deadlines(idx, self.clock()),
            }
            if (not self.draining and self.preemption is not None
                    and self.preemption.should_stop()):
                self._enter_drain(idx, report)
                if self.drained_snapshot is not None:
                    # snapshot mode: queued + in-flight are persisted,
                    # the engine is done — nothing left to prefill or
                    # decode
                    report["queued"] = 0
                    report["blocks_in_use"] = self.cache.blocks_in_use
                    self._publish_gauges()
                    return state, report
            state = self._scrub_pending(state)
            exhausted = faults.should_pool_exhaust(idx)
            if exhausted:
                self._registry.event("serving_pool_exhausted", step=idx,
                                     injected=True,
                                     queued=len(self.queue),
                                     in_flight=len(self.running))
                if not self._pool_exhausted_dumped:
                    self._pool_exhausted_dumped = True
                    _flight.notify(
                        "serving_pool_exhausted", fleet=False,
                        extra={"step": idx, "queued": len(self.queue),
                               "blocks_in_use": self.cache.blocks_in_use,
                               "prefix_cache": self.cache.prefix_stats()})
        with self._span("apex.serve.admit"):
            direct, chunked = self._admit(exhausted)
        report["admitted"] = [f.req.id for f in direct + chunked]
        report["queued"] = len(self.queue)
        self.prefilling.extend(chunked)
        if direct:
            state, finite = self._prefill(direct, state)
            good = [f for i, f in enumerate(direct) if finite[i]]
            bad = [(f, "nonfinite logits (prefill)")
                   for i, f in enumerate(direct) if not finite[i]]
            self.running.extend(good)
            if bad:
                state = self._quarantine(state, bad, idx, report)
        if self.prefilling:
            # one bucketed chunk per sequence, budget-bounded — the
            # step's decode dispatch below runs either way (chunked
            # prefill's co-scheduling contract)
            state = self._prefill_chunks(state, idx, report)
        # reap BEFORE decoding: a request whose prefill token already
        # hit max_new/EOS must not buy a decode slot
        report["finished"].extend(self._reap())
        if self.running:
            widths = [len(self.cache.table(f.seq_id))
                      for f in self.running]
            width = bucket(max(widths), self.min_width_bucket)
            lane = faults.nonfinite_lane_at(idx)
            if lane is not None and lane < len(self.running):
                from apex_tpu.serving import resilience as _sresil

                f = self.running[lane]
                state = _sresil.poison_lane_kv(
                    state, self.cache, f.seq_id, f.position - 1)
            t0 = self.clock()
            state, accepted, quarantined = self._isolate(
                state, self.running, idx, width)
            tr = self.tracer
            traced = tr is not None and tr.enabled
            for f, tok, now in accepted:
                f.generated.append(tok)
                f.t_last = now
                if traced:
                    tr.decode_tick(f.req.id, t0, now)
            report["decoded"] = [f.req.id for f, _, _ in accepted]
            if quarantined:
                state = self._quarantine(state, quarantined, idx,
                                         report)
        with self._span("apex.serve.finish"):
            report["finished"].extend(self._reap())
            report["blocks_in_use"] = self.cache.blocks_in_use
            self._count_held()
            self._publish_gauges()
            if self.slo is not None:
                now = self.clock()
                self.slo.observe("queue_depth", float(report["queued"]),
                                 t=now)
                self.slo.tick(now=now, step=idx)
        return state, report


def serve_loop(batcher: ContinuousBatcher, state, requests:
               Sequence[Request], *,
               arrivals: Optional[Sequence[float]] = None,
               clock: Callable[[], float] = time.perf_counter,
               sleep: Callable[[float], None] = time.sleep):
    """Drive ``batcher`` over an arrival schedule until every request
    finishes; returns ``(final_cache_state, results)``.

    ``arrivals`` are seconds offsets from loop start (default: all at
    t=0). Submissions happen when the wall clock passes each offset —
    the serving bench's Poisson schedule goes through here.

    A draining engine ends the loop early: once the batcher flags
    ``draining`` (preemption), un-submitted arrivals stay with the
    caller and the loop returns as soon as the in-flight work is
    finished or snapshotted (``batcher.drained_snapshot`` names the
    snapshot a fresh engine resumes from).
    """
    order = sorted(range(len(requests)),
                   key=lambda i: arrivals[i] if arrivals else 0.0)
    t0 = clock()
    results: List[RequestResult] = []
    i = 0
    while i < len(order) or not batcher.idle():
        if (batcher.draining and not batcher.running
                and not batcher.prefilling):
            break
        now = clock() - t0
        while (i < len(order) and not batcher.draining
               and (not arrivals or arrivals[order[i]] <= now)):
            batcher.submit(requests[order[i]])
            i += 1
        if batcher.idle():
            if batcher.draining:
                break
            if i < len(order):
                sleep(max(0.0, min(arrivals[order[i]] - now, 0.001)))
            continue
        state, _ = batcher.step(state)
        results.extend(batcher.drain())
    results.extend(batcher.drain())
    return state, results


def static_batch_generate(model, params, cache: KVCache, state,
                          requests: Sequence[Request], *,
                          batch_size: int = 8,
                          arrivals: Optional[Sequence[float]] = None,
                          clock: Callable[[], float] = time.perf_counter,
                          sleep: Callable[[float], None] = time.sleep,
                          step_fn: Optional[DecodeStep] = None,
                          min_seq_bucket: int = 16,
                          min_width_bucket: int = 4):
    """The naive baseline the serving bench compares against: fixed
    batches in arrival order, each run to the SLOWEST member's last
    token before the next batch starts — late arrivals wait behind the
    barrier, early finishers idle inside it. Same jitted steps, same
    cache machinery; only the scheduling differs. Returns
    ``(final_cache_state, results)``.
    """
    import jax

    step = step_fn if step_fn is not None else make_decode_step(model,
                                                                cache)
    t0 = clock()
    results: List[RequestResult] = []
    pending = list(requests)
    submit_at = list(arrivals) if arrivals else [0.0] * len(pending)
    pos = 0
    while pos < len(pending):
        batch = pending[pos:pos + batch_size]
        t_sub = submit_at[pos:pos + batch_size]
        pos += len(batch)
        # the static server cannot start until every member has arrived
        wait = max(t_sub) - (clock() - t0)
        if wait > 0:
            sleep(wait)
        seqs = []
        for j, req in enumerate(batch):
            sid = ("static", pos, j)
            cache.allocate(sid, len(req.prompt) + req.max_new_tokens)
            seqs.append(sid)
        b = bucket(len(batch))
        s = bucket(max(len(r.prompt) for r in batch), min_seq_bucket)
        w = bucket(max(len(cache.table(sid)) for sid in seqs),
                   min_width_bucket)
        tokens = np.zeros((b, s), np.int32)
        lengths = np.zeros((b,), np.int32)
        for j, req in enumerate(batch):
            tokens[j, :len(req.prompt)] = req.prompt
            lengths[j] = len(req.prompt)
        tables = cache.table_array(seqs, w, batch=b)
        out = step.prefill(params, state, tokens, lengths, tables)
        jax.block_until_ready(out.next_token)
        now = clock()
        state = out.cache
        gen = [[int(t)] for t in np.asarray(out.next_token)[:len(batch)]]
        t_first = [now] * len(batch)
        t_last = [now] * len(batch)
        # decode until the SLOWEST member is done (no early slot reuse)
        rounds = max(r.max_new_tokens for r in batch) - 1
        for _ in range(rounds):
            toks = np.zeros((b,), np.int32)
            poss = np.zeros((b,), np.int32)
            for j, req in enumerate(batch):
                toks[j] = gen[j][-1]
                poss[j] = len(req.prompt) + len(gen[j]) - 1
            out = step.decode(params, state, toks, poss, tables)
            jax.block_until_ready(out.next_token)
            now = clock()
            state = out.cache
            ids = np.asarray(out.next_token)
            for j, req in enumerate(batch):
                if len(gen[j]) < req.max_new_tokens:
                    gen[j].append(int(ids[j]))
                    t_last[j] = now
        for j, req in enumerate(batch):
            n = len(gen[j])
            ttft = t_first[j] - (t0 + t_sub[j])
            tpot = ((t_last[j] - t_first[j]) / (n - 1)) if n > 1 else None
            results.append(RequestResult(
                id=req.id, tokens=gen[j], ttft_s=ttft, tpot_s=tpot,
                finish_reason="length"))
            cache.free(seqs[j])
    return state, results


__all__ = [
    "ContinuousBatcher",
    "Request",
    "RequestResult",
    "serve_loop",
    "static_batch_generate",
]
