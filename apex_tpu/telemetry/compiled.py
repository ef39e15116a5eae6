"""Compile-plane observability: compile timing, re-trace detection,
and recompile-storm escalation.

PRs 4-5 lit up the host loop and the fleet; the COMPILER plane stayed
dark — nothing said how long XLA compiles took, or that a training
loop had quietly fallen into a re-trace storm (a shape-polymorphic
input or a drifting static option recompiling the train step every few
steps, each one a multi-second stall that looks like "the chip got
slow"). This module is that plane:

- **Compile timing** rides jax's own ``jax.monitoring`` duration
  events: :func:`enable` registers a listener for XLA backend-compile
  durations, so EVERY real compile in the process — the fused train
  step's per-layout specialization, a guard fingerprint program, a
  Pallas engine sweep — publishes ``compile_count{fn=}`` /
  ``compile_ms{fn=}`` / a ``compile_seconds{fn=}`` histogram into the
  global registry and a ``"compile"`` span into the global timeline.
  Attribution comes from :func:`label` scopes the instrumented entry
  points (``optimizers.train_step``, ``multi_tensor.engine``,
  ``resilience.guard``, ``telemetry.cost``) push around their
  dispatches; unlabeled compiles land under ``fn="unattributed"``.
- **Re-trace detection**: :meth:`CompileTracker.observe` registers the
  abstract signature (static options + aval summary) each jit entry
  point is about to compile under. The first signature of a fn is a
  ``compile``; a signature already seen is a ``hit`` and publishes
  NOTHING (cache hits are free, and must read as free); a NEW
  signature on a previously-compiled fn is a **recompile** — a
  ``recompile`` event carrying the structured signature diff
  (changed/added/removed keys, old -> new) so the log names exactly
  which static option or shape moved.
- **Storm escalation**: more than ``storm_threshold`` recompiles of
  one fn within ``storm_window`` steps emits one ``recompile_storm``
  event (and resets the count, so a persisting storm escalates once
  per threshold-full, not once per recompile). Knobs:
  ``APEX_TPU_RECOMPILE_STORM_N`` (default 3) and
  ``APEX_TPU_RECOMPILE_STORM_WINDOW`` (default 100 steps).

- **Scope tables** (PR 37): every operation of a step lies under one
  *part* of the model, a ``jax.named_scope`` from :data:`PARTS`. The
  entry points :func:`register_program` each new program on the same
  cold paths (its name as a trace prints it, its signature, the jitted
  function, its abstract arguments); :func:`scope_tables`
  pulls each one's compiled text (an in-memory hit: nothing compiles),
  parses it once and hands out ``{instruction name -> op_name}``, so
  a profiler trace's ``fusion.70`` can be given to the part of the
  model it belongs to (:func:`part_of`). Armed or not, a registration
  is one dict write a *new* program; no text is pulled until asked.

Everything is host-side and disarmed by default: with no tracker
enabled, :func:`observe` is one module-global read and :func:`label`
returns a shared null context — the instrumented entry points only
reach them on their COLD paths (a new layout, a fingerprint boundary),
never per hot-loop dispatch, and the ``disabled is step`` /
<1%-overhead contracts of docs/observability.md hold unchanged
(tools/check_observability.sh re-asserts both with the tracker armed).
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import os
import re
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

_STORM_N_ENV = "APEX_TPU_RECOMPILE_STORM_N"
_STORM_WINDOW_ENV = "APEX_TPU_RECOMPILE_STORM_WINDOW"
_DEFAULT_STORM_N = 3
_DEFAULT_STORM_WINDOW = 100

# the jax.monitoring duration key fired once per actual XLA backend
# compile (trace/lowering have their own keys; the backend compile is
# the multi-second one worth a span)
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_LOCAL = threading.local()
_NULL_CM = contextlib.nullcontext()


def _label_stack():
    st = getattr(_LOCAL, "stack", None)
    if st is None:
        st = _LOCAL.stack = []
    return st


def current_label() -> Optional[str]:
    """The innermost :func:`label` scope on this thread, or None."""
    st = getattr(_LOCAL, "stack", None)
    return st[-1] if st else None


@contextlib.contextmanager
def _labeled(fn: str):
    st = _label_stack()
    st.append(str(fn))
    try:
        yield
    finally:
        st.pop()


def label(fn: str):
    """Attribution scope: backend compiles fired inside the block are
    credited to ``fn`` by the monitoring bridge. A shared null context
    (no allocation, no state) when no tracker is armed — entry points
    may wrap their cold-path dispatches unconditionally."""
    if _TRACKER is None:
        return _NULL_CM
    return _labeled(fn)


def signature_diff(old: Dict[str, Any],
                   new: Dict[str, Any]) -> Dict[str, Any]:
    """Structured top-level diff between two abstract signatures:
    ``{"changed": {k: [old, new]}, "added": {...}, "removed": {...}}``
    with empty sections dropped — what a ``recompile`` event carries so
    the log names exactly which static option or shape moved."""
    changed, added, removed = {}, {}, {}
    for k in sorted(set(old) | set(new)):
        if k not in old:
            added[k] = new[k]
        elif k not in new:
            removed[k] = old[k]
        elif old[k] != new[k]:
            changed[k] = [old[k], new[k]]
    out: Dict[str, Any] = {}
    if changed:
        out["changed"] = changed
    if added:
        out["added"] = added
    if removed:
        out["removed"] = removed
    return out


def abstract_signature(tree=None, **static) -> Dict[str, Any]:
    """A JSON-able abstract signature: the ``static`` kwargs verbatim
    plus, when a pytree is given, a compact aval summary (leaf count,
    total elements, digest of every leaf's shape/dtype string) — big
    trees never inline thousands of shapes into an event."""
    sig: Dict[str, Any] = dict(static)
    if tree is not None:
        import jax

        leaves = jax.tree.leaves(tree)
        avals = [f"{getattr(l, 'dtype', type(l).__name__)}"
                 f"[{','.join(str(d) for d in getattr(l, 'shape', ()))}]"
                 for l in leaves]
        sig["leaves"] = len(leaves)
        sig["total_elements"] = int(sum(
            int(getattr(l, "size", 1)) for l in leaves))
        sig["aval_digest"] = hashlib.sha256(
            "|".join(avals).encode()).hexdigest()[:12]
    return sig


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


class CompileTracker:
    """Signature registry + recompile/storm detection + the metric
    surface the monitoring bridge publishes through.

    - ``storm_threshold`` (N) / ``storm_window`` (M): escalate past N
      recompiles of one fn within M steps. Step indices come from the
      explicit ``step=`` argument, else the global timeline's current
      step, else an internal observation counter.
    - ``registry``: defaults to the process-global metrics registry.
    """

    def __init__(self, registry=None, *, storm_threshold: Optional[int] = None,
                 storm_window: Optional[int] = None):
        from apex_tpu.telemetry import metrics as _metrics

        self.registry = (registry if registry is not None
                         else _metrics.registry())
        self.storm_threshold = int(
            storm_threshold if storm_threshold is not None
            else _env_int(_STORM_N_ENV, _DEFAULT_STORM_N))
        self.storm_window = int(
            storm_window if storm_window is not None
            else _env_int(_STORM_WINDOW_ENV, _DEFAULT_STORM_WINDOW))
        self._lock = threading.Lock()
        self._signatures: Dict[str, Dict[str, Dict[str, Any]]] = {}
        self._last_key: Dict[str, str] = {}
        self._recompile_steps: Dict[str, deque] = {}
        self._observations = 0
        self.compiles = 0
        self.recompiles = 0
        self.storms = 0

    # -- steps ---------------------------------------------------------------

    def _step_now(self, step: Optional[int]) -> int:
        if step is not None:
            return int(step)
        from apex_tpu.telemetry import timeline as _timeline

        tl = _timeline._GLOBAL          # never CREATE the global here
        if tl is not None and tl.enabled and tl._step >= 0:
            return tl._step
        return self._observations

    # -- signature observation ----------------------------------------------

    def observe(self, fn: str, signature: Dict[str, Any], *,
                step: Optional[int] = None) -> str:
        """Register that ``fn`` is being dispatched under ``signature``.

        Returns ``"hit"`` (seen before — publishes NOTHING),
        ``"compile"`` (first signature of this fn), or ``"recompile"``
        (new signature on a previously-compiled fn: ``recompile`` event
        with the signature diff, ``recompile_count{fn=}`` bump, and a
        ``recompile_storm`` escalation past the threshold).
        """
        fn = str(fn)
        key = json.dumps(signature, sort_keys=True, default=str)
        with self._lock:
            self._observations += 1
            sigs = self._signatures.setdefault(fn, {})
            if key in sigs:
                return "hit"
            prev_key = self._last_key.get(fn)
            prev_sig = sigs.get(prev_key) if prev_key is not None else None
            sigs[key] = dict(signature)
            self._last_key[fn] = key
            now = self._step_now(step)
        self.registry.counter(
            "compiled_signatures",
            "distinct (fn, abstract signature) pairs observed by the "
            "compile tracker").inc(fn=fn)
        if prev_sig is None:
            self.compiles += 1
            return "compile"
        self.recompiles += 1
        diff = signature_diff(prev_sig, signature)
        self.registry.counter(
            "recompile_count",
            "re-traces: a NEW abstract signature on a previously-"
            "compiled fn").inc(fn=fn)
        self.registry.event("recompile", fn=fn, step=now,
                            signature_diff=diff,
                            signatures=len(self._signatures[fn]))
        with self._lock:
            ring = self._recompile_steps.setdefault(fn, deque())
            ring.append(now)
            while ring and ring[0] <= now - self.storm_window:
                ring.popleft()
            storm = len(ring) >= self.storm_threshold
            count = len(ring)
            if storm:
                # escalate once per threshold-full: a persisting storm
                # re-escalates after N MORE recompiles, not per recompile
                ring.clear()
        if storm:
            self.storms += 1
            self.registry.counter(
                "recompile_storms",
                "recompile-storm escalations (> threshold recompiles "
                "of one fn inside the window)").inc(fn=fn)
            self.registry.event("recompile_storm", fn=fn, step=now,
                                count=count,
                                threshold=self.storm_threshold,
                                window_steps=self.storm_window)
        return "recompile"

    # -- compile durations (monitoring bridge) -------------------------------

    def record_compile(self, fn: str, seconds: float) -> None:
        """One actual XLA backend compile: ``compile_count{fn=}``,
        ``compile_ms{fn=}`` (most recent), the ``compile_seconds{fn=}``
        histogram, and a ``"compile"`` span into the global timeline
        (when it is on)."""
        seconds = float(seconds)
        self.registry.counter(
            "compile_count", "XLA backend compiles observed").inc(fn=fn)
        self.registry.gauge(
            "compile_ms",
            "duration of the most recent XLA backend compile").set(
            seconds * 1e3, fn=fn)
        self.registry.histogram(
            "compile_seconds", "XLA backend compile durations").observe(
            seconds, fn=fn)
        from apex_tpu.telemetry.timeline import record_global_span

        record_global_span("compile", time.perf_counter() - seconds,
                           seconds, category="compile")

    def summary(self) -> Dict[str, Any]:
        """Compact JSON-able state: per-fn signature counts plus the
        compile/recompile/storm totals — what dashboards and the
        flight recorder's ``compile_plane`` block read."""
        with self._lock:
            per_fn = {fn: len(sigs)
                      for fn, sigs in self._signatures.items()}
        return {"signatures": per_fn, "compiles": self.compiles,
                "recompiles": self.recompiles, "storms": self.storms,
                "storm_threshold": self.storm_threshold,
                "storm_window": self.storm_window}


# ---------------------------------------------------------------------------
# The process-global tracker + jax.monitoring bridge
# ---------------------------------------------------------------------------

_TRACKER: Optional[CompileTracker] = None
_LISTENER = None


def _on_duration(name: str, secs: float, **kw) -> None:
    t = _TRACKER
    if t is None or name != BACKEND_COMPILE_EVENT:
        return
    try:
        t.record_compile(current_label() or "unattributed", secs)
    except Exception:  # noqa: BLE001 — observability must not kill a compile
        pass


def _register_bridge() -> None:
    global _LISTENER
    if _LISTENER is not None:
        return
    try:
        from jax import monitoring as _monitoring

        _monitoring.register_event_duration_secs_listener(_on_duration)
        _LISTENER = _on_duration
    except Exception:  # noqa: BLE001 — no monitoring API: signatures still work
        _LISTENER = None


def _unregister_bridge() -> None:
    global _LISTENER
    if _LISTENER is None:
        return
    try:
        from jax._src import monitoring as _monitoring

        _monitoring._unregister_event_duration_listener_by_callback(
            _LISTENER)
        _LISTENER = None
    except Exception:  # noqa: BLE001 — listener self-disarms on _TRACKER None
        _LISTENER = None


def enable(**kwargs) -> CompileTracker:
    """Arm the process-global compile tracker (kwargs =
    :class:`CompileTracker`) and register the jax.monitoring bridge.
    Re-arming replaces the previous tracker (fresh signature state)."""
    global _TRACKER
    disable()
    _TRACKER = CompileTracker(**kwargs)
    _register_bridge()
    return _TRACKER


def disable() -> None:
    global _TRACKER
    _TRACKER = None
    _unregister_bridge()


def get_tracker() -> Optional[CompileTracker]:
    return _TRACKER


def observe(fn: str, signature: Dict[str, Any], *,
            step: Optional[int] = None) -> str:
    """Observe on the global tracker; ``"disabled"`` (and nothing else
    — not even an exception) when no tracker is armed."""
    t = _TRACKER
    if t is None:
        return "disabled"
    try:
        return t.observe(fn, signature, step=step)
    except Exception:  # noqa: BLE001
        return "error"


# ---------------------------------------------------------------------------
# Scope tables: which part of the model a compiled instruction belongs to
# ---------------------------------------------------------------------------

#: The parts of a step, each a ``jax.named_scope`` the models, the
#: serving programs and the trainers open around their work
#: (docs/observability.md "Scope tables"). An operation belongs to the
#: INNERMOST part on its ``op_name`` path: a Flax module's own name is
#: on the path too (``layer_3/attention/cache/kv_gather`` is the
#: cache's, ``layer_1/mlp/experts/moe_router`` the experts').
PARTS = ("embed", "attention", "cache", "mixer", "experts", "mlp", "head",
         "loss", "optimizer")
UNSCOPED = "unscoped"

# a path segment under transformations, ``transpose(jvp(attention))``:
# the wrappers, then the name
_WRAPPED = re.compile(r"^((?:[A-Za-z_][\w.]*\()*)([^()]*)\)*$")


def part_of(op_name: Optional[str]) -> Optional[str]:
    """The part of :data:`PARTS` an ``op_name`` path lies under, or
    None: its innermost segment that names one, also inside ``jvp(...)``
    and ``transpose(...)`` (the backward pass) and under ``while/body``.
    A jitted function's own segment (``jit(loss)``) is no scope. Where
    the compiler joined several paths with ``;`` the first that has a
    part speaks."""
    for path in (op_name or "").split(";"):
        for seg in reversed(path.split("/")):
            m = _WRAPPED.match(seg)
            if m and m.group(2) in PARTS and "jit(" not in m.group(1):
                return m.group(2)
    return None


_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLED = re.compile(
    r"\b(calls|body|condition|to_apply|true_computation|false_computation)"
    r"=%?([\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_REFERENCE = re.compile(r"%([\w.\-]+)")
_QUOTED = re.compile(r'"([^"\n]*)"')
# the opcodes whose called computations run as instructions of their
# own (a trace shows them); a fusion's run inside it, a reduce's
# ``to_apply`` is a scalar rule
_CONTROL = ("while", "call", "conditional", "async-start")
# how far an instruction nobody named looks for its consumers
_CONSUMER_DEPTH = 4
#: instructions that do no work of their own: what the 95%-under-a-part
#: rule of the tests leaves out
PLUMBING = ("parameter", "constant", "tuple", "get-tuple-element",
            "bitcast")


def _group(text: str) -> int:
    """Where the parenthesis ``text`` opens with closes."""
    depth = 0
    for i, ch in enumerate(text):
        depth += (ch == "(") - (ch == ")")
        if depth == 0:
            return i
    return len(text) - 1


def _split_instruction(rest: str) -> Tuple[str, str, List[str]]:
    """``(result shape, opcode, operands' names)`` of what follows
    ``%name = ``."""
    if rest.startswith("("):             # a tuple result: to its close
        i = _group(rest)
        result, tail = rest[:i + 1], rest[i + 1:].lstrip()
    else:
        result, _, tail = rest.partition(" ")
    opcode, paren, operands = tail.partition("(")
    operands = (paren + operands)[:_group(paren + operands) + 1]
    return result, opcode, _REFERENCE.findall(operands)


def parse_hlo_text(text: str) -> Dict[str, Any]:
    """One compiled program's text (``compiled.as_text()``) as a scope
    table: ``ops`` ``{instruction name -> op_name}``, ``opcodes`` and
    ``results`` (the result's shape as printed) by the same names, for
    the entry computation and every computation a ``while``, ``call``,
    conditional or asynchronous start of it runs; ``fusion_parts``
    ``{fusion's name -> the parts its fused computation holds}``; and
    ``parts`` ``{instruction name -> its part}`` for the instructions
    that have one: by their own ``op_name`` (:func:`part_of`), else a
    fusion's by the one part its computation holds, else (a weight's
    prefetch, a layout copy: the compiler's own, named by nobody) by
    the one part that consumes the result."""
    comps: Dict[str, List[Tuple[str, str, str, str, List[str]]]] = {}
    operands: Dict[str, List[str]] = {}
    entry, current = None, None
    for line in text.splitlines():
        if current is None:
            m = _COMPUTATION.match(line)
            if m:
                current = comps.setdefault(m.group(1), [])
                if line.startswith("ENTRY"):
                    entry = m.group(1)
            continue
        if line.startswith("}"):
            current = None
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        result, opcode, operands[m.group(1)] = _split_instruction(m.group(2))
        called = [c for _, c in _CALLED.findall(line)]
        for group in _BRANCHES.findall(line):
            called += [c.strip().lstrip("%") for c in group.split(",")]
        found = _OP_NAME.search(line)
        current.append((m.group(1), opcode, result,
                        found.group(1) if found else "", called))

    def parts_inside(comp: str, seen: set) -> set:
        out = set()
        if comp in seen:
            return out
        seen.add(comp)
        for _, _, _, op_name, called in comps.get(comp, ()):
            part = part_of(op_name)
            if part:
                out.add(part)
            for c in called:
                out |= parts_inside(c, seen)
        return out

    table: Dict[str, Any] = {"ops": {}, "opcodes": {}, "results": {},
                             "fusion_parts": {}, "parts": {}}
    todo, done = [entry] if entry else [], set()
    while todo:
        comp = todo.pop()
        if comp in done:
            continue
        done.add(comp)
        for name, opcode, result, op_name, called in comps.get(comp, ()):
            table["ops"][name] = op_name
            table["opcodes"][name] = opcode
            table["results"][name] = result
            if opcode == "fusion":
                inside = set()
                for c in called:
                    inside |= parts_inside(c, set())
                table["fusion_parts"][name] = sorted(inside)
            elif opcode in _CONTROL:
                todo += called
    parts = table["parts"]
    for name, op_name in table["ops"].items():
        inside = table["fusion_parts"].get(name, ())
        part = part_of(op_name) or (inside[0] if len(inside) == 1 else None)
        if part:
            parts[name] = part
    users: Dict[str, List[str]] = {}
    for name in table["ops"]:
        for operand in operands[name]:
            users.setdefault(operand, []).append(name)
    for name in table["ops"]:
        if name in parts or table["opcodes"][name] in PLUMBING:
            continue
        # the parts of the nearest consumers that have one, through
        # those that have none
        found, seen, front = set(), {name}, [name]
        for _ in range(_CONSUMER_DEPTH):
            front = [u for n in front for u in users.get(n, ())
                     if u not in seen and not seen.add(u)]
            found |= {parts[u] for u in front if u in parts}
            front = [u for u in front if u not in parts]
        if len(found) == 1:
            parts[name] = found.pop()
    return table


def _abstract(tree):
    """``tree`` with every array replaced by its shape, dtype and
    sharding: what a program can be lowered from again once its donated
    arguments are gone."""
    import jax

    def leaf(x):
        if not hasattr(x, "shape") or not hasattr(x, "dtype"):
            return x                    # None, a Python scalar
        # an uncommitted array's sharding is no part of the program's
        # key: naming it here would compile a second program
        placed = getattr(x, "committed", False)
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=x.sharding if placed else None,
            weak_type=bool(getattr(x, "weak_type", False)))

    return jax.tree.map(leaf, tree)


# (name, signature key) -> what register_program was told; process-wide,
# armed or not (the tables are asked for after the fact), the newest
# MAX_PROGRAMS of them
_PROGRAMS: Dict[Tuple[str, str], Dict[str, Any]] = {}
_PROGRAMS_LOCK = threading.Lock()
#: The registry keeps the jitted functions themselves, and no more than
#: this many: whoever asks for the tables asks after the fact, when the
#: step that ran the programs may be gone (a benchmark's reader runs
#: once its driver has returned and dropped the engine), so a weak
#: hold hands out nothing. A function holds its closures and jax's
#: traces of it, no array; past the bound the oldest goes.
MAX_PROGRAMS = 128


def register_program(name: str, signature: Dict[str, Any], jitted,
                     args: tuple) -> None:
    """The entry point is about to run a NEW program: ``name`` as a
    device trace prints it (``jit_decode_fn``), its abstract
    ``signature`` (:meth:`CompileTracker.observe`'s), the jitted
    function and the arguments of the call, kept as shapes, dtypes and
    shardings alone (the state is donated). One dict write; never
    raises."""
    try:
        key = (str(name), json.dumps(signature, sort_keys=True, default=str))
        if not callable(getattr(jitted, "lower", None)):
            return
        entry = {"name": str(name), "signature": dict(signature),
                 "jitted": jitted, "args": _abstract(args)}
        with _PROGRAMS_LOCK:
            _PROGRAMS.pop(key, None)
            _PROGRAMS[key] = entry
            while len(_PROGRAMS) > MAX_PROGRAMS:
                del _PROGRAMS[next(iter(_PROGRAMS))]
    except Exception:  # noqa: BLE001 — observability must not stop a step
        pass


def _scope_table(entry: Dict[str, Any]) -> Dict[str, Any]:
    lowered = entry["jitted"].lower(*entry["args"])
    table = parse_hlo_text(lowered.compile().as_text())
    # what this tree's source opens, from the lowering's locations: an
    # executable loaded from a persistent-cache entry an older tree
    # wrote carries THAT tree's scopes (metadata is not in the key)
    opened = {part_of(path) for path in set(_QUOTED.findall(
        lowered.as_text(debug_info=True)))} - {None}
    held = set(table["parts"].values())
    for inside in table["fusion_parts"].values():
        held |= set(inside)
    table.update(name=entry["name"], signature=entry["signature"],
                 missing_parts=sorted(opened - held))
    return table


def scope_tables() -> List[Dict[str, Any]]:
    """One scope table (:func:`parse_hlo_text`, with ``name``,
    ``signature`` and ``missing_parts``: the parts this tree's source
    opens and the compiled text lacks) for every registered program,
    oldest first. Lowering and compiling from the registered shapes is
    an in-memory hit on a program that has run: nothing compiles. Each
    text is parsed once; the table is then kept in the function's
    place. A program that cannot be lowered any more is left out."""
    with _PROGRAMS_LOCK:
        entries = list(_PROGRAMS.values())
    out = []
    for entry in entries:
        if "table" not in entry:
            try:
                entry["table"] = _scope_table(entry)
            except Exception:  # noqa: BLE001 — a reader gets what there is
                continue
            del entry["jitted"], entry["args"]
        out.append(entry["table"])
    return out


def forget_programs() -> None:
    """Empty the registry (a test's clean slate; a long-lived process
    that has rebuilt its steps)."""
    with _PROGRAMS_LOCK:
        _PROGRAMS.clear()


__all__ = [
    "BACKEND_COMPILE_EVENT",
    "CompileTracker",
    "MAX_PROGRAMS",
    "PARTS",
    "PLUMBING",
    "UNSCOPED",
    "abstract_signature",
    "current_label",
    "disable",
    "enable",
    "forget_programs",
    "get_tracker",
    "label",
    "observe",
    "parse_hlo_text",
    "part_of",
    "register_program",
    "scope_tables",
    "signature_diff",
]
