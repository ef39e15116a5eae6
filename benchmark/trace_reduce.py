"""From a profiler trace to numbers: device-op intervals, busy and idle
time, time per operation and per program, idle gaps by host span.

The input is anything shaped like ``jax.profiler.ProfileData``: planes
with a ``name`` and ``lines``; lines with a ``name`` and ``events``;
events with ``name``, ``start_ns``, ``duration_ns`` and ``stats`` (an
iterable of pairs). :func:`load` reads the ``.xplane.pb`` a traced run
wrote; :func:`from_json` reads the same shape from a JSON file, which is
how the recorded fixture under ``tests/`` is kept.

What is read, and nothing else:

- device planes (``/device:TPU:<n>``), line ``XLA Ops``: one event per
  executed HLO operation, named by the instruction's whole text
  (``%fusion.2 = bf16[8,2048]{1,0} fusion(...)``): :func:`parse_hlo`
  cuts that to the instruction's name and keeps the text as the
  ``long_name`` stat, the opcode as ``opcode``. Events nest (a
  ``while`` holds the operations of its body), so *busy* is the union
  of the intervals and an operation's time is its *self* time: its
  interval less what its children cover.
- the same planes, line ``XLA Modules``: one event per executed program.
- the host plane (``/host:CPU``), every line: the events whose name
  starts with ``bench.`` — the benchmark's own ``TraceAnnotation``
  spans, on the trace's clock. ``bench.traced`` is the traced window:
  everything is clipped to it.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
from types import SimpleNamespace
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
WINDOW_SPAN, SPAN_PREFIX = "bench.traced", "bench."

Interval = Tuple[float, float]


@dataclasses.dataclass
class Op:
    name: str
    start: float          # ns
    end: float            # ns
    self_ns: float
    stats: Dict[str, object]


@dataclasses.dataclass
class Reduced:
    window: Interval                      # ns, the traced window
    ops: Dict[int, List[Op]]              # device ordinal -> ops, clipped
    modules: Dict[int, List[Op]]          # device ordinal -> programs
    spans: List[Tuple[str, float, float]]  # host spans (name, start, end)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_intervals(self, device: int) -> List[Interval]:
        return union((o.start, o.end) for o in self.ops[device])

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over devices."""
        per = [total(self.busy_intervals(d)) for d in self.ops]
        return sum(per) / len(per) * 1e-9 if per else 0.0

    def op_seconds(self, key=lambda op: op.name) -> Dict[str, float]:
        """Self time by operation name (or ``key(op)``), averaged over
        devices."""
        out: Dict[str, float] = {}
        for ops in self.ops.values():
            for o in ops:
                out[key(o)] = out.get(key(o), 0.0) + o.self_ns
        return {k: v * 1e-9 / len(self.ops) for k, v in out.items()}

    def module_calls(self, pattern: str) -> List[float]:
        """Seconds of each call of the programs whose name matches,
        on the first device."""
        rx = re.compile(pattern)
        first = min(self.modules) if self.modules else None
        return [(m.end - m.start) * 1e-9
                for m in self.modules.get(first, []) if rx.search(m.name)]

    def idle_gaps(self) -> List[Tuple[str, float]]:
        """The first device's idle time inside the window, by the
        benchmark span the host was in (the innermost one that covers
        the gap's middle; ``(no span)`` otherwise), longest first."""
        if not self.ops:
            return []
        first = min(self.ops)
        t0, t1 = self.window
        edges = [t0] + [t for iv in self.busy_intervals(first)
                        for t in iv] + [t1]
        out: Dict[str, float] = {}
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            mid = (a + b) / 2
            cover = [s for s in self.spans
                     if s[1] <= mid <= s[2] and s[0] != WINDOW_SPAN]
            name = (min(cover, key=lambda s: s[2] - s[1])[0]
                    if cover else "(no span)")
            out[name] = out.get(name, 0.0) + (b - a) * 1e-9
        return sorted(out.items(), key=lambda kv: -kv[1])


def union(intervals) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals) -> float:
    return sum(b - a for a, b in intervals)


def self_times(events: List[Op]) -> None:
    """Set ``self_ns`` of each event of one line: its duration less the
    part its direct children cover (events nest, never cross)."""
    stack: List[Op] = []
    for e in sorted(events, key=lambda e: (e.start, -(e.end - e.start))):
        while stack and stack[-1].end <= e.start:
            stack.pop()
        e.self_ns = e.end - e.start
        if stack:
            stack[-1].self_ns -= min(e.end, stack[-1].end) - e.start
        stack.append(e)


SHAPE = re.compile(r"\b([a-z]+\d+|pred)\[([\d,]*)\]")


def parse_hlo(text: str) -> Tuple[str, str, str]:
    """``(name, opcode, result)`` of an HLO instruction's text,
    ``%name = <result shape> opcode(operands), attributes``; a text of
    another form is its own name."""
    head, eq, rest = text.partition(" = ")
    if not eq:
        return text, "", ""
    if rest.startswith("("):             # a tuple result: to its close
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        result, tail = rest[:i + 1], rest[i + 1:].lstrip()
    else:
        result, _, tail = rest.partition(" ")
    return head.lstrip("%"), tail.partition("(")[0], result


def shapes(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """Every ``dtype[dims]`` in a piece of HLO text, in order."""
    return [(t, tuple(int(d) for d in dims.split(",") if d))
            for t, dims in SHAPE.findall(text)]


def label(op: "Op") -> str:
    """A short name for a breakdown: name, opcode and the first result
    shape, in the characters a metric name may have."""
    first = shapes(str(op.stats.get("result", "")))[:1]
    parts = [op.name, str(op.stats.get("opcode", ""))] + [
        t + "_" + "_".join(map(str, dims)) for t, dims in first]
    return re.sub(r"[^A-Za-z0-9_.\-]", "_", "_".join(p for p in parts if p))


def _events(line, window: Optional[Interval]) -> List[Op]:
    out = []
    for e in line.events:
        a, b = float(e.start_ns), float(e.start_ns) + float(e.duration_ns)
        if window is not None:
            a, b = max(a, window[0]), min(b, window[1])
            if b <= a:
                continue
        stats = dict(e.stats)
        name, opcode, result = parse_hlo(e.name)
        if opcode:
            stats.update(long_name=e.name, opcode=opcode, result=result)
        out.append(Op(name, a, b, b - a, stats))
    return out


def reduce(profile) -> Reduced:
    """Reduce a ``ProfileData``-shaped object (module docstring)."""
    spans: List[Tuple[str, float, float]] = []
    for plane in profile.planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, float(e.start_ns),
                                      float(e.start_ns)
                                      + float(e.duration_ns)))
    spans.sort(key=lambda s: s[1])
    windows = [s for s in spans if s[0] == WINDOW_SPAN]
    window = (windows[0][1], windows[-1][2]) if windows else None
    ops: Dict[int, List[Op]] = {}
    modules: Dict[int, List[Op]] = {}
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        for line in plane.lines:
            if line.name == OPS_LINE:
                evs = _events(line, window)
                self_times(evs)
                ops[int(m.group(1))] = evs
            elif line.name == MODULES_LINE:
                modules[int(m.group(1))] = _events(line, window)
    if window is None:
        every = [t for evs in ops.values() for o in evs
                 for t in (o.start, o.end)]
        window = (min(every), max(every)) if every else (0.0, 0.0)
    return Reduced(window, ops, modules, spans)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(trace_dir: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(find_xplane(trace_dir))


def from_json(path: str):
    """A ``ProfileData``-shaped object from ``{"planes": [{"name",
    "lines": [{"name", "events": [[name, start_ns, duration_ns,
    {stats}], ...]}]}]}``."""
    with open(path) as f:
        doc = json.load(f)
    return SimpleNamespace(planes=[SimpleNamespace(
        name=p["name"], lines=[SimpleNamespace(
            name=ln["name"], events=[SimpleNamespace(
                name=e[0], start_ns=e[1], duration_ns=e[2],
                stats=list((e[3] if len(e) > 3 else {}).items()))
                for e in ln["events"]])
            for ln in p["lines"]]) for p in doc["planes"]])


def to_json(profile, path: str, per_line: int = 400) -> None:
    """Write the first ``per_line`` events of every device and
    ``bench.`` host line in the shape :func:`from_json` reads: how a cut
    of a chip trace is brought back to look at and to keep."""
    planes = []
    for plane in profile.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            evs = [e for e in line.events
                   if device or e.name.startswith(SPAN_PREFIX)]
            if evs:
                lines.append({"name": line.name, "events": [
                    [e.name[:2000], e.start_ns, e.duration_ns,
                     {k: (v if isinstance(v, (int, float, str)) else str(v))
                      for k, v in dict(e.stats).items()}]
                    for e in evs[:per_line]]})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    with open(path, "w") as f:
        json.dump({"planes": planes}, f)
