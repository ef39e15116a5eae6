"""Cut the first engine steps of a traced serving run out of its
``.xplane.pb``, in the shape ``trace_reduce.from_json`` reads: how
``fixtures/trace_serve_spans.json`` was made.

    python3 benchmark/tests/cut_trace.py <trace dir> <out.json> [steps]

Kept: the ``XLA Ops`` and ``XLA Modules`` lines of every device plane
and the host events named ``bench.*`` or ``apex.*`` and the runtime's
launches (``program_span.LAUNCH``), all clipped to
[start of ``bench.traced``, start of the benchmark's submit span that
opens engine step ``steps``]: the cut's window is whole steps. Of the
operations only the outermost stay: a decode step runs some 3,000, all
but 55 of them inside the layer loop's ``while``, and the union of the
intervals, from which busy and idle time are computed, is the same
without them (an operation's self time is not: this cut is for the
readers of idle time). Operation names are cut to ``name_chars``
characters (the instruction's name, result and opcode survive), stats
dropped (they are empty). ``trace_reduce.to_json`` keeps the first
events of each line and drops ``apex.`` events, so it cannot make this
cut.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import trace_reduce  # noqa: E402
from benchmark.readers.program_span import LAUNCH  # noqa: E402


def keep(name: str) -> bool:
    return name.startswith(("bench.", "apex.")) or name == LAUNCH


def cut(profile, steps: int, name_chars: int = 120) -> dict:
    host = [e for p in profile.planes if p.name.startswith("/host:CPU")
            for ln in p.lines for e in ln.events if keep(e.name)]
    traced = next(e for e in host if e.name == trace_reduce.WINDOW_SPAN)
    submits = sorted(e.start_ns for e in host
                     if e.name == "bench.serve.submit"
                     and e.start_ns >= traced.start_ns)
    t0, t1 = traced.start_ns, submits[steps]

    def clipped(events, outermost=False):
        out, end = [], t0
        for e in sorted(events, key=lambda e: (e.start_ns, -e.duration_ns)):
            a, b = max(e.start_ns, t0), min(e.start_ns + e.duration_ns, t1)
            if b > a and not (outermost and b <= end):
                out.append([e.name[:name_chars], a, b - a])
                end = max(end, b)
        return out

    planes = []
    for p in profile.planes:
        if trace_reduce.DEVICE_PLANE.match(p.name):
            lines = [{"name": ln.name, "events": clipped(
                ln.events, outermost=ln.name == trace_reduce.OPS_LINE)}
                for ln in p.lines if ln.name in (
                    trace_reduce.OPS_LINE, trace_reduce.MODULES_LINE)]
        elif p.name.startswith("/host:CPU"):
            lines = [{"name": ln.name, "events": clipped(
                e for e in ln.events if keep(e.name))}
                for ln in p.lines]
        else:
            continue
        lines = [ln for ln in lines if ln["events"]]
        if lines:
            planes.append({"name": p.name, "lines": lines})
    return {"planes": planes}


if __name__ == "__main__":
    trace_dir, out = sys.argv[1], sys.argv[2]
    doc = cut(trace_reduce.load(trace_dir),
              int(sys.argv[3]) if len(sys.argv) > 3 else 4)
    with open(out, "w") as f:
        json.dump(doc, f, separators=(",", ":"))
