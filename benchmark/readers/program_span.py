"""The device's idle time by what the host was doing: each idle interval
of the traced window is cut at the edges of the program's own spans and
every piece goes to the innermost span that covers it.

The program's spans are the host events whose name starts with
``apex.`` (``apex_tpu/telemetry/timeline.py`` ``span``: a
``TraceAnnotation``, so they lie on the trace's clock). A gap of the
serving engine runs from the end of one program through ``wait``,
``fetch``, the step's books, the benchmark's own ``observe`` and
``submit``, into the next ``build`` and ``dispatch``: it crosses ten
spans, which is why it is cut and not given whole to the span over its
middle (``trace_reduce.idle_gaps``). The innermost span is the shortest
one that covers the piece; a piece no span covers is *outside*: the
benchmark's own share of the gap.

The trace lays the device's timeline against the host's only to within
a millisecond: four of PR 26's nineteen traced serving runs stamped
every program about 1.0 ms *before* the host's call that launches it
(``LAUNCH``), the others within 0.3 ms of it (PERF.md section 5). That moves
the border between ``dispatch`` and ``wait`` and nothing else, so the
device's timeline is first moved later by the most that any program
starts before its launch (``stamped_early``): no program then precedes
its cause, and the earliest starts with it. Where the trace does not
hold one launch for each program of the first device (another runtime,
several chips, programs launched before the trace began) nothing is
moved, and the run's note says which.

``params``: ``{"innermost": "<regex on the innermost span's name>",
"of": "idle_ms_per_step" | "count_per_step"}``. ``idle_ms_per_step`` is
the first device's idle time in the pieces whose innermost span
matches, in milliseconds per traced step; ``count_per_step`` the number
of spans whose name matches, per traced step. No device plane, no
``apex.`` span or no traced steps: nothing is returned. The trace is
parsed once a run, and the first metric read leaves one note with the
whole split.
"""

import bisect
import re

from benchmark import trace_reduce

PREFIX = "apex."
OUTSIDE = "(outside)"
LAUNCH = "tpu::System::Execute"     # the runtime starts a program


def host_events(profile, window, keep):
    """``(name, start, end)`` of the host events whose name ``keep``
    accepts, clipped to the window, by start."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for e in line.events:
                if not keep(e.name):
                    continue
                a = max(float(e.start_ns), window[0])
                b = min(float(e.start_ns) + float(e.duration_ns), window[1])
                if b > a:
                    out.append((e.name, a, b))
    return sorted(out, key=lambda s: s[1])


def program_spans(profile, window):
    """The ``apex.`` host events."""
    return host_events(profile, window, lambda name: name.startswith(PREFIX))


def stamped_early(profile, red):
    """Nanoseconds by which the trace stamps the first device's
    programs early: the most that one starts before the host's launch
    of it, which cannot be. Nothing (``None``) where the window does
    not hold one launch for each program."""
    launches = host_events(profile, red.window, lambda name: name == LAUNCH)
    programs = red.modules.get(min(red.ops), [])
    if not launches or len(launches) != len(programs):
        return None
    return max(0.0, max(launch[1] - program.start
                        for launch, program in zip(launches, programs)))


def pieces(spans):
    """``(start, end, name)`` for every stretch between two neighbouring
    span edges that some span covers, ``name`` the shortest such span."""
    points = sorted({t for _, a, b in spans for t in (a, b)})
    out, active, k = [], [], 0
    for t0, t1 in zip(points, points[1:]):
        while k < len(spans) and spans[k][1] <= t0:
            active.append(spans[k])
            k += 1
        active = [s for s in active if s[2] > t0]
        if active:
            out.append((t0, t1, min(active, key=lambda s: s[2] - s[1])[0]))
    return out


def idle_by_span(idle, spans):
    """Nanoseconds of the ``idle`` intervals by innermost span name;
    what no span covers is under ``OUTSIDE``."""
    cut = pieces(spans)
    starts = [p[0] for p in cut]
    out = {OUTSIDE: 0.0}
    for a, b in idle:
        covered = 0.0
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        while i < len(cut) and cut[i][0] < b:
            lo, hi = max(a, cut[i][0]), min(b, cut[i][1])
            if hi > lo:
                out[cut[i][2]] = out.get(cut[i][2], 0.0) + hi - lo
                covered += hi - lo
            i += 1
        out[OUTSIDE] += (b - a) - covered
    return out


def idle_intervals(red, later=0.0):
    """The first device's idle intervals inside the window, as
    ``trace_reduce.idle_gaps`` takes them, with the device's timeline
    moved ``later`` nanoseconds."""
    t0, t1 = red.window
    edges = [t0] + [min(t + later, t1)
                    for iv in red.busy_intervals(min(red.ops))
                    for t in iv] + [t1]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def split(profile, red, steps):
    """``{"idle_ms": {span name: per step}, "count": {...}, "span_ms":
    {...}, "early_ns": ...}`` of one traced run whose trace holds a
    device plane, or nothing where it holds no ``apex.`` span."""
    spans = program_spans(profile, red.window)
    if not spans:
        return None
    early = stamped_early(profile, red)
    idle = idle_by_span(idle_intervals(red, early or 0.0), spans)
    count, span_ms = {}, {}
    for name, a, b in spans:
        count[name] = count.get(name, 0.0) + 1.0 / steps
        span_ms[name] = span_ms.get(name, 0.0) + (b - a) * 1e-6 / steps
    return {"idle_ms": {k: v * 1e-6 / steps for k, v in idle.items()},
            "count": count, "span_ms": span_ms, "early_ns": early}


def note(found):
    idle = found["idle_ms"]
    rows = [f"{name} {found['count'][name]:.2f} x "
            f"{found['span_ms'][name] / found['count'][name]:.3f} ms, idle "
            f"{idle.get(name, 0.0):.3f}" for name in sorted(found["count"])]
    # what the pin cannot move: where it could not be made, compare this
    launch = sum(v for name, v in idle.items()
                 if name.endswith((".dispatch", ".wait", ".fetch")))
    early = found["early_ns"]
    pinned = ("as the trace stamps it (no launch event for each program: "
              "the border between dispatch and wait may lie a millisecond "
              "off)" if early is None else
              f"moved {early * 1e-6:.3f} ms later, to where no program "
              f"starts before its launch")
    return ("program spans, per traced step (count x mean ms, device idle "
            "ms inside and innermost): " + "; ".join(rows)
            + f"; idle in dispatch + wait + fetch {launch:.3f} ms, the "
            f"device's timeline {pinned}; idle outside apex.serve.step and "
            f"every other apex. span (the benchmark's own share) "
            f"{idle[OUTSIDE]:.3f} ms of {sum(idle.values()):.3f}")


def split_of(run):
    """The run's split, computed at the first call and kept on the
    run: every metric of this reader reads the one parse."""
    if not hasattr(run, "_program_spans"):
        steps = run.counters.get("traced_steps")
        red = run.reduced
        found = (split(trace_reduce.load(run.trace_dir), red, steps)
                 if red is not None and red.ops and steps else None)
        if found is not None:
            run.notes.append(note(found))
        run._program_spans = found
    return run._program_spans


def read(params, run):
    found = split_of(run)
    if found is None:
        return None
    rx = re.compile(params["innermost"])
    table = {"idle_ms_per_step": found["idle_ms"],
             "count_per_step": found["count"]}[params["of"]]
    return sum(v for name, v in table.items() if rx.search(name))
