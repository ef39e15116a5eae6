"""Device time per call of the programs whose name matches, split by
the part of the model each operation belongs to, in milliseconds.

The program side is ``apex_tpu.telemetry.compiled.scope_tables()``: one
table for every program the entry points registered, ``{instruction
name -> part}`` from the compiled text's ``op_name`` metadata (the
model's ``jax.named_scope`` parts: ``compiled.PARTS``), with the result
shape of every instruction and the parts each fusion holds. The trace
side is the first device's timeline: every execution on its ``XLA
Modules`` line goes to the registered program of that name whose
instructions are the ones that ran inside it (several shape buckets
share ``jit_decode_fn``: an event's name and its result's shape tell
them apart), and every ``XLA Ops`` event inside it gives its *self*
time to its instruction's part. What no part claims, and the time
inside a call in which no operation ran, is ``unscoped``: the parts and
``unscoped`` add up to the calls' device time.

``params``: ``{"module": "<regex on the program's name>", "part": "<a
part, several joined by '+', or 'unscoped'>", "of": "ms_per_call"}``.
No device plane, no registered program (an older tree has no
``scope_tables``) or no call of a matching program: nothing is
returned. A program whose compiled text lacks a part its source opens
(an executable loaded from a persistent-cache entry an older tree
wrote: metadata is not in the cache's key) is left out, and the note
names the parts. The trace is joined once a run, and the first metric
read leaves one note with the whole split.
"""

import bisect
import re
import time

from benchmark import trace_reduce

UNSCOPED = "unscoped"


def tables_of():
    """The scope tables of the process's programs and the seconds the
    pull took, or nothing where the program has none to give."""
    try:
        from apex_tpu.telemetry import compiled

        pull = compiled.scope_tables
    except (ImportError, AttributeError):
        return None, 0.0
    t0 = time.perf_counter()
    tables = pull()
    return tables, time.perf_counter() - t0


def signed(name, result):
    """What tells an instruction of one shape bucket from the same name
    in another: its name and its result's shapes."""
    return name, tuple(trace_reduce.shapes(result))


def program_of(inside, tables):
    """The table, of those that share the executed program's name,
    whose instructions are the ones that ran: the most events whose
    name and result it holds."""
    ran = {signed(op.name, str(op.stats.get("result", ""))) for op in inside}
    best, best_hits = None, -1
    for table in tables:
        results = table["results"]
        hits = sum(1 for name, shape in ran
                   if name in results
                   and signed(name, results[name])[1] == shape)
        if hits > best_hits:
            best, best_hits = table, hits
    return best


def split(red, tables):
    """``{program name: {"calls", "ns" {part: ...}, "device_ns",
    "fused_ns", "left" {op_name stem: ns}, "missing" [parts],
    "buckets"}}`` of one traced run's first device."""
    first = min(red.ops)
    ops = sorted(red.ops[first], key=lambda o: o.start)
    starts = [o.start for o in ops]
    by_name = {}
    for table in tables:
        by_name.setdefault(table["name"], []).append(table)
    chosen, out = {}, {}
    for module in red.modules.get(first, []):
        base = module.name.partition("(")[0]
        if base not in by_name:
            continue
        lo = bisect.bisect_left(starts, module.start)
        hi = bisect.bisect_right(starts, module.end)
        inside = [o for o in ops[lo:hi] if o.end <= module.end]
        if module.name not in chosen:
            chosen[module.name] = program_of(inside, by_name[base])
        table = chosen[module.name]
        found = out.setdefault(base, {
            "calls": 0, "ns": {}, "device_ns": 0.0, "fused_ns": 0.0,
            "left": {}, "missing": set(), "buckets": set()})
        found["buckets"].add(module.name)
        found["missing"] |= set(table["missing_parts"])
        found["calls"] += 1
        found["device_ns"] += module.end - module.start
        ran = 0.0
        for op in inside:
            part = table["parts"].get(op.name, UNSCOPED)
            found["ns"][part] = found["ns"].get(part, 0.0) + op.self_ns
            ran += op.self_ns
            if len(table["fusion_parts"].get(op.name, ())) > 1:
                found["fused_ns"] += op.self_ns
            if part == UNSCOPED:
                stem = table.get("ops", {}).get(op.name) or str(
                    op.stats.get("opcode", "")) or op.name
                found["left"][stem] = found["left"].get(stem, 0.0) \
                    + op.self_ns
        # a stretch of a call in which no operation ran is no part's
        idle = max(module.end - module.start - ran, 0.0)
        found["ns"][UNSCOPED] = found["ns"].get(UNSCOPED, 0.0) + idle
        found["left"]["(no operation)"] = found["left"].get(
            "(no operation)", 0.0) + idle
    return out


def note(found, pulled_s, compiled_during):
    rows = []
    for name, f in sorted(found.items()):
        per = 1e-6 / f["calls"]
        parts = ", ".join(
            f"{part} {ns * per:.3f}"
            for part, ns in sorted(f["ns"].items(), key=lambda kv: -kv[1]))
        left = ", ".join(
            f"{stem} {ns * per:.3f}" for stem, ns in sorted(
                f["left"].items(), key=lambda kv: -kv[1])[:4])
        total = sum(f["ns"].values())
        row = (f"{name} {f['calls']} calls of {len(f['buckets'])} "
               f"program(s), {f['device_ns'] * per:.3f} ms a call: {parts}; "
               f"{100.0 * f['fused_ns'] / total if total else 0.0:.1f}% in "
               f"fusions that hold two parts or more (given to the "
               f"fusion's own); unscoped is {left}")
        if f["missing"]:
            row += ("; NOT REPORTED: the compiled text lacks "
                    + ", ".join(sorted(f["missing"]))
                    + ", which the source opens (an executable from an "
                    "older tree's cache entry)")
        rows.append(row)
    return ("device ms a call by part of the model (compiled.scope_tables, "
            f"pulled in {pulled_s:.2f} s with {compiled_during} compile "
            f"request(s)): " + " | ".join(rows))


def split_of(run):
    """The run's split, computed at the first call and kept on the
    run: every metric of this reader reads the one join."""
    if not hasattr(run, "_scope_time"):
        red, found = run.reduced, None
        if red is not None and red.ops and red.modules:
            before = getattr(run.compiles, "n", 0)
            tables, pulled_s = tables_of()
            if tables:
                found = split(red, tables)
                run.notes.append(note(
                    found, pulled_s, getattr(run.compiles, "n", 0) - before))
        run._scope_time = found
    return run._scope_time


def read(params, run):
    found = split_of(run)
    if not found:
        return None
    rx = re.compile(params["module"])
    parts = params["part"].split("+")
    ns = calls = 0.0
    for name, f in found.items():
        if not rx.search(name):
            continue
        if f["missing"]:
            return None
        calls += f["calls"]
        ns += sum(f["ns"].get(part, 0.0) for part in parts)
    return ns * 1e-6 / calls if calls else None
