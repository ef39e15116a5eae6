"""The share of the device's time spent in operations that match, in
percent of its busy time (or, with ``"over": "window"``, of the traced
window), summed over the chips used.

``params``: any of ``{"name": "<regex on the operation's name>",
"opcode": "<regex on its opcode>", "shape_has": "<a key of run.facts>",
"over": "busy" | "window"}``. ``shape_has`` keeps the operations whose
HLO text holds that number as a dimension. An operation's time is its
self time on the ``XLA Ops`` line, which is the core's own timeline: a
collective counted there (a synchronous one, or the wait in its
``-done``) is time in which that core computes nothing.
"""

import re


def read(params, run):
    red = run.reduced
    if red is None or not red.ops:
        return None
    name = re.compile(params.get("name", ""))
    opcode = re.compile(params.get("opcode", ""))
    dim = run.facts.get(params.get("shape_has"))
    dim_rx = re.compile(rf"[\[,]{dim}[,\]]") if dim is not None else None
    hit = busy = 0.0
    for ops in red.ops.values():
        for op in ops:
            busy += op.self_ns
            if (name.search(op.name)
                    and opcode.search(str(op.stats.get("opcode", "")))
                    and (dim_rx is None or dim_rx.search(
                        str(op.stats.get("long_name", op.name))))):
                hit += op.self_ns
    if params.get("over") == "window":
        busy = red.window_s * 1e9 * len(red.ops)
    return 100.0 * hit / busy if busy else None
