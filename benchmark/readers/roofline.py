"""A kernel's share of its roofline, in percent: the least time the chip
could take for its calls (``costs.least_seconds`` of the operations and
bytes a cost function computes from each call's shapes) over the device
time the calls took in the traced window.

``params``: ``{"name": "<regex on the operation's name>", "opcode":
"custom-call", "cost": "<module>:<function>"}``; the function takes
``(results, operands)`` as ``trace_reduce.shapes`` gives them. The
notes say which bound, compute or memory, gave the least time.
"""

import importlib
import re

from benchmark import costs, trace_reduce


def read(params, run):
    red = run.reduced
    if red is None or not red.ops:
        return None
    module, _, fn = params["cost"].partition(":")
    cost = getattr(importlib.import_module(module), fn)
    rx = re.compile(params["name"])
    least = took = 0.0
    bounds = {}
    memo = {}
    for ops in red.ops.values():
        for op in ops:
            if (op.stats.get("opcode") != params.get("opcode", "custom-call")
                    or not rx.search(op.name)):
                continue
            text = op.stats["long_name"]
            if text not in memo:
                result = str(op.stats["result"])
                operands = text.partition(result)[2]
                memo[text] = costs.least_seconds(
                    *cost(trace_reduce.shapes(result),
                          trace_reduce.shapes(
                              operands.partition("custom_call_target")[0])),
                    run.peaks)
            seconds, bound = memo[text]
            least += seconds
            took += op.self_ns * 1e-9
            bounds[bound] = bounds.get(bound, 0.0) + seconds
    if not took:
        return None
    run.notes.append(f"roofline {params['name']}: least {least:.4f} s of "
                     f"{took:.4f} s, bound by {bounds}")
    return 100.0 * least / took
