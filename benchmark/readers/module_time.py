"""Device time per call of the programs whose name matches, in
milliseconds, from the trace's ``XLA Modules`` line.

``params``: ``{"module": "<regex>", "stat": "mean" | "median" | ...}``.
"""

from benchmark import common


def read(params, run):
    if run.reduced is None:
        return None
    calls = run.reduced.module_calls(params["module"])
    return 1e3 * common.stat(calls, params.get("stat", "mean")) \
        if calls else None
