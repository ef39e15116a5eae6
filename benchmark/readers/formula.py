"""A per-layer metric as arithmetic over what the run counted.

``params``: ``{"expr": "<python expression>"}`` over the driver's
counters, the cell's end-to-end values, ``window_s`` and, in a traced
run, ``trace_window_s`` and ``busy_s``. A name the run does not have
means the metric is not in this run: nothing is returned.
"""


def read(params, run):
    names = dict(run.counters)
    names.update(run.end_to_end)
    names["window_s"] = run.window_s
    if run.reduced is not None:
        names["trace_window_s"] = run.reduced.window_s
        names["busy_s"] = run.reduced.busy_s()
    try:
        return eval(params["expr"], {"__builtins__": {}}, names)
    except (NameError, ZeroDivisionError):
        return None
