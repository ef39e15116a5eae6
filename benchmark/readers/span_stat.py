"""A statistic of one of the driver's host-clock sample lists.

``params``: ``{"samples": "<name>", "stat": "median" | "mean" | "iqm" |
"p<q>"}``. No samples, no metric.
"""

from benchmark import common


def read(params, run):
    values = run.samples.get(params["samples"])
    return common.stat(values, params["stat"]) if values else None
