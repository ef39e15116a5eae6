"""The benchmark's one command.

    python3 benchmark/run.py --workload <config>.<traffic> --seed N \
        --seconds S --trace 0|1

A cell is ``<config>.<traffic>``: ``BENCHMARK.json`` names it,
``configs/<config>.json`` holds the sizes, ``traffic/<traffic>.json``
the work and the name of the driver (``drivers/<driver>.py``) that deals
it. With ``--trace 0`` the last line of standard output holds the cell's
end-to-end metrics, taken from the host clock; with ``--trace 1`` part
of the window runs under the profiler and the line holds the per-layer
metrics, each computed by the reader (``readers/<reader>.py``) that
``layer_metrics/<metric>.json`` names. Nothing here knows a cell, a
metric or a reader by name. Without a TPU of a kind listed in
``peaks.json`` the command fails and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()       # set-up is counted from here

import argparse                     # noqa: E402
import dataclasses                  # noqa: E402
import importlib                    # noqa: E402
import json                         # noqa: E402
import os                           # noqa: E402
import sys                          # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def say(msg: str) -> None:
    print(f"# {msg}", flush=True)


def load_json(*parts: str) -> Any:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find(data_dirs: List[str], *parts: str) -> str:
    for d in data_dirs:
        path = os.path.join(d, *parts)
        if os.path.exists(path):
            return path
    raise FileNotFoundError(f"{os.path.join(*parts)} under {data_dirs}")


@dataclasses.dataclass
class Run:
    """What a driver is given and what it hands back to the readers."""

    cell: str
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    seed: int
    seconds: float
    trace: bool
    devices: list
    peaks: Dict[str, float]
    trace_dir: str
    t_start: float
    # filled by the driver
    setup_s: float = 0.0
    window_s: float = 0.0
    end_to_end: Dict[str, float] = dataclasses.field(default_factory=dict)
    counters: Dict[str, float] = dataclasses.field(default_factory=dict)
    samples: Dict[str, List[float]] = dataclasses.field(default_factory=dict)
    facts: Dict[str, Any] = dataclasses.field(default_factory=dict)
    attempted: int = 0
    failed: int = 0
    correct: bool = False
    notes: List[str] = dataclasses.field(default_factory=list)
    window_compilations: int = 0
    compiles: Any = None            # CompileCount
    reduced: Any = None             # trace_reduce.Reduced of a traced run
    phases: List[str] = dataclasses.field(default_factory=list)

    def mark(self, label: str) -> None:
        """A set-up phase ends now: where set-up's seconds go."""
        now = time.perf_counter()
        last = getattr(self, "_marked", self.t_start)
        self.phases.append(f"{label} {now - last:.2f} s")
        self._marked = now


class CompileCount:
    """Programs jax was asked to compile, persistent-cache hits
    included: inside a window there should be none."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_listener(self._event)

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            self.n += 1


def accelerator(chips: int):
    """The devices the cell runs on; no TPU, or too few, is an error."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"benchmark: no TPU: jax found {len(devices)} "
                         f"{devices[0].platform} device(s)")
    if len(devices) < chips:
        raise SystemExit(f"benchmark: the cell needs {chips} chip(s), "
                         f"jax found {len(devices)}")
    return devices


def peaks_for(kind: str, data_dirs: List[str]) -> Dict[str, float]:
    table = load_json(find(data_dirs, "peaks.json"))
    if kind not in table:
        raise SystemExit(f"benchmark: device kind {kind!r} is not in "
                         f"peaks.json ({sorted(table)})")
    return table[kind]


def applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def per_layer_metrics(manifest, run: Run, data_dirs) -> Dict[str, Any]:
    out = {}
    for metric in manifest["per_layer"]:
        if not applies(metric, run.cell):
            continue
        spec = load_json(find(data_dirs, "layer_metrics",
                              metric["name"] + ".json"))
        reader = importlib.import_module(
            f"benchmark.readers.{spec['reader']}")
        value = reader.read(spec.get("params", {}), run)
        if value is not None:
            out[metric["name"]] = {"value": float(value),
                                   "unit": metric["unit"]}
    return out


def main(argv: Optional[List[str]] = None, *, manifest_path: str = None,
         data_dirs: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    data_dirs = list(data_dirs or [BENCH_DIR])
    manifest_path = os.path.abspath(
        manifest_path or os.path.join(ROOT, "BENCHMARK.json"))
    manifest = load_json(manifest_path)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if args.workload not in cells:
        raise SystemExit(f"benchmark: no workload {args.workload!r} in "
                         f"BENCHMARK.json ({sorted(cells)})")
    cell = cells[args.workload]
    config_file = next(c["file"] for c in manifest["configs"]
                       if c["name"] == cell["config"])
    config = load_json(os.path.dirname(manifest_path), config_file)
    traffic = load_json(find(data_dirs, "traffic",
                             cell["traffic"] + ".json"))
    seconds = (args.seconds if args.seconds is not None
               else manifest["run_seconds"])

    from apex_tpu import compile_cache

    cache_dir = compile_cache.enable()

    import jax

    # every program goes to the persistent cache, the small ones too:
    # a later run of the cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = accelerator(cell["chips"])
    kind = devices[0].device_kind
    peaks = peaks_for(kind, data_dirs)
    say(f"{args.workload}: seed {args.seed}, window {seconds} s, trace "
        f"{args.trace}; {len(devices)} x {kind}; compile cache {cache_dir}")

    run = Run(cell=args.workload, config=config, traffic=traffic,
              seed=args.seed, seconds=seconds, trace=bool(args.trace),
              devices=devices[:cell["chips"]], peaks=peaks,
              trace_dir=os.path.join(ROOT, ".bench_trace"),
              t_start=T_START if argv is None else time.perf_counter(),
              compiles=CompileCount())
    driver = importlib.import_module(
        f"benchmark.drivers.{traffic['driver']}")
    run.mark("imports, files, devices")
    driver.run(run)
    say("set-up by phase: " + ", ".join(run.phases))

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": max(
                  (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                  for d in run.devices)}
    result: Dict[str, Any] = {
        "correct": bool(run.correct and run.window_compilations == 0),
        "attempted": int(run.attempted), "failed": int(run.failed),
        "window_compilations": run.window_compilations}
    if run.trace:
        from benchmark import trace_reduce

        red = run.reduced = trace_reduce.reduce(
            trace_reduce.load(run.trace_dir))
        device["busy_s"] = red.busy_s()
        device["window_s"] = red.window_s
        result["metrics"] = per_layer_metrics(manifest, run, data_dirs)
        ops = sorted(red.op_seconds(trace_reduce.label).items(),
                     key=lambda kv: -kv[1])
        result["breakdown"] = {
            "device_ops": [[k, v] for k, v in ops[:10]],
            "idle_gaps": [[k, v] for k, v in red.idle_gaps()[:10]]}
    else:
        result["metrics"] = {
            m["name"]: {"value": float(run.end_to_end[m["name"]]),
                        "unit": m["unit"]}
            for m in manifest["end_to_end"]
            if applies(m, run.cell) and m["name"] in run.end_to_end}
    result["device"] = device
    for note in run.notes:
        say(note)
    say(f"set-up {run.setup_s:.2f} s, window {run.window_s:.3f} s, "
        f"{run.window_compilations} compilation(s) inside the window")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
