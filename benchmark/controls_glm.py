"""The controls of ``glm-4.7-flash.serve-agent``'s ``correct``, at the
cell's size and on the chip: ``controls_granite.py``'s procedure over
``reference_glm``.

    python3 benchmark/controls_glm.py --seed N [--seconds S]

runs the cell once through ``run.py`` (a run like any other: its result
line is printed), keeps what ``serve_glm`` handed to
``reference_glm.check_served`` (the weights, the two checked
requests, the program's own choice of experts, the program's own
logits of the served rows, the cell's limits), and judges the same
served tokens and the same logits again against references that are
wrong in one way each: everything rounded through fp8 (e4m3, the
nearest type below the bf16 the configuration states), and each of
``reference_glm.FAULTS`` (``c_kv`` without its norm, rotary over the
rotate-half layout, scores scaled by ``192^-0.5``, the routing weights
without ``routed_scaling_factor``, the latents carried in fp8). A
control is *refused* when the run would not have been ``correct`` under
it. "The run itself" is correct only where the run's own result line
reads ``correct`` (which also holds it to every request it followed
being checked, no failed request and no compilation in the window) and
the checks it made pass. The exit code is 0 only if the run itself was
correct and every control the configuration lists under
``reference_controls`` was refused; the others are printed with their
readings.

``--controls a,b`` runs those controls only. ``--dump DIR`` also writes
each judgement's per-row readings (``<DIR>/controls_<seed>.npz``),
which is where the limits' readings in the configuration file's
``reference_tolerance_why`` come from.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CELL = "glm-4.7-flash.serve-agent"


def main(argv=None, **where) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", default=CELL)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--controls", default=None,
                    help="comma-separated; default: every control")
    ap.add_argument("--dump", default=None)
    args = ap.parse_args(argv)

    import numpy as np

    from benchmark import reference_glm, run

    calls, runs = [], []
    check, made = reference_glm.check_served, run.Run

    def keeping(*a, **kw):
        out = check(*a, **kw)
        calls.append((a, kw, out))
        return out

    def kept_run(*a, **kw):
        runs.append(made(*a, **kw))
        return runs[-1]

    reference_glm.check_served, run.Run = keeping, kept_run
    try:
        argv_run = ["--workload", args.workload, "--seed", str(args.seed),
                    "--trace", "0"]
        if args.seconds is not None:
            argv_run += ["--seconds", str(args.seconds)]
        run.main(argv_run, **where)
    finally:
        reference_glm.check_served, run.Run = check, made
    # what the result line reads as ``correct``
    correct = bool(runs[0].correct and runs[0].window_compilations == 0)
    if not calls:
        print("controls: the run checked no request", flush=True)
        return 1

    import jax.numpy as jnp

    manifest_path = where.get("manifest_path") or os.path.join(
        ROOT, "BENCHMARK.json")
    manifest = run.load_json(manifest_path)
    cell = next(w for w in manifest["workloads"]
                if w["name"] == args.workload)
    config = run.load_json(
        os.path.dirname(manifest_path),
        next(c["file"] for c in manifest["configs"]
             if c["name"] == cell["config"]))
    must = config["reference_controls"]
    share_max = config["reference_excused_share_max"]

    def verdict(outs):
        rows = sum(o["rows"] for o in outs)
        return (all(o["ok"] for o in outs)
                and sum(o["excused"] for o in outs) <= share_max * rows)

    dump = {}
    table = {"the run itself": [out for _, _, out in calls]}
    controls = {"fp8_everywhere": dict(round_to=jnp.float8_e4m3fn)}
    controls.update({f: dict(faults=(f,)) for f in reference_glm.FAULTS})
    if args.controls:
        controls = {name: controls[name] for name in args.controls.split(",")}
    for name, wrong in controls.items():
        table[name] = [check(*a, **kw, **wrong) for a, kw, _ in calls]
    failed = []
    for name, outs in table.items():
        passes = verdict(outs) and (name != "the run itself" or correct)
        print(f"# control {name}: "
              f"{'correct' if passes else 'refused'}; a request: "
              + "; ".join(
                  f"worst held row {o['worst_ulps']:.2f} ulp(s), "
                  f"{o['excused']} of {o['rows']} excused (worst "
                  f"{o['worst_excused_ulps']:.2f}; {o['may_differ']} might "
                  f"be), {o['followed']} followed, {o['refused']} refused, "
                  f"worst misfit {o['worst_misfit']:.5f}, logits off by "
                  f"{o['logit_error']:.3g} (rms {o['logit_rms']:.3g})"
                  for o in outs)
              + ("" if name != "the run itself"
                 else f"; the result line's correct: {correct}"),
              flush=True)
        if passes != (name == "the run itself") and (
                name == "the run itself" or name in must):
            failed.append(name)             # a control not run fails nothing
        for i, o in enumerate(outs):
            key = f"{name.replace(' ', '_')}.{i}"
            dump[key + ".ulps"] = o["ulps_by_row"]
            dump[key + ".margin"] = o["program_margin"]
            dump[key + ".logit_error"] = o.get("logit_error_by_row",
                                               np.zeros(0))
            dump[key + ".misfit"] = np.stack(o["misfit_by_layer"]) \
                if o["misfit_by_layer"] else np.zeros((0, 0))
    if args.dump:
        os.makedirs(args.dump, exist_ok=True)
        np.savez_compressed(
            os.path.join(args.dump, f"controls_{args.seed}.npz"), **dump)
    print(json.dumps({"controls_ok": not failed, "failed": failed,
                      "must_refuse": must}), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
