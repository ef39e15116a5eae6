"""The controls of ``ai21-jamba2-3b.serve-reasoning``'s ``correct``, at
the cell's size and on the chip: ``controls_granite.py``'s procedure
over ``reference_jamba``.

    python3 benchmark/controls_jamba.py --seed N [--seconds S]

runs the cell once through ``run.py`` (a run like any other: its result
line is printed), keeps what the driver handed to
``reference_jamba.check_served`` (the weights, the two checked
requests, the program's own logits of the served rows, the cell's
limits), and judges the same served tokens and the same logits again
against references that are wrong in one way each: everything rounded
through fp8 (e4m3, the nearest type below the bf16 the configuration
states), and each of ``reference_jamba.FAULTS`` (the state carried in
bfloat16, no inner norms, one decay a channel, no ``dt`` bias, no
convolution bias, no ``D x``, rotary on the attention layers). A
control is *refused* when the run would not have been ``correct``
under it. The exit code is 0 only if the run itself was ``correct``
and every control the configuration lists under ``reference_controls``
was refused; the others are printed with their readings.

``--controls a,b`` runs those controls only. ``--dump DIR`` also writes
each judgement's per-row readings (``<DIR>/controls_<seed>.npz``).
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

CELL = "ai21-jamba2-3b.serve-reasoning"


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

    from benchmark import reference_jamba, run

    calls = []
    check = reference_jamba.check_served

    def keeping(*a, **kw):
        out = check(*a, **kw)
        calls.append((a, kw, out))
        return out

    reference_jamba.check_served = keeping
    try:
        argv_run = ["--workload", args.workload, "--seed", str(args.seed),
                    "--trace", "0"]
        if args.seconds is not None:
            argv_run += ["--seconds", str(args.seconds)]
        run.main(argv_run, **where)
    finally:
        reference_jamba.check_served = check
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

    dump = {}
    table = {"the run itself": [out for _, _, out in calls]}
    controls = {"fp8_everywhere": dict(round_to=jnp.float8_e4m3fn)}
    controls.update({f: dict(faults=(f,)) for f in reference_jamba.FAULTS})
    if args.controls:
        controls = {name: controls[name] for name in args.controls.split(",")}
    for name, wrong in controls.items():
        table[name] = [check(*a, **kw, **wrong) for a, kw, _ in calls]
    failed = []
    for name, outs in table.items():
        passes = all(o["ok"] for o in outs)
        print(f"# control {name}: "
              f"{'correct' if passes else 'refused'}; a request: "
              + "; ".join(
                  f"{o['rows']} rows, worst {o['worst_ulps']:.2f} ulp(s), "
                  f"{o['exact']} exact, logits off by {o['logit_error']:.4g} "
                  f"(rms {o['logit_rms']:.4g})" for o in outs),
              flush=True)
        if passes != (name == "the run itself") and (
                name == "the run itself" or name in must):
            failed.append(name)             # a control not run fails nothing
        for i, o in enumerate(outs):
            key = f"{name.replace(' ', '_')}.{i}"
            dump[key + ".ulps"] = o["ulps_by_row"]
            dump[key + ".logit_error"] = o.get("logit_error_by_row",
                                               np.zeros(0))
    if args.dump:
        os.makedirs(args.dump, exist_ok=True)
        np.savez_compressed(
            os.path.join(args.dump, f"controls_{args.seed}.npz"), **dump)
    print(json.dumps({"controls_ok": not failed, "failed": failed,
                      "must_refuse": must}), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
