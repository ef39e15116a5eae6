"""Render the run-ledger attribution table (docs/observability.md
"Run ledger & goodput") from the live process, a checkpoint
directory, or any bundle JSON.

Every second of the run lands in a cause bucket — ``productive``,
``compile``, ``checkpoint_save`` / ``checkpoint_restore``,
``data_wait``, ``rollback``, ``rework``, ``drain_shutdown``,
``straggler_wait`` — with the residual published as ``unattributed``
rather than hidden.  This tool is the postmortem entry point: point it
at whatever the dead run left behind and it prints the table a human
reads first (docs/resilience.md "Postmortem runbook")::

    python tools/goodput_report.py                     # live ledger
    python tools/goodput_report.py ckpts/              # checkpoint dir alone
    python tools/goodput_report.py flightrec_*.json    # bundle / dump / record
    python tools/goodput_report.py --json ckpts/

A directory argument is resolved through
:class:`~apex_tpu.resilience.checkpoint.CheckpointManager` — the
newest checkpoint a resume would actually accept (``latest_valid``),
its manifest ``extra["goodput"]`` pack re-derived into the full table
(fraction, unattributed, effective tok/s are computed here; the pack
stores only raw buckets + wall).  File arguments are resolved by
shape, not name: a flight-recorder bundle (``payload.goodput``), a
telemetry dump (``goodput`` section), a serving drain snapshot, or a
bare pack/summary all work.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from apex_tpu.telemetry.goodput import CAUSES  # noqa: E402


def normalize(gp):
    """A checkpoint ``pack()`` (raw buckets + wall) or a live
    ``summary()`` -> one summary-shaped dict with the derived fields
    (attributed / unattributed / overlap / fraction / effective tok/s)
    always present, identity re-derived here so the table sums to wall
    no matter which producer wrote the blob."""
    if not isinstance(gp, dict) or "seconds" not in gp:
        raise ValueError("not a goodput pack/summary (no 'seconds' table)")
    seconds = {c: float(gp["seconds"].get(c, 0.0)) for c in CAUSES}
    wall = float(gp.get("wall_seconds", 0.0))
    attributed = sum(seconds.values())
    unattributed = max(0.0, wall - attributed)
    out = {
        "enabled": True,
        "wall_seconds": round(wall, 6),
        "attributed_seconds": round(attributed, 6),
        "unattributed_seconds": round(unattributed, 6),
        "overlap_seconds": round(max(0.0, attributed - wall), 6),
        "goodput_fraction": (round(seconds["productive"] / wall, 6)
                             if wall > 0 else 0.0),
        "seconds": {**{c: round(v, 6) for c, v in seconds.items()},
                    "unattributed": round(unattributed, 6)},
        "tokens_trained_total": int(gp.get("tokens_trained_total", 0)),
        "effective_tokens_per_sec": (
            round(float(gp.get("tokens_trained_total", 0)) / wall, 3)
            if wall > 0 else 0.0),
        "steps": int(gp.get("steps", 0)),
        "rework_steps": int(gp.get("rework_steps", 0)),
        "restarts": int(gp.get("restarts", 0)),
        "median_step_s": gp.get("median_step_s"),
    }
    for key in ("incarnation", "rollbacks", "step_high_water", "stages",
                "timeline_dropped_span_seconds"):
        if key in gp:
            out[key] = gp[key]
    # summary() carries the series summary under "anomalies"; pack()
    # persists only the episode counters.
    anomalies = gp.get("anomalies")
    episodes = (anomalies or {}).get("episodes") if isinstance(
        anomalies, dict) else None
    if episodes is None:
        episodes = gp.get("anomaly_episodes") or {}
    out["anomaly_episodes"] = dict(episodes)
    return out


def extract(obj):
    """The goodput blob inside any JSON shape this repo writes, or
    None.  Checked shapes: a bare pack/summary, a flight bundle
    (``payload.goodput``), a telemetry dump / snapshot_detail
    (``goodput``), a serving drain snapshot (``goodput`` pack alongside
    the request log)."""
    if not isinstance(obj, dict):
        return None
    if "seconds" in obj and "wall_seconds" in obj:
        return obj
    for path in (("goodput",),
                 ("payload", "goodput"),
                 ("telemetry", "goodput"),
                 ("payload", "telemetry", "goodput"),
                 ("extra", "goodput")):
        cur = obj
        for key in path:
            cur = cur.get(key) if isinstance(cur, dict) else None
        if isinstance(cur, dict) and "seconds" in cur:
            return cur
    return None


def from_checkpoint_dir(directory):
    """The goodput pack of the newest checkpoint a resume would accept
    in ``directory`` — the same ``latest_valid`` scan
    ``CheckpointManager.restore(None)`` runs, so the report and an
    actual resume always describe the same checkpoint.  Multi-host
    layouts read host 0's shard (each host packs its own ledger)."""
    from apex_tpu.resilience.checkpoint import CheckpointManager, MANIFEST
    mgr = CheckpointManager(directory)
    path = mgr.latest_valid(record_events=False)
    if path is None:
        raise SystemExit(f"no valid checkpoint under {directory!r}")
    leaf = path
    if not os.path.exists(os.path.join(leaf, MANIFEST)):
        hosts = sorted(n for n in os.listdir(path)
                       if os.path.exists(os.path.join(path, n, MANIFEST)))
        if not hosts:
            raise SystemExit(f"checkpoint {path!r} has no manifest")
        leaf = os.path.join(path, hosts[0])
    manifest = mgr.read_manifest(leaf)
    gp = (manifest.get("extra") or {}).get("goodput") \
        if isinstance(manifest.get("extra"), dict) else None
    if not isinstance(gp, dict):
        raise SystemExit(
            f"checkpoint {path!r} carries no goodput pack — was the run "
            "armed via apex_tpu.telemetry.goodput.enable()?")
    return gp, path


def _fmt_tokens(n):
    return f"{int(n):,}"


def render(summary):
    """The human attribution table for one normalized summary."""
    s = summary
    lines = ["== goodput report =="]
    frac = s.get("goodput_fraction") or 0.0
    lines.append(f"wall        {s['wall_seconds']:.3f} s")
    lines.append(f"goodput     {100.0 * frac:.1f} %  (productive / wall)")
    lines.append(
        f"tokens      {_fmt_tokens(s['tokens_trained_total'])} total"
        f" · {s['effective_tokens_per_sec']:,.1f} tok/s effective")
    med = s.get("median_step_s")
    med_txt = f" · median step {1e3 * med:.1f} ms" if med else ""
    lines.append(
        f"steps       {s['steps']} (rework {s['rework_steps']}){med_txt}")
    roll = f" · rollbacks {s['rollbacks']}" if "rollbacks" in s else ""
    lines.append(f"restarts    {s['restarts']}{roll}")
    episodes = {k: v for k, v in (s.get("anomaly_episodes") or {}).items()
                if v}
    if episodes:
        lines.append("anomalies   " + " ".join(
            f"{k}={v}" for k, v in sorted(episodes.items())))
    lines.append("")
    lines.append(f"{'cause':<20}{'seconds':>12}{'%':>8}")
    wall = s["wall_seconds"]
    for cause in (*CAUSES, "unattributed"):
        sec = s["seconds"].get(cause, 0.0)
        pct = 100.0 * sec / wall if wall > 0 else 0.0
        lines.append(f"{cause:<20}{sec:>12.3f}{pct:>8.1f}")
    if s.get("overlap_seconds"):
        lines.append(
            f"(overlap {s['overlap_seconds']:.3f} s — async work counted "
            "in its bucket while steps ran)")
    if s.get("stages"):
        lines.append("")
        lines.append("pipeline stages (diagnostic, outside the identity):")
        for k, v in sorted(s["stages"].items()):
            lines.append(f"  {k:<18}{v:>12.3f}")
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Render the run-ledger goodput attribution table.")
    ap.add_argument("source", nargs="?", default=None,
                    help="checkpoint directory or bundle/dump JSON file; "
                         "omit for the live in-process ledger")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit the normalized summary as JSON")
    args = ap.parse_args(argv)

    origin = "live"
    if args.source is None:
        from apex_tpu.telemetry import goodput
        sec = goodput.section()
        if not sec.get("enabled"):
            if args.as_json:
                print(json.dumps(sec, indent=2, sort_keys=True))
            else:
                print(f"goodput: disarmed — {sec.get('goodput_reason')}")
            return 0
        gp = sec
    elif os.path.isdir(args.source):
        gp, origin = from_checkpoint_dir(args.source)
    else:
        with open(args.source) as f:
            obj = json.load(f)
        gp = extract(obj)
        origin = args.source
        if gp is None:
            raise SystemExit(
                f"{args.source!r} holds no goodput section in any known "
                "shape (bundle / dump / snapshot / pack)")

    summary = normalize(gp)
    summary["source"] = origin
    if args.as_json:
        print(json.dumps(summary, indent=2, sort_keys=True))
    else:
        print(render(summary))
        if origin != "live":
            print(f"\nsource: {origin}")
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BrokenPipeError:
        # a downstream `grep -q`/`head` closing the pipe early is a
        # normal way to consume this report, not an error — reopen
        # stdout on devnull so the interpreter's exit flush is quiet
        os.dup2(os.open(os.devnull, os.O_WRONLY), sys.stdout.fileno())
        sys.exit(0)
