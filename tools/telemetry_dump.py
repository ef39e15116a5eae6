"""Print a telemetry snapshot — Prometheus text or JSON — from the
live process registry or a flight-recorder bundle.

The scrape-shaped view of the observability layer
(docs/observability.md): the same ``to_prometheus_text()`` rendering a
node-exporter-style endpoint would serve, runnable against the black
box a dead run left behind::

    python tools/telemetry_dump.py                      # live registry
    python tools/telemetry_dump.py --format json
    python tools/telemetry_dump.py bench_records/flightrec_*.json
    python tools/telemetry_dump.py --format json some_snapshot.json

File arguments are resolved by shape, not by name: a flight-recorder
bundle (``payload.telemetry.registry``), a ``snapshot_detail()`` dump
(``registry``) or a bare registry snapshot all work.

Both formats carry the COMPILE and DEVMEM planes
(docs/observability.md "compile & memory plane"): JSON output appends
``compile`` / ``devmem`` sections (the plane's series pulled out of
the snapshot, with the explicit ``devmem_reason`` when the backend has
no stats); Prometheus output renders every ``compile_*`` /
``recompile*`` / ``devmem_*`` series through the standard exposition
and appends one summary comment line per plane.

The SERVING plane rides the same way (docs/observability.md "Request
plane"): JSON output appends a ``serving`` section — every
``serving_*`` / ``slo_*`` series by kind, the computed prefix-cache
hit rate, and the SLO window summary the monitor mirrored into
``info["slo_window"]`` — and Prometheus output adds one serving
summary comment line (requests by outcome, tokens, queue depth, hit
rate, SLO alerts).

So does the COMMS plane (docs/observability.md "Comms & sharding
plane"): JSON output appends a ``comms`` section — every
``collective_*`` series plus the ``fleet_clock_offset*`` gauges, with
the per-op payload bandwidth recomputed from the bytes/ms histogram
sums (the measured column of the ledger) — and Prometheus output adds
one comms summary comment line (op count, slow events, per-op
bandwidth, clock spread). A snapshot whose comms plane never armed
reports the explicit ``comms_reason`` instead.

And the MESH plane (docs/mesh.md): JSON output appends a ``mesh``
section — the ``sharding_devices{fn=}`` / ``sharding_bytes_per_device``
gauges the GSPMD train step and mesh-armed serving decode publish,
the ``layout_plan_*`` gauges, and the planner's full ranked
``layout_plan`` info blob — and Prometheus output adds one mesh
summary comment line (chosen layout + publishing fns). A snapshot
with neither published layouts nor a plan reports ``mesh_reason``.

And the PIPELINE plane (docs/mesh.md "Pipeline schedules on the pipe
axis"): JSON output appends a ``pipeline`` section — the per-stage
``pipeline_bubble_fraction{schedule=,stage=}`` / ``pipeline_ticks``
gauges and the ``pipeline`` info blob the mesh pipeline train step
publishes (schedule, microbatches, per-stage activity windows, step
wall time) — and Prometheus output adds one pipeline summary comment
line. A snapshot where no schedule ran reports ``pipeline_reason``.

And the MOE plane (docs/moe.md): JSON output appends a ``moe``
section — every ``moe_*`` series plus the per-expert load histogram
folded out of the ``moe_expert_load{expert=}`` gauges — and
Prometheus output adds one MoE summary comment line (aux loss,
dropped tokens, imbalance EWMA, hottest expert). A snapshot from a
dense run reports ``moe_reason``.

And the GOODPUT plane (docs/observability.md "Run ledger & goodput"):
JSON output appends a ``goodput`` section — the
``goodput_seconds{cause=}`` attribution gauges, the fraction /
token-rate / ``mfu_ewma`` gauges, and the full ``info["goodput"]``
summary blob the ledger publishes (buckets, unattributed residual,
rework, restarts, anomaly episodes) — and Prometheus output adds one
goodput summary comment line. A snapshot whose ledger never armed
reports the explicit ``goodput_reason``; see
``tools/goodput_report.py`` for the human attribution table.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def extract_registry_snapshot(obj):
    """The registry snapshot inside any of the JSON shapes this repo
    writes (flight bundle, snapshot_detail dump, bare snapshot);
    None when the object holds no registry."""
    if not isinstance(obj, dict):
        return None
    # bare snapshot: has the three section keys
    if {"counters", "gauges", "histograms"} <= set(obj):
        return obj
    for path in (("payload", "telemetry", "registry"),
                 ("telemetry", "registry"),
                 ("registry",)):
        node = obj
        for key in path:
            node = node.get(key) if isinstance(node, dict) else None
            if node is None:
                break
        if isinstance(node, dict) and {"counters", "gauges",
                                       "histograms"} <= set(node):
            return node
    return None


_COMPILE_PREFIXES = ("compile_", "compiled_signatures", "recompile")
_DEVMEM_PREFIX = "devmem_"


def _series_base(series: str) -> str:
    return series.split("{", 1)[0]


def _plane(snap, match):
    out = {}
    for kind in ("counters", "gauges", "histograms"):
        sel = {k: v for k, v in (snap.get(kind) or {}).items()
               if match(_series_base(k))}
        if sel:
            out[kind] = sel
    return out


def compile_section(snap):
    """The compile plane of a registry snapshot: every ``compile_*`` /
    ``compiled_signatures`` / ``recompile*`` series, by kind."""
    return _plane(snap, lambda base: base.startswith(_COMPILE_PREFIXES))


def devmem_section(snap):
    """The memory plane of a registry snapshot: every ``devmem_*``
    series — or, when no poll ever landed a gauge, the explicit
    ``devmem_reason`` (the mfu_reason contract: null sections always
    say why)."""
    out = _plane(snap, lambda base: base.startswith(_DEVMEM_PREFIX))
    if not out.get("gauges"):
        out["devmem_reason"] = ((snap.get("info") or {}).get(
            "devmem_reason") or "no device-memory poll in this snapshot")
    return out


_SERVING_PREFIXES = ("serving_", "slo_")


def _counter_total(snap, base):
    return sum(v for k, v in (snap.get("counters") or {}).items()
               if _series_base(k) == base)


def _counter_label(snap, base, **labels):
    # snapshot series names carry sorted labels (metrics._series_name)
    inner = ",".join(f'{k}="{v}"' for k, v in sorted(labels.items()))
    return (snap.get("counters") or {}).get(f"{base}{{{inner}}}", 0.0)


def serving_section(snap):
    """The serving plane of a registry snapshot: every ``serving_*``
    and ``slo_*`` series by kind, plus the computed prefix-cache hit
    rate and the SLO window summary the monitor mirrors into
    ``info["slo_window"]`` (absent = no monitor armed, reported
    explicitly — the null-with-reason contract)."""
    out = _plane(snap, lambda base: base.startswith(_SERVING_PREFIXES))
    hits = _counter_label(snap, "serving_prefix_cache_hits",
                          outcome="hit")
    misses = _counter_label(snap, "serving_prefix_cache_hits",
                            outcome="miss")
    out["prefix_cache_hit_rate"] = (
        round(hits / (hits + misses), 4) if hits + misses else None)
    slo = (snap.get("info") or {}).get("slo_window")
    if slo is not None:
        out["slo_window"] = slo
    else:
        out["slo_reason"] = "no SLO monitor armed in this snapshot"
    return out


_COMMS_PREFIXES = ("collective_", "fleet_clock_offset")


def _series_labels(series: str):
    """The label dict out of a snapshot series name
    (``base{k="v",...}`` — metrics._series_name sorts and quotes)."""
    if "{" not in series:
        return {}
    inner = series.split("{", 1)[1].rstrip("}")
    out = {}
    for part in inner.split(","):
        if "=" in part:
            k, v = part.split("=", 1)
            out[k] = v.strip('"')
    return out


def comms_section(snap):
    """The comms plane of a registry snapshot: every ``collective_*``
    series plus the ``fleet_clock_offset*`` gauges, with the per-op
    payload bandwidth recomputed from the bytes/ms histogram sums —
    the measured column of the tracer's ledger, recoverable from any
    scrape. A snapshot whose comms plane never armed gets the explicit
    ``comms_reason`` (the null-with-reason contract)."""
    out = _plane(snap, lambda base: base.startswith(_COMMS_PREFIXES))
    hists = snap.get("histograms") or {}
    bw = {}
    for series, h in hists.items():
        if _series_base(series) != "collective_bytes":
            continue
        op = _series_labels(series).get("op")
        if not op:
            continue
        ms = (hists.get(f'collective_ms{{op="{op}"}}') or {}).get(
            "sum", 0.0)
        payload = (h or {}).get("sum", 0.0)
        bw[op] = (round(payload / (ms / 1e3) / 1e6, 4)
                  if ms and payload else None)
    if any(out.get(k) for k in ("counters", "gauges", "histograms")):
        out["collective_bandwidth_mbps"] = bw or None
    else:
        out["comms_reason"] = (
            "no collective tracing in this snapshot "
            "(telemetry.comms.enable() / APEX_TPU_COMMS=1)")
    return out


_MESH_PREFIXES = ("sharding_", "layout_plan")


def mesh_section(snap):
    """The mesh/sharding plane of a registry snapshot (docs/mesh.md):
    the ``sharding_devices{fn=}`` / ``sharding_bytes_per_device``
    gauges next to the ``layout_plan_*`` gauges and the planner's
    ranked ``layout_plan`` info blob — what the compiler DID beside
    what the planner ASKED for. Null-with-``mesh_reason`` when the
    snapshot holds neither."""
    out = _plane(snap, lambda base: base.startswith(_MESH_PREFIXES))
    plan = (snap.get("info") or {}).get("layout_plan")
    if plan is not None:
        out["layout_plan"] = plan
    if not out.get("gauges") and plan is None:
        out["mesh_reason"] = (
            "no sharding layouts or layout plan published in this "
            "snapshot (mesh.publish_plan / publish_shardings)")
    return out


_PIPELINE_PREFIX = "pipeline_"


def pipeline_section(snap):
    """The pipeline plane of a registry snapshot (docs/mesh.md
    "Pipeline schedules on the pipe axis"): the per-stage
    ``pipeline_bubble_fraction{schedule=,stage=}`` / ``pipeline_ticks``
    gauges next to the ``pipeline`` info blob (the PipelineSpec plus
    the last step's wall time and per-stage activity windows) the mesh
    pipeline train step publishes each step.
    Null-with-``pipeline_reason`` when no schedule ran."""
    out = _plane(snap, lambda base: base.startswith(_PIPELINE_PREFIX))
    blob = (snap.get("info") or {}).get("pipeline")
    if blob is not None:
        out["pipeline"] = blob
    if not out.get("gauges") and blob is None:
        out["pipeline_reason"] = (
            "no pipeline schedule ran in this snapshot "
            "(mesh.make_mesh_pipeline_train_step)")
    return out


_MOE_PREFIX = "moe_"


def moe_section(snap):
    """The MoE workload plane of a registry snapshot (docs/moe.md):
    every ``moe_*`` series — the ``moe_aux_loss`` /
    ``moe_dropped_tokens`` / ``moe_imbalance_ratio`` gauges and the
    drop counter — plus ``expert_load``, the per-expert histogram
    folded out of the ``moe_expert_load{expert=}`` gauges.
    Null-with-``moe_reason`` when the snapshot is from a dense run
    (the mfu_reason contract)."""
    out = _plane(snap, lambda base: base.startswith(_MOE_PREFIX))
    load = {}
    for series, v in (out.get("gauges") or {}).items():
        if _series_base(series) == "moe_expert_load":
            expert = _series_labels(series).get("expert")
            if expert is not None:
                load[expert] = v
    if load:
        out["expert_load"] = {e: load[e]
                              for e in sorted(load, key=int)}
    if not any(out.get(k) for k in ("counters", "gauges", "histograms")):
        out["moe_reason"] = (
            "no MoE gauges in this snapshot (dense run, or "
            "telemetry.moe.publish_moe_step never called)")
    return out


_GOODPUT_PREFIXES = ("goodput_", "tokens_trained", "effective_tokens",
                     "mfu_ewma")


def goodput_section(snap):
    """The run-ledger plane of a registry snapshot
    (docs/observability.md "Run ledger & goodput"): the
    ``goodput_seconds{cause=}`` attribution gauges next to the
    ``goodput_fraction`` / ``tokens_trained_total`` /
    ``effective_tokens_per_sec`` / ``mfu_ewma`` gauges, plus the full
    ``info["goodput"]`` summary blob the ledger publishes (buckets,
    unattributed residual, rework, restarts, anomaly episodes).
    Null-with-``goodput_reason`` when the ledger never armed in the
    process that wrote the snapshot."""
    out = _plane(snap, lambda base: base.startswith(_GOODPUT_PREFIXES))
    blob = (snap.get("info") or {}).get("goodput")
    if blob is not None:
        out["goodput"] = blob
    if not out.get("gauges") and blob is None:
        out["goodput_reason"] = (
            "goodput ledger not armed in this snapshot "
            "(telemetry.goodput.enable)")
    return out


def plane_comments(snap) -> str:
    """One summary comment line per plane, appended to the Prometheus
    text (comments are legal exposition; the series themselves render
    through the standard format above them)."""
    comp = compile_section(snap)
    counters = comp.get("counters", {})

    def _total(prefix):
        return sum(v for k, v in counters.items()
                   if _series_base(k) == prefix)

    lines = [f"# compile plane: {int(_total('compile_count'))} "
             f"compiles, {int(_total('recompile_count'))} recompiles, "
             f"{int(_total('recompile_storms'))} storms"]
    dm = devmem_section(snap)
    gauges = dm.get("gauges", {})
    if gauges:
        in_use = gauges.get("devmem_bytes_in_use")
        mark = gauges.get("devmem_watermark_bytes")
        lines.append(f"# devmem: bytes_in_use={in_use} "
                     f"watermark={mark}")
    else:
        lines.append(f"# devmem: unavailable ({dm['devmem_reason']})")
    sv = serving_section(snap)
    if sv.get("counters") or sv.get("gauges") or sv.get("histograms"):
        n_req = int(_counter_total(snap, "serving_requests"))
        n_tok = int(_counter_total(snap, "serving_tokens"))
        depth = (sv.get("gauges") or {}).get("serving_queue_depth")
        rate = sv.get("prefix_cache_hit_rate")
        slo = sv.get("slo_window")
        alerts = (slo or {}).get("alerts_total")
        alerting = ",".join((slo or {}).get("alerting") or []) or "none"
        lines.append(
            f"# serving: {n_req} requests, {n_tok} tokens, "
            f"queue_depth={depth} prefix_hit_rate={rate} "
            + (f"slo_alerts={alerts} alerting={alerting}"
               if slo is not None else f"slo={sv.get('slo_reason')}"))
    cm = comms_section(snap)
    if "comms_reason" in cm:
        lines.append(f"# comms: unavailable ({cm['comms_reason']})")
    else:
        n_ops = int(_counter_total(snap, "collective_ops"))
        slow = int(_counter_total(snap, "collective_slow_total"))
        bw = cm.get("collective_bandwidth_mbps") or {}
        bw_s = " ".join(f"{op}={v}MB/s"
                        for op, v in sorted(bw.items())
                        if v is not None) or "n/a"
        spread = (cm.get("gauges") or {}).get(
            "fleet_clock_offset_spread_ms")
        lines.append(f"# comms: {n_ops} collective ops, "
                     f"slow_events={slow} bandwidth[{bw_s}] "
                     f"clock_spread_ms={spread}")
    ms = mesh_section(snap)
    if "mesh_reason" in ms:
        lines.append(f"# mesh: unavailable ({ms['mesh_reason']})")
    else:
        best = (ms.get("layout_plan") or {}).get("best")
        fns = sorted({_series_labels(k).get("fn")
                      for k in (ms.get("gauges") or {})
                      if _series_base(k) == "sharding_devices"}
                     - {None})
        lines.append(f"# mesh: plan={best} "
                     f"sharding_fns=[{','.join(fns)}]")
    pl = pipeline_section(snap)
    if "pipeline_reason" in pl:
        lines.append(f"# pipeline: none ({pl['pipeline_reason']})")
    else:
        blob = pl.get("pipeline") or {}
        bub = {_series_labels(k).get("stage"): v
               for k, v in (pl.get("gauges") or {}).items()
               if _series_base(k) == "pipeline_bubble_fraction"}
        bub_s = " ".join(f"s{s}={bub[s]}" for s in sorted(bub)) or "n/a"
        lines.append(
            f"# pipeline: schedule={blob.get('schedule')} "
            f"stages={blob.get('num_stages')} "
            f"microbatches={blob.get('num_microbatches')} "
            f"step_ms={blob.get('step_ms')} bubble[{bub_s}]")
    mo = moe_section(snap)
    if "moe_reason" in mo:
        lines.append(f"# moe: none ({mo['moe_reason']})")
    else:
        g = mo.get("gauges") or {}
        load = mo.get("expert_load") or {}
        hot = (max(load, key=load.get) if load else None)
        lines.append(
            f"# moe: aux_loss={g.get('moe_aux_loss')} "
            f"dropped={g.get('moe_dropped_tokens')} "
            f"imbalance_ewma={g.get('moe_imbalance_ratio')} "
            f"hot_expert={hot} experts={len(load)}")
    gp = goodput_section(snap)
    if "goodput_reason" in gp:
        lines.append(f"# goodput: none ({gp['goodput_reason']})")
    else:
        blob = gp.get("goodput") or {}
        gauges = gp.get("gauges") or {}
        secs = blob.get("seconds") or {}
        frac = blob.get("goodput_fraction",
                        gauges.get("goodput_fraction"))
        lines.append(
            f"# goodput: fraction={frac} "
            f"productive={secs.get('productive')}s "
            f"unattributed={blob.get('unattributed_seconds')}s "
            f"restarts={blob.get('restarts')} "
            f"rework_steps={blob.get('rework_steps')} "
            f"eff_tok_per_s={blob.get('effective_tokens_per_sec')}")
    return "\n".join(lines) + "\n"


def _emit(snap, fmt, help_source=None) -> None:
    from apex_tpu.telemetry import metrics

    if fmt == "json":
        out = dict(snap)
        out["compile"] = compile_section(snap)
        out["devmem"] = devmem_section(snap)
        out["serving"] = serving_section(snap)
        out["comms"] = comms_section(snap)
        out["mesh"] = mesh_section(snap)
        out["pipeline"] = pipeline_section(snap)
        out["moe"] = moe_section(snap)
        out["goodput"] = goodput_section(snap)
        print(json.dumps(out, indent=1, sort_keys=True))
        return
    if help_source is not None:
        text = help_source.to_prometheus_text()
    else:
        text = metrics.prometheus_text_from_snapshot(snap)
    sys.stdout.write(text + plane_comments(snap))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="print a telemetry snapshot (live registry, "
                    "or flight-recorder bundle)")
    parser.add_argument("path", nargs="?", default=None,
                        help="JSON file holding a registry snapshot "
                             "(flightrec bundle / snapshot); "
                             "default: the live process registry")
    parser.add_argument("--format", choices=("prom", "json"),
                        default="prom",
                        help="prom = Prometheus text exposition "
                             "(default), json = the snapshot dict")
    args = parser.parse_args(argv)

    from apex_tpu.telemetry import metrics

    if args.path is None:
        # live path: the registry renders with its HELP text
        _emit(metrics.registry().snapshot(), args.format,
              help_source=metrics.registry())
        return 0

    try:
        with open(args.path) as f:
            obj = json.load(f)
    except (OSError, ValueError) as e:
        print(f"error: cannot read {args.path}: {e}", file=sys.stderr)
        return 2
    snap = extract_registry_snapshot(obj)
    if snap is None:
        print(f"error: no telemetry registry snapshot found in "
              f"{args.path}", file=sys.stderr)
        return 2
    _emit(snap, args.format)
    return 0


if __name__ == "__main__":
    sys.exit(main())
