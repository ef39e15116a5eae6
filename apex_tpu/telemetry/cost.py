"""Static step-cost estimation: FLOPs / bytes-moved / MFU from XLA.

``jax.jit(...).lower().compile().cost_analysis()`` is XLA's own static
accounting of a compiled program — model FLOPs and HBM bytes accessed
— available before (and independent of) any timed run. Pairing it with
a measured step time gives:

- **MFU** (model FLOPs utilization) against the chip's published peak
  (:func:`chip_peak_tflops`), the TorchTitan-style headline
  efficiency number;
- **achieved HBM bandwidth** for the memory-bound phases (the fused
  optimizer step's real ceiling — see docs/train_step.md's
  accesses-per-element budget).

Every helper degrades to ``None`` **with a reason string** instead of
raising: some backends expose no cost model, some device kinds have no
peak-TFLOPs entry, and a bench record must say *why* its ``mfu`` is
null rather than silently dropping the field (BENCH_r0x fallback-saga
rule: records never contradict themselves).

The program's memory FOOTPRINT (``memory_analysis()``) lives next
door in :mod:`~apex_tpu.telemetry.devmem`; :func:`bytes_per_element`
below is the measured side of the bench's measured-vs-analytic HBM
ledger (docs/observability.md "compile & memory plane").
"""

from __future__ import annotations

from typing import Any, Dict, Optional


def normalize_cost_analysis(ca: Any) -> Optional[Dict[str, float]]:
    """``cost_analysis()`` returns a dict on new jax, a one-element
    list of dicts on older releases, or None/raises when the backend
    has no cost model — normalize all of that to one dict or None."""
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None
    return ca


def compiled_cost(compiled) -> Optional[Dict[str, float]]:
    """``{"flops": ..., "bytes_accessed": ...}`` of a compiled
    computation (``jax.jit(f).lower(...).compile()``), or None when
    the backend exposes no cost model."""
    try:
        ca = normalize_cost_analysis(compiled.cost_analysis())
    except Exception:  # noqa: BLE001 — "no cost model" raises on some backends
        return None
    if ca is None:
        return None
    flops = ca.get("flops")
    nbytes = ca.get("bytes accessed")
    if flops is None and nbytes is None:
        return None
    return {
        "flops": float(flops) if flops is not None else None,
        "bytes_accessed": float(nbytes) if nbytes is not None else None,
    }


def jitted_cost(fn, *args, **kwargs) -> Optional[Dict[str, float]]:
    """Lower+compile ``fn`` (a ``jax.jit`` result) on the given
    arguments and return its static cost; None on any failure — cost
    accounting must never take down the loop it describes."""
    from apex_tpu.telemetry import compiled as _compiled

    try:
        with _compiled.label("jitted_cost"):
            return compiled_cost(fn.lower(*args, **kwargs).compile())
    except Exception:  # noqa: BLE001
        return None


def train_step_cost(step, state, flat_grads,
                    scaler_state=None, lr=None) -> Optional[Dict[str, float]]:
    """Static cost of one fused train step
    (:class:`~apex_tpu.optimizers.train_step.TrainStep`). Uses the
    step's ``lower`` passthrough, so nothing executes and no buffer is
    donated — safe to call right before the timed run."""
    from apex_tpu.telemetry import compiled as _compiled

    try:
        with _compiled.label("train_step_cost"):
            return compiled_cost(
                step.lower(state, flat_grads, scaler_state,
                           lr=lr).compile())
    except Exception:  # noqa: BLE001
        return None


def bytes_per_element(cost: Optional[Dict[str, float]],
                      n_elements: int) -> Optional[float]:
    """Measured HBM bytes per model element from a cost dict — the
    MEASURED side of the bench's measured-vs-analytic HBM ledger (the
    analytic side is ``hbm_accesses_per_element``, the fp32
    accesses/element design numbers of docs/train_step.md). None when
    the backend has no cost model or the element count is unusable —
    the record then says null instead of a made-up number."""
    if not cost or not cost.get("bytes_accessed") or not n_elements:
        return None
    return round(float(cost["bytes_accessed"]) / float(n_elements), 3)


def device_kind() -> str:
    try:
        import jax

        return str(getattr(jax.devices()[0], "device_kind", "cpu"))
    except Exception:  # noqa: BLE001
        return "unknown"


def chip_peak_tflops(device_kind: str) -> float:
    """Peak dense bf16-matmul TFLOP/s per chip for MFU accounting, by
    the ``device_kind`` jax reports (a v5e says ``TPU v5 lite``).

    bf16 only — the dtype every bench mode computes in. An unknown
    kind raises: a measurement never gets a made-up denominator.
    """
    kind = device_kind.lower()
    table = [
        ("v6", 918.0),           # Trillium / v6e
        ("v5p", 459.0),
        ("v5", 197.0),           # v5 lite / v5e
        ("v4", 275.0),
        ("v3", 123.0),
        ("v2", 45.0),
    ]
    for pat, peak in table:
        if pat in kind:
            return peak
    raise ValueError(
        f"no peak-TFLOPs entry for device kind {device_kind!r} — mfu "
        "denominator unknown")


def mfu_estimate(cost: Optional[Dict[str, float]], seconds: float,
                 kind: Optional[str] = None) -> Dict[str, Any]:
    """MFU + bandwidth accounting for one timed step.

    Always returns the full key set — ``mfu`` is a value or None, and
    when None ``mfu_reason`` names exactly why (no cost model, unknown
    chip, bad timing) so downstream JSON consumers never guess.
    """
    kind = kind if kind is not None else device_kind()
    try:
        peak = chip_peak_tflops(kind)
        peak_reason = None
    except ValueError as e:
        peak, peak_reason = None, str(e)
    out: Dict[str, Any] = {
        "flops_per_step": None, "bytes_per_step": None,
        "tflops_per_sec": None, "hbm_gb_per_sec": None,
        "chip": kind, "chip_peak_tflops": peak,
        "mfu": None, "mfu_reason": None,
    }
    if cost is None:
        out["mfu_reason"] = ("backend exposes no XLA cost model "
                             "(cost_analysis unavailable)")
        return out
    out["flops_per_step"] = cost.get("flops")
    out["bytes_per_step"] = cost.get("bytes_accessed")
    if not seconds or seconds <= 0.0:
        out["mfu_reason"] = f"non-positive step time ({seconds})"
        return out
    if out["bytes_per_step"] is not None:
        out["hbm_gb_per_sec"] = round(out["bytes_per_step"] / seconds / 1e9,
                                      2)
    if out["flops_per_step"] is None:
        out["mfu_reason"] = "cost model reports no flops for this program"
        return out
    tflops = out["flops_per_step"] / seconds / 1e12
    out["tflops_per_sec"] = round(tflops, 4)
    if peak is None:
        out["mfu_reason"] = peak_reason
        return out
    out["mfu"] = round(tflops / peak, 6)
    return out


def publish_mfu(est: Dict[str, Any], registry=None) -> None:
    """Mirror an :func:`mfu_estimate` into the metrics registry: the
    ``mfu`` gauge when known, the reason as an info blob when not, plus
    the flops/bytes gauges — so ``snapshot()`` (and through it every
    bench record) carries the numbers."""
    from apex_tpu.telemetry import metrics as _metrics

    reg = registry if registry is not None else _metrics.registry()
    if est.get("mfu") is not None:
        reg.gauge("mfu", "model FLOPs utilization of the timed step").set(
            est["mfu"])
    reg.set_info("mfu_reason", est.get("mfu_reason"))
    if est.get("flops_per_step") is not None:
        reg.gauge("step_flops", "static FLOPs of one compiled step").set(
            est["flops_per_step"])
    if est.get("bytes_per_step") is not None:
        reg.gauge("step_bytes_accessed",
                  "static HBM bytes accessed by one compiled step").set(
            est["bytes_per_step"])
    if est.get("hbm_gb_per_sec") is not None:
        reg.gauge("step_hbm_gb_per_sec",
                  "achieved HBM bandwidth of the timed step").set(
            est["hbm_gb_per_sec"])


def publish_mfu_window(cost: Optional[Dict[str, float]], seconds: float,
                       *, kind: Optional[str] = None, alpha: float = 0.2,
                       registry=None) -> Dict[str, Any]:
    """Windowed MFU: fold one :func:`mfu_estimate` into the
    ``mfu_ewma`` gauge so utilization updates continuously (the
    goodput ledger calls this with its productive-step-window median
    each publish) instead of only at the one-shot :func:`publish_mfu`.

    Same degradation contract as everything here: when the estimate is
    null, the gauge is left untouched and ``mfu_reason`` says why —
    the returned dict carries ``mfu_ewma`` as a value or None."""
    from apex_tpu.telemetry import metrics as _metrics

    reg = registry if registry is not None else _metrics.registry()
    est = mfu_estimate(cost, seconds, kind)
    if est["mfu"] is None:
        reg.set_info("mfu_reason", est.get("mfu_reason"))
        est["mfu_ewma"] = None
        return est
    g = reg.gauge("mfu_ewma",
                  "EWMA model FLOPs utilization over the ledger's "
                  "productive-step window")
    prev = g.value()
    cur = est["mfu"] if not prev else (
        (1.0 - alpha) * prev + alpha * est["mfu"])
    cur = round(cur, 6)
    g.set(cur)
    est["mfu_ewma"] = cur
    return est


__all__ = [
    "bytes_per_element",
    "chip_peak_tflops",
    "compiled_cost",
    "device_kind",
    "jitted_cost",
    "mfu_estimate",
    "normalize_cost_analysis",
    "publish_mfu",
    "publish_mfu_window",
    "train_step_cost",
]
