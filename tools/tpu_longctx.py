"""Long-context scaling measurement — sliding-window DMA banding on chip.

The banded flash kernel walks only the k-blocks inside each query's
sliding window (apex_tpu/ops/attention.py `_band`), so fwd+bwd cost for
a fixed window should scale ~linearly in sequence length where full
causal attention scales quadratically. This records that claim on real
hardware at S = 4k/8k/16k; nothing in the reference reaches these
lengths (its fmha caps at seqlen 512, ref
apex/contrib/fmha/fmha.py:33-74).

    python tools/tpu_longctx.py            # full sweep
    python tools/tpu_longctx.py --max-s 8192

Emits one JSON line per (S, variant) with absolute time, achieved
TFLOP/s, and the linear-scaling ratio vs the previous S.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from tpu_smoke import _time, grad_feed  # noqa: E402  (chained timer)

WINDOW = 1024


def _time_adaptive(fn, *args, target_s=2.0, max_chain=400, feed=None):
    """Chained timing sized so total wall >= ``target_s``.

    Each timed call ends in one host round-trip (the fence); a fixed
    small chain measures that floor, not the kernel. Estimate with a
    short chain, then rerun with the chain length that amortizes the
    fence below ~1% of the total.
    """
    t = _time(fn, *args, iters=1, warmup=1, chain=4, feed=feed)
    chain = int(min(max_chain, max(4, target_s / max(t, 1e-6) / 2)))
    if chain <= 4:
        return t
    return _time(fn, *args, iters=2, warmup=1, chain=chain, feed=feed)


def band_flops(b, h, s, d, window):
    """fwd matmul FLOPs of the banded computation: each query row sees
    ~min(window, its causal span) keys; fwd = 2 matmuls of 2*keys*d per
    row; fwd+bwd = 3.5x fwd (bwd recomputes scores + 5 s^2-scale
    matmuls), matching bench.py's attention accounting."""
    rows = np.arange(s, dtype=np.float64)
    keys = np.minimum(rows + 1, window).sum()
    fwd = 2 * (2 * b * h * keys * d)
    return fwd * 3.5


def causal_flops(b, h, s, d):
    fwd = 0.5 * 2 * (2 * b * h * s * s * d)
    return fwd * 3.5


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--max-s", type=int, default=16384)
    ap.add_argument("--causal-max-s", type=int, default=8192,
                    help="largest S to also time full-causal at (the "
                    "quadratic baseline gets slow/large fast)")
    args = ap.parse_args()

    from apex_tpu import compile_cache
    from apex_tpu.telemetry.cost import chip_peak_tflops

    compile_cache.enable()
    import jax
    import jax.numpy as jnp

    from apex_tpu.ops.attention import flash_attention

    backend = jax.default_backend()
    on_cpu = backend == "cpu"
    impl = "interpret" if on_cpu else "pallas"
    peak = chip_peak_tflops(str(jax.devices()[0].device_kind)) \
        if not on_cpu else None

    b, h, d = (1, 2, 64) if on_cpu else (1, 16, 128)
    seqs = [512, 1024] if on_cpu else \
        [s for s in (4096, 8192, 16384) if s <= args.max_s]
    dt = jnp.float32 if on_cpu else jnp.bfloat16
    rng = np.random.RandomState(0)

    prev = {}
    for s in seqs:
        q, k, v = (jnp.asarray(
            rng.randn(b, h, s, d).astype(np.float32) * 0.1, dt)
            for _ in range(3))
        variants = [("window", dict(causal=True, window_size=WINDOW))]
        if s <= args.causal_max_s:
            variants.append(("causal", dict(causal=True)))
        for name, kw in variants:
            def fwd_bwd(q, k, v, kw=kw):
                def loss(q, k, v):
                    o = flash_attention(q, k, v, impl=impl, **kw)
                    return jnp.sum(o.astype(jnp.float32) ** 2)
                l, g = jax.value_and_grad(loss, argnums=(0, 1, 2))(
                    q, k, v)
                return (l, *g)

            try:
                if on_cpu:
                    t = _time(fwd_bwd, q, k, v, iters=2, warmup=1,
                              chain=2, feed=grad_feed)
                else:
                    t = _time_adaptive(fwd_bwd, q, k, v,
                                       feed=grad_feed)
            except Exception as e:  # noqa: BLE001
                print(json.dumps({
                    "s": s, "variant": name, "error":
                    f"{type(e).__name__}: {str(e)[:120]}"}))
                continue
            fl = (band_flops(b, h, s, d, WINDOW) if name == "window"
                  else causal_flops(b, h, s, d))
            tf = fl / t / 1e12
            rec = {
                "s": s, "variant": name, "ms": round(t * 1e3, 3),
                "tflops_per_sec": round(tf, 2),
                "mfu": round(tf / peak, 4) if peak else None,
                "backend": backend, "window": WINDOW,
                "shape_bhd": [b, h, d],
            }
            if name in prev:
                ps, pt = prev[name]
                # window should track s (ratio ~ s/ps); causal ~ (s/ps)^2
                rec["time_ratio_vs_prev_s"] = round(t / pt, 2)
                rec["s_ratio"] = round(s / ps, 2)
            prev[name] = (s, t)
            print(json.dumps(rec))
            if backend == "tpu":
                from apex_tpu.records import write_record

                write_record("longctx", rec, backend="tpu")


if __name__ == "__main__":
    main()
