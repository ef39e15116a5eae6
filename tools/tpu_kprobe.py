"""Pallas fused-engine factor isolation: where do the GB/s go?

An earlier round's session notes (tools/tpu_optdiag.py; transcribed,
not in the ledger): raw streaming 574 GB/s, engine pallas adam 133-137
GB/s (tile-size-INsensitive), engine xla impl 236 GB/s, optax-on-trees
~480+. This probe times a
ladder of kernels from a pure copy up to the real engine call, each
step adding ONE suspect factor, so the slowdown attributes to a
mechanism instead of a guess:

  copy1          1-in/1-out pallas copy            (pallas ceiling)
  multi7         4-in/3-out passthrough            (stream count)
  adam_math      + real Adam arithmetic            (VPU cost)
  adam_found     + found_inf SMEM accumulator      (revisited output)
  adam_alias     + input_output_aliases, undonated (defensive
                 copies; NOTE a donated rung is impossible here —
                 donation inside _time's traced loop is a no-op, and
                 the loop's threaded carry already gives XLA
                 steady-state buffer reuse)
  engine         mt.fused_adam_update as shipped
  jnp_fused      one fused jnp expression, no engine machinery

    python tools/tpu_kprobe.py             # n=64M, tile 512
    python tools/tpu_kprobe.py --n 16777216 --tile-rows 1024
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from tpu_smoke import opt_feed  # noqa: E402
from tpu_longctx import _time_adaptive  # noqa: E402

LANES = 128


def rec(**kw):
    print(json.dumps(kw), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=64_000_000)
    ap.add_argument("--tile-rows", type=int, default=512)
    args = ap.parse_args()

    from apex_tpu import compile_cache

    compile_cache.enable()
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    import apex_tpu.multi_tensor as mt

    on_cpu = jax.default_backend() == "cpu"
    n = 1 << 20 if on_cpu else args.n
    tr = args.tile_rows
    tile = tr * LANES
    padded = ((n + tile - 1) // tile) * tile
    num_tiles = padded // tile
    rng = np.random.RandomState(0)
    p = jnp.asarray(rng.randn(padded).astype(np.float32))
    g = jnp.asarray(rng.randn(padded).astype(np.float32) * 1e-3)
    m = jnp.zeros_like(p)
    v = jnp.zeros_like(p)
    gb = padded * 4 / 1e9
    interp = on_cpu
    rec(what="config", n=padded, tile_rows=tr, backend=str(
        jax.default_backend()), fp32_gb=round(gb, 3))

    spec = pl.BlockSpec((tr, LANES), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)
    r2 = lambda b: b.reshape(padded // LANES, LANES)   # noqa: E731

    def timed(name, fn, *bufs, acc, feed):
        try:
            t = _time_adaptive(fn, *bufs, feed=feed)
            rec(what=name, ms=round(t * 1e3, 3),
                gb_per_sec=round(acc * gb / t, 1))
        except Exception as e:  # noqa: BLE001
            rec(what=name, error=f"{type(e).__name__}: {str(e)[:110]}")

    # -- copy1: the pallas streaming ceiling -------------------------
    def copy_kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 1.0000001

    copy_call = pl.pallas_call(
        copy_kernel, grid=(num_tiles,), in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((padded // LANES, LANES),
                                       jnp.float32),
        interpret=interp)
    timed("copy1", lambda x: (copy_call(r2(x)).reshape(-1),), p,
          acc=2, feed=lambda out, carry: out)

    # -- multi7: 4 streams in, 3 out, no math ------------------------
    def multi_kernel(p_ref, m_ref, v_ref, g_ref, po, mo, vo):
        po[...] = p_ref[...] * 1.0000001
        mo[...] = m_ref[...] * 1.0000001
        vo[...] = v_ref[...] + g_ref[...]

    multi_call = pl.pallas_call(
        multi_kernel, grid=(num_tiles,), in_specs=[spec] * 4,
        out_specs=[spec] * 3,
        out_shape=[jax.ShapeDtypeStruct((padded // LANES, LANES),
                                        jnp.float32)] * 3,
        interpret=interp)
    timed("multi7",
          lambda p_, m_, v_, g_: tuple(
              o.reshape(-1) for o in multi_call(
                  r2(p_), r2(m_), r2(v_), r2(g_))),
          p, m, v, g, acc=7, feed=opt_feed)

    # -- adam math (no found, no alias) ------------------------------
    def adam_body(p_, m_, v_, g_):
        m2 = 0.9 * m_ + 0.1 * g_
        v2 = 0.999 * v_ + 0.001 * g_ * g_
        up = m2 / (jnp.sqrt(v2) + 1e-8) + 0.01 * p_
        return p_ - 1e-3 * up, m2, v2

    def adam_kernel(p_ref, m_ref, v_ref, g_ref, po, mo, vo):
        p2, m2, v2 = adam_body(p_ref[...], m_ref[...], v_ref[...],
                               g_ref[...])
        po[...] = p2
        mo[...] = m2
        vo[...] = v2

    adam_call = pl.pallas_call(
        adam_kernel, grid=(num_tiles,), in_specs=[spec] * 4,
        out_specs=[spec] * 3,
        out_shape=[jax.ShapeDtypeStruct((padded // LANES, LANES),
                                        jnp.float32)] * 3,
        interpret=interp)
    timed("adam_math",
          lambda p_, m_, v_, g_: tuple(
              o.reshape(-1) for o in adam_call(
                  r2(p_), r2(m_), r2(v_), r2(g_))),
          p, m, v, g, acc=7, feed=opt_feed)

    # -- + found_inf SMEM accumulator --------------------------------
    def adamf_kernel(p_ref, m_ref, v_ref, g_ref, po, mo, vo, fo):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            fo[0, 0] = jnp.float32(0.0)

        gv = g_ref[...]
        ok = jnp.all(jnp.isfinite(gv))
        fo[0, 0] = jnp.maximum(
            fo[0, 0], jnp.where(ok, 0.0, 1.0).astype(jnp.float32))
        p2, m2, v2 = adam_body(p_ref[...], m_ref[...], v_ref[...], gv)
        po[...] = p2
        mo[...] = m2
        vo[...] = v2

    adamf_call = pl.pallas_call(
        adamf_kernel, grid=(num_tiles,), in_specs=[spec] * 4,
        out_specs=[spec] * 3 + [
            pl.BlockSpec((1, 1), lambda i: (0, 0),
                         memory_space=pltpu.SMEM)],
        out_shape=[jax.ShapeDtypeStruct((padded // LANES, LANES),
                                        jnp.float32)] * 3
        + [jax.ShapeDtypeStruct((1, 1), jnp.float32)],
        interpret=interp)
    timed("adam_found",
          lambda p_, m_, v_, g_: tuple(
              o.reshape(-1) if o.ndim > 1 and o.shape[-1] == LANES
              else o
              for o in adamf_call(r2(p_), r2(m_), r2(v_), r2(g_)))[:3],
          p, m, v, g, acc=7, feed=opt_feed)

    # -- + aliases, UNdonated (XLA inserts defensive copies) ---------
    adama_call = pl.pallas_call(
        adam_kernel, grid=(num_tiles,), in_specs=[spec] * 4,
        out_specs=[spec] * 3,
        out_shape=[jax.ShapeDtypeStruct((padded // LANES, LANES),
                                        jnp.float32)] * 3,
        input_output_aliases={0: 0, 1: 1, 2: 2},
        interpret=interp)
    timed("adam_alias_undonated",
          lambda p_, m_, v_, g_: tuple(
              o.reshape(-1) for o in adama_call(
                  r2(p_), r2(m_), r2(v_), r2(g_))),
          p, m, v, g, acc=7, feed=opt_feed)

    # -- the engine as shipped ---------------------------------------
    timed("engine_fused_adam",
          lambda p_, m_, v_, g_: mt.fused_adam_update(
              p_, m_, v_, g_, lr=1e-3, step=2, weight_decay=0.01,
              impl="xla" if on_cpu else "pallas")[:3],
          p, m, v, g, acc=7, feed=opt_feed)

    # -- one fused jnp expression (XLA on the flat buffer) -----------
    timed("jnp_fused",
          lambda p_, m_, v_, g_: adam_body(p_, m_, v_, g_),
          p, m, v, g, acc=7, feed=opt_feed)


if __name__ == "__main__":
    main()
