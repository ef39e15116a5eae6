"""Kernel tuning sweep — run on the real chip to pick tile/block sizes.

Chained-iteration timing (see tpu_smoke._time): each candidate config
runs K iterations inside one jitted fori_loop, so per-op numbers are
kernel time, not host dispatch. Prints a table per op family; the
winner feeds the defaults in the op modules.

    python tools/tpu_tune.py            # everything
    python tools/tpu_tune.py attn ln    # subset
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from tpu_smoke import _time  # noqa: E402  (chained timer)
from tpu_smoke import grad_feed as _grad_feed  # noqa: E402
from tpu_smoke import opt_feed as _opt_feed  # noqa: E402

from apex_tpu.ops.mosaic_limits import block_ok  # noqa: E402

_LINES = []
_print = print


def print(*args, **kw):  # noqa: A001 — tee stdout into the record
    _LINES.append(" ".join(str(a) for a in args))
    _print(*args, **kw)

def tune_attn():
    import jax
    import jax.numpy as jnp

    from apex_tpu.ops.attention import flash_attention

    rng = np.random.RandomState(0)
    for (b, h, s, d), dt in [((4, 16, 2048, 128), jnp.bfloat16),
                             ((2, 16, 4096, 128), jnp.bfloat16),
                             ((8, 16, 512, 64), jnp.bfloat16)]:
        q, k, v = (jnp.asarray(
            rng.randn(b, h, s, d).astype(np.float32) * 0.1, dt)
            for _ in range(3))
        print(f"flash fwd+bwd bhsd={(b, h, s, d)} {dt.__name__}")
        base = None
        for bq, bk in [(256, 256), (512, 512), (512, 1024), (1024, 512),
                       (1024, 1024), (2048, 1024), (1024, 2048)]:
            if bq > s or bk > s:
                continue
            isz = jnp.dtype(dt).itemsize
            if not (block_ok(bq, d, isz) and block_ok(bk, d, isz)):
                print(f"  bq={bq:5d} bk={bk:5d}  SKIP (block the "
                      "compiler refuses, ops/mosaic_limits.py)")
                continue

            def fwd_bwd(q, k, v, bq=bq, bk=bk):
                def loss(q, k, v):
                    o = flash_attention(q, k, v, causal=True, impl="pallas",
                                        block_q=bq, block_k=bk)
                    return jnp.sum(o.astype(jnp.float32) ** 2)
                l, g = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
                return (l, *g)

            try:
                t = _time(fwd_bwd, q, k, v, iters=3, chain=10,
                          feed=_grad_feed)
                base = base or t
                print(f"  bq={bq:5d} bk={bk:5d}  {t*1e3:8.3f} ms "
                      f"({base/t:4.2f}x)")
            except Exception as e:  # noqa: BLE001
                print(f"  bq={bq:5d} bk={bk:5d}  FAIL {str(e)[:60]}")

        def xla_fb(q, k, v):
            def loss(q, k, v):
                o = flash_attention(q, k, v, causal=True, impl="xla")
                return jnp.sum(o.astype(jnp.float32) ** 2)
            l, g = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
            return (l, *g)

        try:
            t = _time(xla_fb, q, k, v, iters=3, chain=10, feed=_grad_feed)
            print(f"  xla reference   {t*1e3:8.3f} ms")
        except Exception as e:  # noqa: BLE001
            print(f"  xla reference   FAIL {str(e)[:60]}")


def tune_attn_bwd():
    """Sweep the BACKWARD dq/dkv blocks independently of the forward's
    (fixed at the round-2 winner 1024x1024): the dq and dkv kernels have
    different reuse patterns than the fwd, so their best block shape can
    differ. Winner feeds flash_attention's bwd_block_q/bwd_block_k
    defaults."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.ops.attention import flash_attention

    rng = np.random.RandomState(0)
    for (b, h, s, d), dt in [((4, 16, 2048, 128), jnp.bfloat16),
                             ((2, 16, 4096, 128), jnp.bfloat16)]:
        q, k, v = (jnp.asarray(
            rng.randn(b, h, s, d).astype(np.float32) * 0.1, dt)
            for _ in range(3))
        print(f"flash BWD blocks (fwd fixed 1024x1024) "
              f"bhsd={(b, h, s, d)} {dt.__name__}")
        base = None
        for bbq, bbk in [(256, 256), (512, 512), (512, 1024), (1024, 512),
                         (1024, 1024), (2048, 1024), (1024, 2048),
                         (2048, 2048), (256, 1024), (1024, 256)]:
            if bbq > s or bbk > s:
                continue
            isz = jnp.dtype(dt).itemsize
            if not (block_ok(bbq, d, isz) and block_ok(bbk, d, isz)):
                print(f"  bbq={bbq:5d} bbk={bbk:5d}  SKIP (block the "
                      "compiler refuses, ops/mosaic_limits.py)")
                continue

            def fwd_bwd(q, k, v, bbq=bbq, bbk=bbk):
                def loss(q, k, v):
                    o = flash_attention(q, k, v, causal=True,
                                        impl="pallas",
                                        block_q=1024, block_k=1024,
                                        bwd_block_q=bbq, bwd_block_k=bbk)
                    return jnp.sum(o.astype(jnp.float32) ** 2)
                l, g = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
                return (l, *g)

            try:
                t = _time(fwd_bwd, q, k, v, iters=3, chain=10,
                          feed=_grad_feed)
                base = base or t
                print(f"  bbq={bbq:5d} bbk={bbk:5d}  {t*1e3:8.3f} ms "
                      f"({base/t:4.2f}x)")
            except Exception as e:  # noqa: BLE001
                print(f"  bbq={bbq:5d} bbk={bbk:5d}  FAIL {str(e)[:60]}")


def tune_ln():
    import jax
    import jax.numpy as jnp

    from apex_tpu.ops import layer_norm as ln_mod
    from apex_tpu.ops.layer_norm import fused_layer_norm

    rng = np.random.RandomState(0)
    rows, hidden = 8192, 4096
    x = jnp.asarray(rng.randn(rows, hidden).astype(np.float32),
                    jnp.bfloat16)
    w = jnp.asarray(rng.randn(hidden).astype(np.float32))
    b = jnp.asarray(rng.randn(hidden).astype(np.float32))

    def fwd_bwd(x, w, b, impl):
        def loss(x, w, b):
            return jnp.sum(
                fused_layer_norm(x, w, b, impl=impl).astype(jnp.float32)
                ** 2)
        l, g = jax.value_and_grad(loss, argnums=(0, 1, 2))(x, w, b)
        return (l, *g)

    print(f"layer_norm fwd+bwd rows={rows} hidden={hidden} bf16 x")
    orig = ln_mod._DEF_ROWS
    for tile_rows in (64, 128, 256, 512, 1024):
        if not block_ok(tile_rows, hidden, 2):
            print(f"  tile_rows={tile_rows:5d}  SKIP (block the "
                  "compiler refuses, ops/mosaic_limits.py)")
            continue
        ln_mod._DEF_ROWS = tile_rows
        try:
            t = _time(lambda x, w, b: fwd_bwd(x, w, b, "pallas"),
                      x, w, b, iters=3, chain=20, feed=_grad_feed)
            print(f"  tile_rows={tile_rows:5d}  {t*1e3:8.3f} ms")
        except Exception as e:  # noqa: BLE001
            print(f"  tile_rows={tile_rows:5d}  FAIL {str(e)[:60]}")
    ln_mod._DEF_ROWS = orig
    t = _time(lambda x, w, b: fwd_bwd(x, w, b, "xla"), x, w, b,
              iters=3, chain=20, feed=_grad_feed)
    print(f"  xla reference     {t*1e3:8.3f} ms")


def tune_softmax():
    import jax
    import jax.numpy as jnp

    from apex_tpu.ops.softmax import scaled_upper_triang_masked_softmax

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(32, 1024, 1024).astype(np.float32),
                    jnp.bfloat16)

    def fwd_bwd(x, impl):
        def loss(x):
            return jnp.sum(
                scaled_upper_triang_masked_softmax(x, 0.5, impl=impl)
                .astype(jnp.float32) ** 2)
        return jax.value_and_grad(loss)(x)

    print("causal softmax fwd+bwd (32,1024,1024) bf16")
    for impl in ("pallas", "xla"):
        try:
            t = _time(lambda x: fwd_bwd(x, impl), x, iters=3, chain=20,
                      feed=_grad_feed)
            print(f"  {impl:8s}  {t*1e3:8.3f} ms")
        except Exception as e:  # noqa: BLE001
            print(f"  {impl:8s}  FAIL {str(e)[:60]}")


def _sweep_tile_rows(label, step_fn, args, n, accesses_per_elem):
    """Sweep engine.DEFAULT_TILE_ROWS for one fused-update step.

    ``accesses_per_elem`` = fp32 reads+writes per element (drives the
    achieved-GB/s column; keep it in sync with the op's actual traffic).
    """
    from apex_tpu.multi_tensor import engine

    print(f"{label} n={n}")
    orig = engine.DEFAULT_TILE_ROWS
    for tile_rows in (128, 256, 512, 1024, 2048):
        if not block_ok(tile_rows, 128, 4):
            print(f"  tile_rows={tile_rows:5d}  SKIP (block the "
                  "compiler refuses, ops/mosaic_limits.py)")
            continue
        engine.DEFAULT_TILE_ROWS = tile_rows
        try:
            t = _time(step_fn, *args, iters=3, chain=5, feed=_opt_feed)
            gbps = accesses_per_elem * n * 4 / t / 1e9
            print(f"  tile_rows={tile_rows:5d}  {t*1e3:8.3f} ms "
                  f"({gbps:6.1f} GB/s)")
        except Exception as e:  # noqa: BLE001
            print(f"  tile_rows={tile_rows:5d}  FAIL {str(e)[:60]}")
    engine.DEFAULT_TILE_ROWS = orig


def tune_opt():
    import jax
    import jax.numpy as jnp

    import apex_tpu.multi_tensor as mt

    rng = np.random.RandomState(0)
    n = 64_000_000   # ~BERT-large scale flat buffer
    p = jnp.asarray(rng.randn(n).astype(np.float32))
    g = jnp.asarray(rng.randn(n).astype(np.float32) * 1e-3)
    m = jnp.zeros_like(p)
    v = jnp.zeros_like(p)

    def adam_step(p, m, v, g, impl="pallas"):
        p2, m2, v2, f = mt.fused_adam_update(
            p, m, v, g, lr=1e-3, step=2, weight_decay=0.01, impl=impl)
        return (p2, m2, v2)

    # adam: reads p/m/v/g + writes p/m/v = 7 accesses per element
    _sweep_tile_rows("fused adam update", adam_step, (p, m, v, g), n, 7)
    t = _time(lambda *a: adam_step(*a, impl="xla"), p, m, v, g,
              iters=3, chain=5, feed=_opt_feed)
    print(f"  xla reference     {t*1e3:8.3f} ms ({7*n*4/t/1e9:6.1f} GB/s)")

    # LAMB with the stage-1-fused per-tensor norm partials: sweep the
    # stage-1 tile (read via DEFAULT_TILE_ROWS at call time). Layout
    # only needs shapes/dtypes — no device zeros materialized.
    tree = {f"p{i}": jax.ShapeDtypeStruct((4096, 1024), jnp.float32)
            for i in range(16)}
    space = mt.FlatSpace.create(tree)
    pL = jnp.asarray(rng.randn(space.total).astype(np.float32))
    gL = jnp.asarray(rng.randn(space.total).astype(np.float32) * 1e-3)
    mL = jnp.zeros_like(pL)
    vL = jnp.zeros_like(pL)

    def lamb_step(p, m_, v_, g_):
        p2, m2, v2, f = mt.fused_lamb_update(
            p, m_, v_, g_, space, lr=1e-3, step=2, weight_decay=0.01,
            impl="pallas")
        return (p2, m2, v2)

    # stage 1: 4 reads + 3 writes; stage 2: 2 reads + 1 write = 10
    _sweep_tile_rows("fused lamb update (stage-1-fused norms)",
                     lamb_step, (pL, mL, vL, gL), space.total, 10)


def tune_segmented():
    """Sweep the segmented one-pass LAMB's knobs: segment size
    (VMEM-scratch bound) x scratch config (stash_p / p-stream /
    bf16-u). This is the production headline impl — its winner feeds
    flat_buffer.default_seg_elems / DEFAULT_SEG_VMEM_BUDGET."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.multi_tensor.flat_buffer import (
        default_seg_elems,
        segmented_space,
    )
    from apex_tpu.multi_tensor.segmented import (
        CHUNK,
        fused_lamb_segmented_update,
    )

    rng = np.random.RandomState(0)
    # optdiag's 41.5M-param tensor mix: many smalls + a few large leaves
    tree = {}
    for i in range(48):
        tree[f"w{i}"] = jax.ShapeDtypeStruct((512, 1024), jnp.float32)
    for i in range(8):
        tree[f"b{i}"] = jax.ShapeDtypeStruct((1024,), jnp.float32)
    for i in range(4):
        tree[f"W{i}"] = jax.ShapeDtypeStruct((4096, 1024), jnp.float32)

    est = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(tree))
    base_seg = default_seg_elems(est)
    configs = [("stash_p", dict(stash_p=True)),
               ("p-stream", dict(stash_p=False)),
               ("bf16-u", dict(stash_p=False, u_dtype=jnp.bfloat16))]
    for seg_mult in (0.5, 1.0, 2.0):
        seg = max(CHUNK, int(base_seg * seg_mult) // CHUNK * CHUNK)
        space, meta = segmented_space(tree, seg_elems=seg)
        p = jnp.asarray(rng.randn(space.total).astype(np.float32))
        g = jnp.asarray(
            rng.randn(space.total).astype(np.float32) * 1e-3)
        m = jnp.zeros_like(p)
        v = jnp.zeros_like(p)

        for label, kw in configs:
            def step(p_, m_, v_, g_, kw=kw):
                p2, m2, v2, f = fused_lamb_segmented_update(
                    p_, m_, v_, g_, space, meta, lr=1e-3, step=2,
                    weight_decay=0.01, use_nvlamb=True,
                    max_grad_norm=0.0, impl="pallas", **kw)
                return (p2, m2, v2)

            # traffic model: small segments ride the one-pass kernel
            # (7 accesses/elem, 8 with p-stream); leaves larger than a
            # segment take the two-stage path (~10). Weight by the
            # actual split so the GB/s is comparable with tune_opt's.
            acc_small = 8 if not kw.get("stash_p", True) else 7
            large_elems = sum(plen for _, _, plen in meta.large)
            small_elems = space.total - large_elems
            traffic = (acc_small * small_elems + 10 * large_elems) * 4
            try:
                t = _time(step, p, m, v, g, iters=3, chain=5,
                          feed=_opt_feed)
                gbps = traffic / t / 1e9
                print(f"  seg={seg:>9} ({seg_mult:3.1f}x) {label:9s} "
                      f"{t*1e3:8.3f} ms ({gbps:6.1f} GB/s, "
                      f"{small_elems/space.total:4.0%} one-pass)")
            except Exception as e:  # noqa: BLE001 — sweep must finish
                msg = str(e).split("\n")[0][:100]
                print(f"  seg={seg:>9} ({seg_mult:3.1f}x) {label:9s} "
                      f"FAILED {type(e).__name__}: {msg}")
        del p, g, m, v


ALL = {"attn": tune_attn, "attnbwd": tune_attn_bwd, "ln": tune_ln,
       "softmax": tune_softmax, "opt": tune_opt,
       "segmented": tune_segmented}

if __name__ == "__main__":
    import jax

    from apex_tpu import compile_cache

    compile_cache.enable()
    print("backend:", jax.default_backend())
    which = sys.argv[1:] or list(ALL)
    for name in which:
        ALL[name]()
    if jax.default_backend() == "tpu":
        from apex_tpu.records import write_record

        path = write_record(
            "tune", {"modes": which, "lines": _LINES},
            backend="tpu")
        if path:
            _print(f"# record: {path}", file=sys.stderr)
