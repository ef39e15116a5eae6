"""Hardware smoke + parity sweep for every Pallas kernel.

The test suite runs kernels in interpreter mode on CPU (tests/conftest.py);
this tool runs the SAME kernel-vs-XLA comparisons compiled for the real
backend (TPU via Mosaic), mirroring how the reference validates its CUDA
exts on-device (ref: tests/L0/run_amp/test_multi_tensor_scale.py style).

    python tools/tpu_smoke.py          # parity PASS/FAIL per op + timing
    python tools/tpu_smoke.py --perf   # adds a perf table (pallas vs xla)

It runs on the devices jax finds (``JAX_PLATFORMS=cpu`` with ``--impl
interpret`` is the logic check without a chip). Exit code is the number
of failing ops.
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _time(fn, *args, iters=30, warmup=2, chain=20, feed=None):
    """Per-call device time of ``fn``: ``chain`` iterations run inside
    ONE jitted fori_loop, so that host dispatch, which would dominate
    a sub-ms kernel, is paid once per ``chain`` calls. The outer loop
    then queues all calls and syncs once, on the ``device_get`` of a
    scalar that depends on all of them.

    ``feed(out, args) -> next_args`` threads each iteration's outputs
    into the next iteration's inputs. THIS IS LOAD-BEARING, because of
    dead-code elimination: without a real data dependence XLA hoists
    the loop-invariant ``fn(*args)`` out of the fori_loop and the
    "chain" measures ONE call (an optimization_barrier on a discarded
    output does NOT stop it; a 1024x1024 matmul "sped up" 50x at
    chain=50). When no natural feed exists, every output leaf is
    folded into a probe scalar that scales the inputs — a multiply by
    a runtime value the compiler cannot fold away.
    """
    import jax
    import jax.numpy as jnp

    def chained(*a):
        def body(_, c):
            carry, probe = c
            out = fn(*carry)
            if feed is not None:
                nxt = feed(out, carry)
                # leaves the feed threads forward stay live through the
                # loop carry; only the DEAD leaves (e.g. the loss in a
                # (loss, *grads) tuple) need folding into the probe —
                # summing live ones would add full-array reductions to
                # every timed iteration
                live = {id(l) for l in jax.tree.leaves(nxt)}
                dead = [l for l in jax.tree.leaves(out)
                        if id(l) not in live]
            else:
                # no natural output->input feed: every output leaf is
                # dead, and EVERY input must be made iteration-variant
                # (scaling only one would let XLA hoist sub-computations
                # that read the others) — scale by a runtime-dependent
                # 1.0 (isnan of a runtime value can't be constant-
                # folded). This costs a read+write of the inputs plus
                # the probe reductions per iteration; prefer a real
                # `feed` for bandwidth-sensitive measurements.
                dead = list(jax.tree.leaves(out))
                one = jnp.where(jnp.isnan(probe), probe, 1.0)
                nxt = jax.tree.map(
                    lambda l: (l * one.astype(l.dtype))
                    if hasattr(l, "dtype")
                    and jnp.issubdtype(l.dtype, jnp.floating) else l,
                    carry)
            probe = probe + sum(
                jnp.sum(l).astype(jnp.float32) for l in dead)
            return (tuple(nxt), probe)

        final, probe = jax.lax.fori_loop(0, chain, body,
                                         (a, jnp.float32(0.0)))
        # tap one element of each final carry leaf: the chain's last
        # outputs are consumed, so no iteration can be pruned, while the
        # host transfer stays scalar. (Element-0 slices can't reach back
        # through the loop: carries are full arrays every iteration.)
        return probe + sum(
            l.ravel()[0].astype(jnp.float32)
            for l in jax.tree.leaves(final))

    f = jax.jit(chained)
    for _ in range(warmup):
        jax.device_get(f(*args))
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = f(*args)
    jax.device_get(out)
    return (time.perf_counter() - t0) / (iters * chain)


def grad_feed(out, carry):
    """Natural feed for ``(loss, *grads)`` outputs: grads become the
    next iteration's inputs (shapes/dtypes match their primals)."""
    return out[1:]


def opt_feed(out, carry):
    """Natural feed for optimizer steps ``(p,m,v,g) -> (p2,m2,v2)``:
    thread the state, reuse the grad."""
    return (*out, carry[3])


def run(perf=False, kimpl="pallas", only=None):
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    results = []

    def check(name, fn, *args, tol=2e-2, grad_wrt=None):
        """Compare impl='pallas' vs impl='xla' outputs (and grads)."""
        import functools

        if only and only not in name:
            return
        try:
            f_p = jax.jit(functools.partial(fn, impl=kimpl))
            f_x = jax.jit(functools.partial(fn, impl="xla"))
            out_p = jax.tree.leaves(f_p(*args))
            out_x = jax.tree.leaves(f_x(*args))
            def rel_err(pairs):
                # max relative error, absolute below unit scale
                return max(
                    float(jnp.max(
                        jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))
                        / (1.0 + jnp.abs(b.astype(jnp.float32)))))
                    for a, b in zip(*pairs) if hasattr(a, "dtype"))

            err = rel_err((out_p, out_x))
            ok = err < tol
            if grad_wrt is not None and ok:
                def loss(impl_):
                    def g(*a):
                        out = fn(*a, impl=impl_)
                        lv = jax.tree.leaves(out)[0]
                        return jnp.sum(lv.astype(jnp.float32) ** 2)
                    return g
                gp = jax.tree.leaves(
                    jax.jit(jax.grad(loss(kimpl), argnums=grad_wrt))(*args))
                gx = jax.tree.leaves(
                    jax.jit(jax.grad(loss("xla"), argnums=grad_wrt))(*args))
                gerr = rel_err((gp, gx))
                ok = gerr < tol * 10
                err = max(err, gerr)
            t_p = t_x = None
            if perf and ok:
                t_p = _time(f_p, *args)
                t_x = _time(f_x, *args)
            results.append((name, ok, err, t_p, t_x))
            mark = "PASS" if ok else "FAIL"
            extra = ""
            if t_p is not None:
                extra = f"  pallas {t_p*1e3:8.3f} ms  xla {t_x*1e3:8.3f} ms  ({t_x/t_p:4.2f}x)"
            print(f"  [{mark}] {name:42s} max_err {err:.2e}{extra}")
        except Exception as e:  # noqa: BLE001 — report, keep sweeping
            results.append((name, False, float("inf"), None, None))
            msg = str(e).split("\n")[0][:140]
            print(f"  [FAIL] {name:42s} {type(e).__name__}: {msg}")

    print(f"backend: {jax.default_backend()}  devices: {len(jax.devices())}")
    if perf:
        print("# perf note: timings use _time's no-feed fallback, which "
              "adds fixed per-iteration probe traffic (one input "
              "read+write + output reductions). Common-mode for both "
              "impls, so the (Nx) column UNDERSTATES bandwidth-bound "
              "kernel speedups; tools/tpu_tune.py carries the "
              "feed-threaded numbers that count.")

    # ---- multi_tensor engine ops over a flat buffer -------------------
    from apex_tpu import multi_tensor as mt

    tree = {f"p{i}": jnp.asarray(rng.randn(*s).astype(np.float32))
            for i, s in enumerate([(1024, 1024), (4096,), (513, 255), (7,)])}
    space = mt.FlatSpace.create(tree)
    buf = space.pack(tree)
    gbuf = space.pack(jax.tree.map(
        lambda v: jnp.asarray(rng.randn(*v.shape).astype(np.float32)), tree))

    check("multi_tensor_scale", lambda b, impl: mt.multi_tensor_scale(b, 0.5, impl=impl), buf)
    check("multi_tensor_axpby", lambda b, g, impl: mt.multi_tensor_axpby(b, g, 2.0, -0.5, impl=impl), buf, gbuf)
    check("multi_tensor_l2norm", lambda b, impl: mt.multi_tensor_l2norm(b, impl=impl), buf)
    check("per_tensor_l2norm", lambda b, impl: mt.per_tensor_l2norm(b, space, impl=impl), buf, tol=1e-1)

    m = jnp.zeros_like(buf)
    v = jnp.zeros_like(buf)
    check("fused_adam_update",
          lambda p, g, m_, v_, impl: mt.fused_adam_update(
              p, m_, v_, g, lr=1e-3, beta1=0.9, beta2=0.999, eps=1e-8,
              step=1, weight_decay=0.01, impl=impl),
          buf, gbuf, m, v, tol=1e-4)
    check("fused_sgd_update",
          lambda p, g, m_, impl: mt.fused_sgd_update(
              p, g, m_, lr=1e-2, momentum=0.9, weight_decay=1e-4,
              nesterov=True, impl=impl),
          buf, gbuf, m, tol=1e-4)
    check("fused_lamb_update",
          lambda p, g, m_, v_, impl: mt.fused_lamb_update(
              p, m_, v_, g, space, lr=1e-3, beta1=0.9, beta2=0.999,
              eps=1e-6, step=1, weight_decay=0.01, impl=impl),
          buf, gbuf, m, v, tol=1e-4)
    # segment-resident single-pass LAMB vs its two-stage reference on
    # the SAME segmented layout — the round-3 schedule that brings
    # LAMB to ~7 HBM accesses/element (multi_tensor/segmented.py).
    # New Mosaic surface: (seg, phase, chunk) grid with resident
    # phase-1 blocks, VMEM scratch persisting across grid steps, and
    # in-kernel one-hot dot_generals.
    from apex_tpu.multi_tensor.flat_buffer import segmented_space
    from apex_tpu.multi_tensor.segmented import (
        CHUNK as SEG_CHUNK,
        fused_lamb_segmented_update,
    )

    seg_tree = {
        "w0": jnp.asarray(rng.randn(600, 700).astype(np.float32)),
        "b0": jnp.asarray(rng.randn(700).astype(np.float32)),
        "w1": jnp.asarray(rng.randn(3 * SEG_CHUNK + 777)
                          .astype(np.float32)),   # large leaf
        "w2": jnp.asarray(rng.randn(512, 512).astype(np.float32)),
    }
    seg_space, seg_meta = segmented_space(seg_tree,
                                          seg_elems=2 * SEG_CHUNK)
    seg_pk = lambda t: seg_space.pack(t, dtype=jnp.float32)  # noqa: E731
    seg_p = seg_pk(seg_tree)
    seg_g = seg_pk(jax.tree.map(
        lambda x: jnp.asarray(
            np.random.RandomState(7).randn(*x.shape).astype(np.float32)
            * 1e-2), seg_tree))
    seg_m = jnp.zeros_like(seg_p)
    seg_v = jnp.zeros_like(seg_p)

    check("fused_lamb_segmented (one-pass)",
          lambda p, g, m_, v_, impl: fused_lamb_segmented_update(
              p, m_, v_, g, seg_space, seg_meta, lr=1e-3,
              weight_decay=0.01, use_nvlamb=True, step=1,
              max_grad_norm=0.0, impl=impl),
          seg_p, seg_g, seg_m, seg_v, tol=1e-4)

    # segmented + in-kernel SR: the counter-hash bits make the stream
    # impl-independent (tests/test_multi_tensor.py pins the interpret
    # schedule); this chip check proves the SAME schedule lowers
    # through Mosaic and stays unbiased: a tiny constant update must
    # round up/down ~50/50 and be unbiased in the mean
    name = "fused_lamb_segmented SR bf16 (in-kernel prng)"
    if kimpl == "pallas" and not (only and only not in name):
        try:
            sr_tree = {"w": jnp.full((2 * SEG_CHUNK,), 1.0, jnp.bfloat16)}
            sr_space, sr_meta = segmented_space(sr_tree,
                                                seg_elems=2 * SEG_CHUNK)
            sr_p = sr_space.pack(sr_tree, dtype=jnp.bfloat16)
            # grads sized so the LAMB update lands well below one bf16
            # ulp of 1.0 (2^-8): SR must preserve it in expectation
            sr_g = jnp.full((sr_space.total,), 1.0, jnp.float32)
            sr_m = jnp.zeros((sr_space.total,), jnp.float32)
            sr_v = jnp.zeros((sr_space.total,), jnp.float32)
            p2s, *_ = jax.jit(
                lambda p_, m_, v_, g_: fused_lamb_segmented_update(
                    p_, m_, v_, g_, sr_space, sr_meta, lr=2.0 ** -11,
                    weight_decay=0.0, use_nvlamb=False, step=1,
                    max_grad_norm=0.0, bias_correction=True,
                    impl=kimpl, sr_seed=11))(sr_p, sr_m, sr_v, sr_g)
            vals = np.asarray(jax.device_get(p2s), np.float32)
            # exact update: 1 - 2^-11 (trust ratio 1: wd=0, nvlamb off);
            # bf16 neighbors are 1.0 and 1-2^-8 -> frac_hi ~ 1-2^-3/...
            exp = 1.0 - 2.0 ** -11
            mean_err = abs(float(vals.mean()) - exp)
            uniq = np.unique(vals)
            ok = mean_err < 2e-4 and 1 < uniq.size <= 3
            results.append((name, ok, mean_err, None, None))
            print(f"  [{'PASS' if ok else 'FAIL'}] {name:42s} "
                  f"mean_err {mean_err:.2e} uniq {uniq.size}")
        except Exception as e:  # noqa: BLE001 — report, keep sweeping
            results.append((name, False, float("inf"), None, None))
            msg = str(e).split("\n")[0][:140]
            print(f"  [FAIL] {name:42s} {type(e).__name__}: {msg}")

    # the VMEM-budget variants must also lower: p-streaming (half the
    # scratch) and the bf16 u-stash
    check("fused_lamb_segmented stream_p",
          lambda p, g, m_, v_, impl: fused_lamb_segmented_update(
              p, m_, v_, g, seg_space, seg_meta, lr=1e-3,
              weight_decay=0.01, use_nvlamb=True, step=1,
              max_grad_norm=0.0, stash_p=False, impl=impl),
          seg_p, seg_g, seg_m, seg_v, tol=1e-4)
    check("fused_lamb_segmented bf16-u",
          lambda p, g, m_, v_, impl: fused_lamb_segmented_update(
              p, m_, v_, g, seg_space, seg_meta, lr=1e-3,
              weight_decay=0.01, use_nvlamb=True, step=1,
              max_grad_norm=0.0, stash_p=False, u_dtype=jnp.bfloat16,
              impl=impl),
          seg_p, seg_g, seg_m, seg_v, tol=1e-2)

    check("fused_novograd_update",
          lambda p, g, m_, impl: mt.fused_novograd_update(
              p, m_, jnp.zeros((space.num_leaves,), jnp.float32), g, space,
              lr=1e-3, beta1=0.95, beta2=0.98, eps=1e-8, step=1,
              weight_decay=0.01, impl=impl),
          buf, gbuf, m, tol=1e-4)
    check("fused_lars_update",
          lambda p, g, m_, impl: mt.fused_lars_update(
              p, m_, g, space, lr=1e-2, momentum=0.9, weight_decay=1e-4,
              trust_coefficient=0.02, impl=impl),
          buf, gbuf, m, tol=1e-4)

    # stochastic rounding: the in-kernel pltpu.prng path has NO CPU
    # lowering, so this statistics check (not parity — streams differ
    # from the xla emulation by design) is its only validation surface
    name = "stochastic_round bf16 (in-kernel prng)"
    if not (only and only not in name):
        try:
            nsr = 1 << 14
            psr = jnp.full((nsr,), 1.0, jnp.bfloat16)
            gsr = jnp.full((nsr,), 2.0 ** -9, jnp.float32)
            p2sr, _, _ = jax.jit(
                lambda p_, g_: mt.fused_sgd_update(
                    p_, jnp.zeros((nsr,), jnp.float32), g_, lr=1.0,
                    impl=kimpl, sr_seed=7))(psr, gsr)
            vals = np.asarray(jax.device_get(p2sr), np.float32)
            frac_hi = float((vals == 1.0).mean())
            mean_err = abs(float(vals.mean()) - (1.0 - 2.0 ** -9))
            ok = abs(frac_hi - 0.5) < 0.05 and mean_err < 2e-4
            results.append((name, ok, mean_err, None, None))
            print(f"  [{'PASS' if ok else 'FAIL'}] {name:42s} "
                  f"mean_err {mean_err:.2e} frac_hi {frac_hi:.3f}")
        except Exception as e:  # noqa: BLE001 — report, keep sweeping
            results.append((name, False, float("inf"), None, None))
            msg = str(e).split("\n")[0][:140]
            print(f"  [FAIL] {name:42s} {type(e).__name__}: {msg}")

    # ---- layer norm / rms norm ---------------------------------------
    from apex_tpu import ops

    x = jnp.asarray(rng.randn(8 * 512, 1024).astype(np.float32))
    w = jnp.asarray(rng.randn(1024).astype(np.float32))
    b = jnp.asarray(rng.randn(1024).astype(np.float32))
    check("fused_layer_norm (fwd+bwd)",
          lambda x_, w_, b_, impl: ops.fused_layer_norm(x_, w_, b_, impl=impl),
          x, w, b, grad_wrt=(0, 1, 2), tol=1e-3)
    check("fused_rms_norm (fwd+bwd)",
          lambda x_, w_, impl: ops.fused_rms_norm(x_, w_, impl=impl),
          x, w, grad_wrt=(0, 1), tol=1e-3)
    xb = x.astype(jnp.bfloat16)
    check("fused_layer_norm bf16",
          lambda x_, w_, b_, impl: ops.fused_layer_norm(x_, w_, b_, impl=impl),
          xb, w, b, tol=1e-1)

    # ---- softmax family ----------------------------------------------
    s4 = jnp.asarray(rng.randn(4, 8, 512, 512).astype(np.float32))
    mask = jnp.asarray(rng.rand(4, 1, 512, 512) < 0.2)
    check("scaled_softmax (fwd+bwd)",
          lambda a, impl: ops.scaled_softmax(a, 0.5, impl=impl),
          s4, grad_wrt=(0,), tol=1e-3)
    s3 = s4.reshape(32, 512, 512)  # (attn_batches, sq, sk)
    check("scaled_upper_triang_masked_softmax",
          lambda a, impl: ops.scaled_upper_triang_masked_softmax(a, 0.5, impl=impl),
          s3, grad_wrt=(0,), tol=1e-3)
    check("scaled_masked_softmax",
          lambda a, m_, impl: ops.scaled_masked_softmax(a, m_, 0.5, impl=impl),
          s4, mask, tol=1e-3)
    s4b = s4.astype(jnp.bfloat16)
    check("scaled_softmax bf16",
          lambda a, impl: ops.scaled_softmax(a, 0.5, impl=impl), s4b, tol=1e-2)

    # ---- rope ---------------------------------------------------------
    t = jnp.asarray(rng.randn(512, 4, 8, 128).astype(np.float32))
    freqs = jnp.asarray(rng.randn(512, 1, 1, 128).astype(np.float32))
    check("fused_apply_rotary_pos_emb (fwd+bwd)",
          lambda t_, f_, impl: ops.fused_apply_rotary_pos_emb(t_, f_, impl=impl),
          t, freqs, grad_wrt=(0,), tol=1e-3)

    # ---- xentropy -----------------------------------------------------
    logits = jnp.asarray(rng.randn(4096, 32000).astype(np.float32))
    labels = jnp.asarray(rng.randint(0, 32000, (4096,)), jnp.int32)
    check("softmax_cross_entropy_loss (fwd+bwd)",
          lambda lg, lb, impl: ops.softmax_cross_entropy_loss(
              lg, lb, smoothing=0.1, impl=impl),
          logits, labels, grad_wrt=(0,), tol=1e-3)

    # ---- flash attention ---------------------------------------------
    q = jnp.asarray(rng.randn(2, 8, 1024, 128).astype(np.float32) * 0.1)
    k = jnp.asarray(rng.randn(2, 8, 1024, 128).astype(np.float32) * 0.1)
    v_ = jnp.asarray(rng.randn(2, 8, 1024, 128).astype(np.float32) * 0.1)
    check("flash_attention causal (fwd+bwd)",
          lambda q_, k_, vv, impl: ops.flash_attention(
              q_, k_, vv, causal=True, impl=impl),
          q, k, v_, grad_wrt=(0, 1, 2), tol=2e-2)
    seg = jnp.asarray(
        np.repeat(np.arange(4), 256)[None, :].repeat(2, 0), jnp.int32)
    check("flash_attention packed-varlen",
          lambda q_, k_, vv, impl: ops.flash_attention(
              q_, k_, vv, segment_ids=seg, impl=impl),
          q, k, v_, tol=2e-2)
    qb, kb, vb = (a.astype(jnp.bfloat16) for a in (q, k, v_))
    check("flash_attention bf16 causal",
          lambda q_, k_, vv, impl: ops.flash_attention(
              q_, k_, vv, causal=True, impl=impl),
          qb, kb, vb, tol=5e-2)
    check("flash_attention sliding-window",
          lambda q_, k_, vv, impl: ops.flash_attention(
              q_, k_, vv, causal=True, window_size=256, impl=impl),
          q, k, v_, grad_wrt=(0, 1, 2), tol=2e-2)
    kg = jnp.asarray(rng.randn(2, 2, 1024, 128).astype(np.float32) * 0.1)
    vg = jnp.asarray(rng.randn(2, 2, 1024, 128).astype(np.float32) * 0.1)
    check("flash_attention GQA (8q/2kv, fwd+bwd)",
          lambda q_, k_, vv, impl: ops.flash_attention(
              q_, k_, vv, causal=True, impl=impl),
          q, kg, vg, grad_wrt=(0, 1, 2), tol=2e-2)
    # the serving decode shape: one query over a cached prefix + itself,
    # a key count (1025) no lane-aligned block divides — padded to the
    # block and masked by index inside the kernels (ops/attention.py)
    kv_seg = jnp.asarray(rng.rand(2, 1025) < 0.3, jnp.int32)
    kd = jnp.asarray(rng.randn(2, 2, 1025, 128).astype(np.float32) * 0.1)
    vd = jnp.asarray(rng.randn(2, 2, 1025, 128).astype(np.float32) * 0.1)
    check("flash_attention decode sk=1025 (padded tail, fwd+bwd)",
          lambda q_, k_, vv, impl: ops.flash_attention(
              q_, k_, vv, kv_segment_ids=kv_seg, impl=impl),
          q[:, :, :1], kd, vd, grad_wrt=(0, 1, 2), tol=2e-2)
    check("flash_attention dropout (fwd+bwd)",
          lambda q_, k_, vv, impl: ops.flash_attention(
              q_, k_, vv, causal=True, dropout_rate=0.1,
              dropout_rng=jax.random.PRNGKey(0), impl=impl),
          q, k, v_, grad_wrt=(0, 1, 2), tol=2e-2)
    check("flash_attention return_lse (fwd+bwd)",
          lambda q_, k_, vv, impl: ops.flash_attention(
              q_, k_, vv, causal=True, return_lse=True, impl=impl),
          q, k, v_, grad_wrt=(0, 1, 2), tol=2e-2)
    pos = jnp.arange(1024, dtype=jnp.int32)
    check("flash_attention positions causal",
          lambda q_, k_, vv, impl: ops.flash_attention(
              q_, k_, vv, causal=True, q_positions=pos, kv_positions=pos,
              impl=impl),
          q, k, v_, grad_wrt=(0, 1, 2), tol=2e-2)

    # ---- ring attention chunk math (single-chunk degenerate ring:
    # flash with positions + lse-merge identity) --------------------
    def chunk_merge(q_, k_, vv, impl):
        o1, l1 = ops.flash_attention(
            q_, k_[:, :, :512], vv[:, :, :512], causal=True,
            q_positions=pos, kv_positions=pos[:512],
            return_lse=True, impl=impl)
        o2, l2 = ops.flash_attention(
            q_, k_[:, :, 512:], vv[:, :, 512:], causal=True,
            q_positions=pos, kv_positions=pos[512:],
            return_lse=True, impl=impl)
        lse = jnp.logaddexp(l1, l2)
        return (o1.astype(jnp.float32) * jnp.exp(l1 - lse)[..., None]
                + o2.astype(jnp.float32) * jnp.exp(l2 - lse)[..., None])

    check("flash chunked lse-merge == full", chunk_merge, q, k, v_,
          tol=2e-2)

    # separately-tuned backward blocks (new bwd_block_q/bwd_block_k
    # threading) must lower through Mosaic and match the XLA grads
    check("flash_attention bwd blocks 512x512",
          lambda q_, k_, vv, impl: ops.flash_attention(
              q_, k_, vv, causal=True, bwd_block_q=512, bwd_block_k=512,
              impl=impl),
          q, k, v_, grad_wrt=(0, 1, 2), tol=2e-2)

    # ring-attention recompute backward's per-chunk kernel path:
    # _flash_bwd_pallas evaluated against GLOBAL (lse, delta) statistics
    # must reproduce the XLA chunk-grads (context_parallel._chunk_grads)
    from apex_tpu.transformer.context_parallel import _chunk_grads

    def ring_chunk_grads(q_, k_, vv, impl):
        half = k_.shape[2] // 2
        out, lse = ops.flash_attention(
            q_, k_, vv, causal=True, return_lse=True, impl="xla")
        g = out.astype(jnp.float32) * 2.0     # d(sum out^2)/d out
        delta = jnp.sum(out.astype(jnp.float32) * g, axis=-1)
        return _chunk_grads(
            q_, k_[:, :, :half], vv[:, :, :half],
            pos, pos[:half], g, lse, delta, q_.shape[-1] ** -0.5, True,
            impl)

    check("ring chunk-grads (global lse) kernel", ring_chunk_grads,
          q, k, v_, tol=2e-2)

    n_fail = sum(1 for _, ok, *_ in results if not ok)
    print(f"\n{len(results) - n_fail}/{len(results)} ops pass on "
          f"{jax.default_backend()}")
    if jax.default_backend() == "tpu":
        from apex_tpu.records import write_record

        path = write_record("smoke", {
            "passed": len(results) - n_fail,
            "total": len(results),
            "impl": kimpl,
            "only": only,
            "perf": bool(perf),
            "results": [
                {"name": n, "ok": bool(ok),
                 "max_err": (float(err) if np.isfinite(err) else None),
                 **({"pallas_ms": round(tp * 1e3, 3),
                     "xla_ms": round(tx * 1e3, 3)}
                    if tp is not None and tx is not None else {})}
                for n, ok, err, tp, tx in results
            ],
        }, backend="tpu")
        if path:
            print(f"# record: {path}", file=sys.stderr)
    return n_fail


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--perf", action="store_true")
    ap.add_argument("--impl", default="pallas",
                    choices=("pallas", "interpret"),
                    help="kernel impl to compare against the XLA path "
                         "(interpret = CPU logic check)")
    ap.add_argument("--only", default=None,
                    help="substring filter: run only configs whose name "
                         "contains this (targeted hardware re-checks)")
    args = ap.parse_args()
    from apex_tpu import compile_cache

    compile_cache.enable()
    sys.exit(run(perf=args.perf, kimpl=args.impl, only=args.only))
