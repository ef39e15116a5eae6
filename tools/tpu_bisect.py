"""Bisect a model-bench compile failure to the kernel that causes it.

This compiles + runs each Pallas op AT THE EXACT SHAPES the model
benches use, one jit at a time, so a failing kernel identifies itself
instead of hiding inside a 4000-op model program. A compiler check
failure is an abort, not an exception, and takes the process with it:
ask the compiler without the chip first (tests/test_tpu_compile.py
compiles the main path's kernels for a described v5e).

    python tools/tpu_bisect.py            # all kernel candidates
    python tools/tpu_bisect.py xentropy   # substring filter (kernels)
    python tools/tpu_bisect.py bert_full  # exact: whole-model fwd+bwd
    python tools/tpu_bisect.py gpt_full   # exact: whole-model fwd+bwd
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    only = sys.argv[1] if len(sys.argv) > 1 else None

    from apex_tpu import compile_cache

    compile_cache.enable()
    import jax
    import jax.numpy as jnp

    on_cpu = jax.default_backend() == "cpu"
    impl = "interpret" if on_cpu else "pallas"
    rng = np.random.RandomState(0)

    def check(name, fn, *args):
        if only and only not in name:
            return
        try:
            out = jax.jit(fn)(*args)
            jax.device_get(jax.tree.leaves(out)[0].ravel()[:1])
            print(json.dumps({"op": name, "ok": True}), flush=True)
        except Exception as e:  # noqa: BLE001
            msg = str(e).split("\n")[0][:160]
            print(json.dumps({
                "op": name, "ok": False,
                "error": f"{type(e).__name__}: {msg}"}), flush=True)

    # ---- bench_bert building blocks (bert_large: hidden 1024,
    # heads 16, seq 512, batch 8, vocab 30528) --------------------
    from apex_tpu.ops.layer_norm import fused_layer_norm
    from apex_tpu.ops.xentropy import softmax_cross_entropy_loss
    from apex_tpu.ops.attention import flash_attention
    from apex_tpu.ops.softmax import scaled_masked_softmax

    rows, hidden = (8 * 512, 1024) if not on_cpu else (64, 128)
    x = jnp.asarray(rng.randn(rows, hidden).astype(np.float32) * 0.1,
                    jnp.bfloat16)
    w = jnp.ones((hidden,), jnp.float32)
    b = jnp.zeros((hidden,), jnp.float32)

    def ln_fwd_bwd(x, w, b):
        def loss(x, w, b):
            return jnp.sum(
                fused_layer_norm(x, w, b, impl=impl)
                .astype(jnp.float32) ** 2)
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(x, w, b)

    check("bert_layer_norm_4096x1024_bf16", ln_fwd_bwd, x, w, b)

    for name, vocab in (("bert_xentropy_4096x30528", 30528),
                        ("gpt_xentropy_4096x50257", 50257)):
        vv = vocab if not on_cpu else 512
        logits = jnp.asarray(
            rng.randn(rows, vv).astype(np.float32) * 0.1, jnp.bfloat16)
        labels = jnp.asarray(rng.randint(0, vv, (rows,)), jnp.int32)

        def ce_fwd_bwd(logits, labels):
            def loss(lg):
                return jnp.sum(softmax_cross_entropy_loss(
                    lg, labels, impl=impl))
            return jax.value_and_grad(loss)(logits)

        check(name, ce_fwd_bwd, logits, labels)

    b_, h_, s_, d_ = (8, 16, 512, 64) if not on_cpu else (1, 2, 64, 32)
    q, k, v = (jnp.asarray(
        rng.randn(b_, h_, s_, d_).astype(np.float32) * 0.1,
        jnp.bfloat16) for _ in range(3))
    seg = jnp.zeros((b_, s_), jnp.int32)

    def attn_seg_fwd_bwd(q, k, v, seg):
        def loss(q, k, v):
            o = flash_attention(q, k, v, segment_ids=seg, impl=impl)
            return jnp.sum(o.astype(jnp.float32) ** 2)
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    check("bert_flash_seg_8x16x512x64", attn_seg_fwd_bwd, q, k, v, seg)

    def attn_causal_fwd_bwd(q, k, v):
        def loss(q, k, v):
            o = flash_attention(q, k, v, causal=True, impl=impl)
            return jnp.sum(o.astype(jnp.float32) ** 2)
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    check("gpt_flash_causal_4x16x1024x64", attn_causal_fwd_bwd,
          *((q, k, v) if on_cpu else tuple(
              jnp.asarray(rng.randn(4, 16, 1024, 64)
                          .astype(np.float32) * 0.1, jnp.bfloat16)
              for _ in range(3))))

    scores = jnp.asarray(
        rng.randn(b_, h_, s_, s_).astype(np.float32), jnp.bfloat16)
    mask = jnp.zeros((b_, 1, s_, s_), jnp.bool_)

    def softmax_fwd_bwd(scores):
        def loss(sc):
            return jnp.sum(scaled_masked_softmax(
                sc, mask, 0.125, impl=impl).astype(jnp.float32) ** 2)
        return jax.value_and_grad(loss)(scores)

    check("bert_scaled_masked_softmax_8x16x512x512", softmax_fwd_bwd,
          scores)

    # ---- segmented one-pass LAMB at headline scale: the small
    # smoke config compiles tiny segments; the BENCH config runs
    # ~1.25M-element segments with ~10 MB of VMEM scratch — the
    # construct class that produced both round-3 Mosaic crashes
    if not on_cpu:
        from apex_tpu.multi_tensor.flat_buffer import segmented_space
        from apex_tpu.multi_tensor.segmented import (
            fused_lamb_segmented_update,
        )
        from apex_tpu.optimizers import FusedLAMB
        from bench import bert_large_shapes

        import dataclasses as _dc

        for label, okw, shp in (
            ("seg_lamb_41M_auto", {},
             bert_large_shapes(hidden=512, layers=8)),
            ("seg_lamb_335M_auto", {}, bert_large_shapes()),
            ("seg_lamb_335M_streamp_bf16u",
             {"seg_stash_p": False, "seg_allow_bf16_u": True,
              "seg_u_dtype": jnp.bfloat16}, bert_large_shapes()),
        ):
            if only and only not in label:
                continue
            tree = {f"p{i}": jax.ShapeDtypeStruct(s, jnp.float32)
                    for i, s in enumerate(shp)}
            zeros = jax.tree.map(
                lambda sd: jnp.zeros(sd.shape, sd.dtype), tree)
            opt = FusedLAMB(lr=1e-3, **okw)
            seg, stash, u_dt = opt._segment_config(zeros)
            sp, meta = segmented_space(zeros, seg_elems=seg)
            meta = _dc.replace(meta, stash_p=bool(stash),
                               u_dtype_name=jnp.dtype(u_dt).name)
            pbuf = jnp.zeros((sp.total,), jnp.float32)
            gbuf = jnp.full((sp.total,), 1e-3, jnp.float32)

            check(label,
                  lambda p_, g_, sp=sp, meta=meta:
                  fused_lamb_segmented_update(
                      p_, jnp.zeros_like(p_), jnp.zeros_like(p_), g_,
                      sp, meta, lr=1e-3, step=1, weight_decay=0.01,
                      use_nvlamb=True, max_grad_norm=0.0,
                      impl="pallas"),
                  pbuf, gbuf)
            del pbuf, gbuf, zeros

    # the full bert/gpt fwd-bwd jits — exact names, not substrings
    # (slow compiles; request explicitly with `tpu_bisect.py
    # bert_full` / `gpt_full`)
    if only == "bert_full":
        from apex_tpu.models.bert import (BertConfig, BertModel,
                                          bert_loss_fn)

        cfg = BertConfig.bert_large(attention_backend="flash",
                                    dtype=jnp.bfloat16)
        model = BertModel(cfg)
        tokens = jnp.asarray(rng.randint(0, 30000, (8, 512)), jnp.int32)
        amask = jnp.ones((8, 512), jnp.int32)
        lm_labels = jnp.asarray(rng.randint(0, 30000, (8, 512)),
                                jnp.int32)
        lmask = jnp.ones((8, 512), jnp.float32)
        nsp = jnp.zeros((8,), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), tokens, amask)

        def bert_step(p):
            lm, binary = model.apply(p, tokens, amask,
                                     deterministic=True)
            return bert_loss_fn(lm, binary, lm_labels, lmask, nsp)

        check("bert_full", lambda p: jax.grad(bert_step)(p), params)
    elif only == "gpt_full":

        from apex_tpu.models.gpt import (GPTConfig, GPTModel,
                                         gpt_loss_fn)

        gcfg = GPTConfig.gpt2_345m(attention_backend="flash")
        gmodel = GPTModel(gcfg)
        toks = jnp.asarray(rng.randint(0, 50000, (4, 1025)),
                           jnp.int32)
        gparams = gmodel.init(jax.random.PRNGKey(0), toks[:, :-1])

        def gpt_step(p):
            return gpt_loss_fn(gmodel.apply(p, toks[:, :-1]),
                               toks[:, 1:])

        check("gpt_full", lambda p: jax.grad(gpt_step)(p), gparams)


if __name__ == "__main__":
    main()
