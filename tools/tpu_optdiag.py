"""Fused-optimizer step breakdown on the real chip.

An earlier round's session notes had FusedLAMB at 4.3x optax (84 ms vs
19 ms at 335M params, ~160 GB/s effective; transcribed, not in the
ledger) — far from the <=1.1x north-star. This tool decomposes the
step so the fix lands where the time actually goes. Measurement phases are ordered to stage memory on a
16 GB chip (each drops its buffers before the next allocates) and each
is fault-isolated so one failure never loses the rest:

  1. chip identity + raw HBM streaming bandwidth (natural-feed copy)
  2. optax.lamb on the param tree, state threaded (the baseline)
  3. the FULL FusedLAMB.step as the bench runs it (pack + kernel +
     unpack + per-leaf probe), both impls
  4. kernel-only fused_lamb/adam on pre-flat buffers, both impls
     (full minus kernel = the plumbing the flat design pays)

    python tools/tpu_optdiag.py            # BERT-large-class shapes
    python tools/tpu_optdiag.py --small    # ~40M quick pass

One JSON line per measurement; all timing via the feed-threaded chained
loop (host round-trips never inside the sample; every measurement
has a REAL iteration-to-iteration data dependence, see tpu_smoke._time).
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from tpu_smoke import opt_feed  # noqa: E402
from tpu_longctx import _time_adaptive  # noqa: E402


_LINES = []


def rec(**kw):
    _LINES.append(kw)
    print(json.dumps(kw), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--small", action="store_true")
    args = ap.parse_args()

    from apex_tpu import compile_cache

    compile_cache.enable()
    import jax
    import jax.numpy as jnp
    import optax

    import apex_tpu.multi_tensor as mt
    from apex_tpu.optimizers import FusedLAMB
    from bench import bert_large_shapes

    d = jax.devices()[0]
    rec(what="device", kind=str(d.device_kind),
        platform=str(d.platform),
        backend=str(jax.default_backend()))

    # interpret-mode pallas at these sizes is not a measurement;
    # on CPU only the xla impl is timed (the chip times both)
    impls = (("xla",) if jax.default_backend() == "cpu"
             else ("pallas", "xla"))

    def make_trees():
        # regenerable (same seed) so later phases can rebuild the
        # trees after dropping them for chip-memory headroom
        r = np.random.RandomState(0)
        shapes = (bert_large_shapes(hidden=512, layers=8)
                  if args.small else bert_large_shapes())
        ps = {
            f"p{i}": jnp.asarray(
                r.randn(*s).astype(np.float32) * 0.02)
            for i, s in enumerate(shapes)
        }
        gs = {
            k: jnp.asarray(
                r.randn(*v.shape).astype(np.float32) * 1e-3)
            for k, v in ps.items()
        }
        return shapes, ps, gs

    # 1. raw streaming bandwidth: out-of-place scale of a 1 GiB
    # buffer, output fed back as next input (zero harness traffic)
    try:
        n_raw = 1 << 28   # 268M fp32 = 1 GiB
        buf = jnp.asarray(
            np.random.RandomState(1).randn(n_raw).astype(np.float32))
        t = _time_adaptive(lambda b: (b * 1.0000001,), buf,
                           feed=lambda out, carry: out)
        rec(what="raw_copy_scale", gib=1.0, ms=round(t * 1e3, 3),
            gb_per_sec=round(2 * n_raw * 4 / t / 1e9, 1))
        del buf
    except Exception as e:  # noqa: BLE001
        rec(what="raw_copy_scale",
            error=f"{type(e).__name__}: {str(e)[:120]}")

    shapes, params, grads = make_trees()
    space = mt.FlatSpace.create(params)
    n = int(space.total)
    gb = n * 4 / 1e9
    rec(what="workload", n_params=n, n_tensors=len(shapes),
        fp32_gb=round(gb, 3))

    # 2. optax.lamb on the tree, state threaded (the baseline,
    # measured with the same chained discipline as everything else)
    try:
        tx = optax.lamb(1e-3, weight_decay=0.01)
        ostate = tx.init(params)
        ps_leaves, ps_def = jax.tree.flatten((params, ostate))
        n_ps = len(ps_leaves)
        g_leaves, g_def = jax.tree.flatten(grads)

        def optax_step(*leaves):
            p, s = jax.tree.unflatten(ps_def, leaves[:n_ps])
            g = jax.tree.unflatten(g_def, leaves[n_ps:])
            upd, s2 = tx.update(g, s, p)
            p2 = optax.apply_updates(p, upd)
            probe = sum(jnp.sum(l) for l in jax.tree.leaves(p2))
            return (*jax.tree.leaves((p2, s2)), probe)

        t = _time_adaptive(
            optax_step, *ps_leaves, *g_leaves,
            feed=lambda out, carry: (*out[:n_ps], *carry[n_ps:]))
        rec(what="optax_lamb_tree", ms=round(t * 1e3, 3),
            gb_per_sec=round(10 * gb / t, 1))
        del ostate, ps_leaves
    except Exception as e:  # noqa: BLE001
        rec(what="optax_lamb_tree",
            error=f"{type(e).__name__}: {str(e)[:120]}")

    # 3. the FULL FusedLAMB.step exactly as bench.py's headline runs
    # it: pack(grad tree) + kernel + unpack + per-leaf probe fold.
    # Each impl's 3-buffer state (4 GB at BERT-large scale) is
    # dropped before the next allocates — two live states OOM the
    # 16 GB chip.
    for impl in impls:
        state0 = None
        try:
            opt = FusedLAMB(lr=1e-3, weight_decay=0.01,
                            max_grad_norm=0.0, use_nvlamb=True,
                            impl=impl)
            state0 = opt.init(params)

            def full_step(master, m_, v_, count, *gleaves,
                          opt=opt, state0=state0):
                gtree = dict(zip(sorted(grads), gleaves))
                st = state0._replace(
                    master=master,
                    slots={"m": m_, "v": v_}, count=count)
                new_params, st2 = opt.step(st, gtree)
                probe = sum(jnp.sum(l)
                            for l in jax.tree.leaves(new_params))
                return (st2.master, st2.slots["m"], st2.slots["v"],
                        st2.count, probe)

            t = _time_adaptive(
                full_step, state0.master, state0.slots["m"],
                state0.slots["v"], state0.count,
                *[grads[k] for k in sorted(grads)],
                feed=lambda out, carry: (*out[:4], *carry[4:]))
            rec(what="full_step_pack_kernel_unpack", impl=impl,
                ms=round(t * 1e3, 3))
        except Exception as e:  # noqa: BLE001
            rec(what="full_step_pack_kernel_unpack", impl=impl,
                error=f"{type(e).__name__}: {str(e)[:120]}")
        finally:
            del state0

    # 4. kernel-only updates on pre-flat buffers; the param/grad
    # trees are dropped first so the chained loop has headroom for
    # its in-flight outputs (carry + new state + update term)
    try:
        flat_g = space.pack(grads, dtype=jnp.float32)
        flat_p = space.pack(params, dtype=jnp.float32)
        m = jnp.zeros_like(flat_p)
        v = jnp.zeros_like(flat_p)
        del params, grads
    except Exception as e:  # noqa: BLE001
        rec(what="kernel_only_setup",
            error=f"{type(e).__name__}: {str(e)[:120]}")
        return

    for name, fn in (
        ("lamb", lambda p_, m_, v_, g_, impl: mt.fused_lamb_update(
            p_, m_, v_, g_, space, lr=1e-3, step=2, weight_decay=0.01,
            use_nvlamb=True, max_grad_norm=0.0, impl=impl)[:3]),
        ("adam", lambda p_, m_, v_, g_, impl: mt.fused_adam_update(
            p_, m_, v_, g_, lr=1e-3, step=2, weight_decay=0.01,
            impl=impl)[:3]),
    ):
        # traffic: lamb r(p,m,v,g)+w(u,m,v) stage1, r(p,u)+w(p)
        # stage2 = 10x n*4; adam r(p,m,v,g)+w(p,m,v) = 7x
        acc = 10 if name == "lamb" else 7
        for impl in impls:
            try:
                t = _time_adaptive(
                    lambda p_, m_, v_, g_, fn=fn, impl=impl:
                    fn(p_, m_, v_, g_, impl), flat_p, m, v, flat_g,
                    feed=opt_feed)
                rec(what=f"fused_{name}_update_flat", impl=impl,
                    ms=round(t * 1e3, 3),
                    gb_per_sec=round(acc * gb / t, 1))
            except Exception as e:  # noqa: BLE001
                rec(what=f"fused_{name}_update_flat", impl=impl,
                    error=f"{type(e).__name__}: {str(e)[:120]}")

    # 5. the segment-resident ONE-PASS LAMB (multi_tensor/
    # segmented.py) — the round-3 redesign that answers optax's
    # per-leaf fusion; never measured on chip before round 4. The
    # plain flat buffers are dropped first and the trees rebuilt
    # (different layout padding), keeping peak memory at one
    # workload set.
    del flat_p, flat_g, m, v
    from apex_tpu.multi_tensor.segmented import (
        fused_lamb_segmented_update,
    )

    for label, kw in (
        ("stash_p", {}),
        ("stream_p", {"seg_stash_p": False}),
        ("stream_p_bf16u", {"seg_stash_p": False,
                            "seg_allow_bf16_u": True,
                            "seg_u_dtype": jnp.bfloat16}),
    ):
        seg_p = None
        try:
            _, params, grads = make_trees()
            opt = FusedLAMB(lr=1e-3, weight_decay=0.01,
                            max_grad_norm=0.0, use_nvlamb=True, **kw)
            seg, stash, u_dt = opt._segment_config(params)
            from apex_tpu.multi_tensor.flat_buffer import (
                segmented_space,
            )

            seg_space, seg_meta = segmented_space(params,
                                                  seg_elems=seg)
            import dataclasses as _dc

            seg_meta = _dc.replace(
                seg_meta, stash_p=bool(stash),
                u_dtype_name=jnp.dtype(u_dt).name)
            seg_p = seg_space.pack(params, dtype=jnp.float32)
            seg_g = seg_space.pack(grads, dtype=jnp.float32)
            del params, grads
            sm = jnp.zeros_like(seg_p)
            sv = jnp.zeros_like(seg_p)
            seg_gb = int(seg_space.total) * 4 / 1e9
            covered = 1.0 - sum(
                pl for (_, _, pl) in seg_meta.large
            ) / max(int(seg_space.total), 1)
            acc = 7 if seg_meta.stash_p else 8

            seg_impl = ("xla" if jax.default_backend() == "cpu"
                        else "pallas")

            def seg_fn(p_, m_, v_, g_, seg_impl=seg_impl):
                return fused_lamb_segmented_update(
                    p_, m_, v_, g_, seg_space, seg_meta, lr=1e-3,
                    step=2, weight_decay=0.01, use_nvlamb=True,
                    max_grad_norm=0.0, impl=seg_impl)[:3]

            t = _time_adaptive(
                seg_fn, seg_p, sm, sv, seg_g,
                feed=lambda out, carry: (*out, carry[3]))
            rec(what="fused_lamb_segmented_onepass", config=label,
                seg_elems=int(seg_meta.seg_elems),
                stash_p=bool(seg_meta.stash_p),
                u_dtype=seg_meta.u_dtype_name,
                covered_frac=round(covered, 4),
                ms=round(t * 1e3, 3),
                gb_per_sec_at_small_acc=round(acc * seg_gb / t, 1))
            del sm, sv, seg_g
        except Exception as e:  # noqa: BLE001
            rec(what="fused_lamb_segmented_onepass", config=label,
                error=f"{type(e).__name__}: {str(e)[:200]}")
        finally:
            del seg_p

    if jax.default_backend() == "tpu":
        from apex_tpu.records import write_record

        path = write_record(
            "optdiag",
            {"small": bool(args.small), "lines": _LINES},
            backend="tpu")
        if path:
            print(f"# record: {path}", file=sys.stderr)


if __name__ == "__main__":
    main()
