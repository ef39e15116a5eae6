"""Native host runtime tests: C++ flatten/unflatten vs numpy, bf16
casts vs ml_dtypes, prefetch pipeline ordering."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.runtime import (
    HostFlatSpace,
    PrefetchLoader,
    cast_bf16_f32,
    cast_f32_bf16,
    native_available,
)


def test_native_library_builds():
    """g++ is in the image; the native path must actually be exercised
    by this test run, not silently fall back."""
    assert native_available()


class TestHostFlatSpace:
    def _arrays(self, rng):
        return [rng.randn(17, 5).astype(np.float32),
                rng.randn(3).astype(np.float16),
                (rng.randn(2, 2, 2) * 100).astype(np.int32),
                rng.randn(1000, 33).astype(np.float32)]

    def test_roundtrip(self, rng):
        arrays = self._arrays(rng)
        space = HostFlatSpace.for_arrays(arrays)
        buf = space.flatten(arrays)
        assert buf.dtype == np.uint8 and buf.size == space.total_bytes
        back = space.unflatten(buf)
        for a, b in zip(arrays, back):
            assert a.dtype == b.dtype and a.shape == b.shape
            np.testing.assert_array_equal(a, b)

    def test_alignment(self, rng):
        space = HostFlatSpace([(3,), (5,)], [np.float32, np.float32],
                              align=128)
        assert space.offsets == [0, 128]
        assert space.total_bytes == 256

    def test_matches_numpy_fallback(self, rng, monkeypatch):
        arrays = self._arrays(rng)
        space = HostFlatSpace.for_arrays(arrays)
        native = space.flatten(arrays)
        import apex_tpu.runtime as rt
        monkeypatch.setattr(rt, "_lib", None)
        monkeypatch.setattr(rt, "_lib_tried", True)
        fallback = space.flatten(arrays)
        np.testing.assert_array_equal(native, fallback)
        for a, b in zip(space.unflatten(native), arrays):
            np.testing.assert_array_equal(a, b)

    def test_large_parallel_path(self, rng):
        """> 1 MiB total triggers the thread-pool branch."""
        arrays = [rng.randn(1 << 18).astype(np.float32) for _ in range(4)]
        space = HostFlatSpace.for_arrays(arrays)
        back = space.unflatten(space.flatten(arrays))
        for a, b in zip(arrays, back):
            np.testing.assert_array_equal(a, b)


class TestCasts:
    def test_bf16_roundtrip_exact(self, rng):
        import ml_dtypes
        x = rng.randn(4096).astype(np.float32)
        bf = cast_f32_bf16(x)
        ref = x.astype(ml_dtypes.bfloat16)
        np.testing.assert_array_equal(bf.view(np.uint16),
                                      ref.view(np.uint16))
        back = cast_bf16_f32(bf)
        np.testing.assert_array_equal(back, ref.astype(np.float32))

    def test_bf16_nan_inf(self):
        import ml_dtypes
        x = np.array([np.nan, np.inf, -np.inf, 0.0, -0.0], np.float32)
        bf = cast_f32_bf16(x)
        ref = x.astype(ml_dtypes.bfloat16)
        assert np.isnan(bf.astype(np.float32)[0])
        np.testing.assert_array_equal(bf.view(np.uint16)[1:],
                                      ref.view(np.uint16)[1:])

    def test_large_parallel_cast(self, rng):
        import ml_dtypes
        x = rng.randn(1 << 19).astype(np.float32)
        np.testing.assert_array_equal(
            cast_f32_bf16(x).view(np.uint16),
            x.astype(ml_dtypes.bfloat16).view(np.uint16))


class TestPrefetchLoader:
    def test_order_and_content(self, rng):
        batches = [{"x": np.full((4,), i, np.float32)} for i in range(10)]
        out = list(PrefetchLoader(iter(batches), depth=3))
        assert len(out) == 10
        for i, b in enumerate(out):
            np.testing.assert_array_equal(np.asarray(b["x"]), batches[i]["x"])

    def test_transform_runs_on_worker(self, rng):
        batches = [np.ones((2,), np.float32) * i for i in range(5)]
        out = list(PrefetchLoader(iter(batches), depth=2,
                                  transform=lambda b: b * 2))
        for i, b in enumerate(out):
            np.testing.assert_array_equal(np.asarray(b), batches[i] * 2)

    def test_worker_exception_propagates(self):
        def gen():
            yield np.zeros((1,), np.float32)
            raise ValueError("boom")

        it = iter(PrefetchLoader(gen(), depth=2))
        next(it)
        with pytest.raises(ValueError, match="boom"):
            list(it)

    def test_abandoned_consumer_releases_worker(self):
        def gen():
            while True:
                yield np.zeros((1,), np.float32)

        import threading
        before = threading.active_count()
        it = iter(PrefetchLoader(gen(), depth=2))
        next(it)
        it.close()  # abandon mid-stream -> finally stops the worker
        import time
        time.sleep(0.5)
        assert threading.active_count() <= before + 1

    def test_single_pass_guard(self):
        loader = PrefetchLoader(iter([np.zeros((1,), np.float32)]))
        list(loader)
        with pytest.raises(RuntimeError, match="single-pass"):
            iter(loader)

    def test_flatten_validates_layout(self, rng):
        space = HostFlatSpace([(4,)], [np.float32])
        with pytest.raises(ValueError):
            space.flatten([rng.randn(5).astype(np.float32)])
        with pytest.raises(ValueError):
            space.unflatten(np.zeros(7, np.uint8))

    def test_scalar_leaf_fallback(self, monkeypatch):
        import apex_tpu.runtime as rt
        monkeypatch.setattr(rt, "_lib", None)
        monkeypatch.setattr(rt, "_lib_tried", True)
        space = HostFlatSpace([()], [np.float32])
        buf = space.flatten([np.float32(3.5)])
        assert float(space.unflatten(buf)[0]) == 3.5

    def test_overlap(self):
        """The loader stages ahead: after consuming item 0, at least
        one further batch is already produced without being requested."""
        import time
        produced = []

        def gen():
            for i in range(4):
                produced.append(i)
                yield np.zeros((1,), np.float32)

        it = iter(PrefetchLoader(gen(), depth=2))
        next(it)
        time.sleep(0.5)
        assert len(produced) >= 2
        list(it)


class TestProfiler:
    """SURVEY §5 tracing hooks (ref nvtx ranges / --prof windows)."""

    def test_named_range_and_annotate(self):
        from apex_tpu import profiler

        @profiler.annotate("my_op")
        def f(x):
            with profiler.range("inner"):
                return x * 2

        out = jax.jit(f)(jnp.ones((4,)))
        np.testing.assert_array_equal(np.asarray(out), 2 * np.ones(4))

    def test_trace_capture(self, tmp_path):
        from apex_tpu import profiler

        with profiler.trace(str(tmp_path), enabled=True):
            jax.block_until_ready(jnp.ones((8, 8)) @ jnp.ones((8, 8)))
        # a TensorBoard-loadable trace directory was produced
        assert any(tmp_path.rglob("*.pb")) or any(tmp_path.rglob("*.json.gz"))

    def test_trace_disabled_noop(self, tmp_path):
        from apex_tpu import profiler

        with profiler.trace(str(tmp_path / "off"), enabled=False):
            pass
        assert not (tmp_path / "off").exists()

    def test_ddp_prof_flag(self, rng):
        from apex_tpu.parallel import DistributedDataParallel
        from apex_tpu.transformer import parallel_state as ps

        ps.destroy_model_parallel()
        mesh = ps.initialize_model_parallel()
        try:
            import functools

            from jax import shard_map
            from jax.sharding import PartitionSpec as P

            ddp = DistributedDataParallel(prof=True)
            x = jnp.asarray(rng.randn(8, 4).astype(np.float32))

            run = functools.partial(
                shard_map, mesh=mesh,
                in_specs=(P(ps.DATA_AXIS, None),), out_specs=P(),
                check_vma=False)
            out = jax.jit(run(lambda g: ddp.allreduce_grads(g)))(x)
            np.testing.assert_allclose(
                np.asarray(out), np.mean(np.asarray(x).reshape(8, -1, 4), 0),
                rtol=1e-6)
        finally:
            ps.destroy_model_parallel()


class TestDefaultImpl:
    """``_backend.default_impl``: Pallas on a TPU, XLA elsewhere, the
    env override first. Nothing probes, nothing downgrades — a kernel
    that does not compile on the chip raises where it is called."""

    def test_default_follows_the_backend(self, monkeypatch):
        from apex_tpu import _backend

        monkeypatch.delenv("APEX_TPU_IMPL", raising=False)
        _backend.default_impl.cache_clear()
        try:
            assert _backend.is_tpu() is False          # tier-1 runs on CPU
            assert _backend.default_impl() == "xla"
            _backend.default_impl.cache_clear()
            monkeypatch.setattr(_backend, "is_tpu", lambda: True)
            assert _backend.default_impl() == "pallas"
        finally:
            _backend.default_impl.cache_clear()

    def test_default_impl_env_override_wins(self, monkeypatch):
        from apex_tpu import _backend

        def boom():
            raise AssertionError("backend must not be asked under the "
                                 "env override")

        monkeypatch.setenv("APEX_TPU_IMPL", "xla")
        monkeypatch.setattr(_backend, "is_tpu", boom)
        _backend.default_impl.cache_clear()
        try:
            assert _backend.default_impl() == "xla"
        finally:
            _backend.default_impl.cache_clear()
