"""Native host runtime: flat staging buffers + prefetching input pipeline.

The reference's host-side C++ runtime maps here (SURVEY.md §2.1/§2.8):

  - ``HostFlatSpace.flatten/unflatten`` — apex_C's tensor-list
    flatten/unflatten (ref: csrc/flatten_unflatten.cpp), backed by the
    C++ thread-pool library in apex_tpu/csrc/host_runtime.cpp. One
    aligned buffer per transfer instead of hundreds of small ones.
  - ``cast_f32_bf16 / cast_bf16_f32`` — parallel host casts for
    compressed staging/checkpoints (the host analog of the e5m2
    compressed-allgather option, ref distributed_fused_lamb.py:83-91).
  - ``PrefetchLoader`` — background-thread host->device pipeline (the
    TPU analog of the CUDA-stream data_prefetcher in
    ref examples/imagenet/main_amp.py:256-300): while the device runs
    step N, worker threads stage and ``jax.device_put`` batch N+1.

The C++ library is compiled on first use with g++ (kept under
``apex_tpu/_build``, which git ignores: a checkout never carries a
binary). Where it cannot be built or loaded, one warning says why and
every entry point runs its numpy substitute, so behavior is identical
either way; :func:`native_available` says which of the two is in use.
"""

from __future__ import annotations

import ctypes
import logging
import os
import queue
import subprocess
import threading
import time
from typing import Any, Callable, Iterable, Iterator, Optional, Sequence

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "..", "csrc", "host_runtime.cpp")

_lib = None
_lib_tried = False


def _build_dir() -> str:
    """Writable cache dir: APEX_TPU_BUILD_DIR env override, the package
    tree when writable, else ~/.cache/apex_tpu (read-only installs)."""
    env = os.environ.get("APEX_TPU_BUILD_DIR")
    if env:
        return env
    pkg = os.path.join(_HERE, "..", "_build")
    parent = os.path.dirname(pkg)
    if os.access(parent, os.W_OK):
        return pkg
    return os.path.join(
        os.path.expanduser("~"), ".cache", "apex_tpu", "_build")


def _load_library():
    """Compile (once) and dlopen the native library; None — after one
    warning that names the cause — where that fails."""
    global _lib, _lib_tried
    if _lib_tried:
        return _lib
    _lib_tried = True
    try:
        build_dir = _build_dir()
        lib_path = os.path.join(build_dir, "libapex_host_runtime.so")
        if not os.path.exists(lib_path) or (
            os.path.getmtime(lib_path) < os.path.getmtime(_SRC)
        ):
            os.makedirs(build_dir, exist_ok=True)
            # compile to a process-unique temp path, then atomically
            # rename — concurrent builders can't serve each other a
            # half-written ELF
            tmp = f"{lib_path}.{os.getpid()}.tmp"
            subprocess.run(
                ["g++", "-O3", "-std=c++17", "-shared", "-fPIC",
                 "-pthread", _SRC, "-o", tmp],
                check=True, capture_output=True)
            os.replace(tmp, lib_path)
        lib = ctypes.CDLL(lib_path)
        lib.apex_host_runtime_abi_version.restype = ctypes.c_int
        if lib.apex_host_runtime_abi_version() != 1:
            raise OSError(f"{lib_path} has another ABI version")
        lib.apex_flatten.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int64]
        lib.apex_unflatten.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int64]
        lib.apex_cast_f32_bf16.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64]
        lib.apex_cast_bf16_f32.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64]
        _lib = lib
    except (OSError, subprocess.CalledProcessError) as e:
        # g++'s own last line where it ran and failed, else the error
        said = (getattr(e, "stderr", None) or b"").decode(
            errors="replace").strip().splitlines()
        logging.getLogger("apex_tpu").warning(
            "native host runtime not available (%s: %s) — the numpy "
            "substitute runs instead", type(e).__name__,
            said[-1] if said else e)
        _lib = None
    return _lib


def native_available() -> bool:
    return _load_library() is not None


def _as_c_buffers(arrays: Sequence[np.ndarray]):
    ptrs = (ctypes.c_char_p * len(arrays))()
    for i, a in enumerate(arrays):
        ptrs[i] = ctypes.cast(a.ctypes.data, ctypes.c_char_p)
    return ptrs


class HostFlatSpace:
    """Static layout of N host arrays in one aligned byte buffer
    (the host mirror of apex_tpu.multi_tensor.FlatSpace; alignment in
    bytes, default 128 to match lane tiling on the device side)."""

    def __init__(self, shapes: Sequence[tuple], dtypes: Sequence[Any],
                 align: int = 128):
        self.shapes = [tuple(s) for s in shapes]
        self.dtypes = [np.dtype(d) for d in dtypes]
        self.align = align
        self.offsets, self.nbytes = [], []
        off = 0
        for s, d in zip(self.shapes, self.dtypes):
            n = int(np.prod(s, dtype=np.int64)) * d.itemsize if s else d.itemsize
            self.offsets.append(off)
            self.nbytes.append(n)
            off += ((n + align - 1) // align) * align
        self.total_bytes = off

    @classmethod
    def for_arrays(cls, arrays: Sequence[np.ndarray],
                   align: int = 128) -> "HostFlatSpace":
        return cls([a.shape for a in arrays], [a.dtype for a in arrays],
                   align)

    def _check(self, arrays):
        if len(arrays) != len(self.shapes):
            raise ValueError(
                f"expected {len(self.shapes)} arrays, got {len(arrays)}")
        for a, s, d in zip(arrays, self.shapes, self.dtypes):
            # ascontiguousarray promotes 0-d to (1,): size-1 arrays only
            # need the size to agree; everything else matches shape
            # exactly (equal-size wrong shapes would scramble data)
            ok = (tuple(a.shape) == s
                  or (a.size == 1 and int(np.prod(s, dtype=np.int64)) == 1))
            if not ok or a.dtype != d:
                raise ValueError(
                    f"array {a.shape}/{a.dtype} does not match layout "
                    f"{s}/{d}")

    def flatten(self, arrays: Sequence[np.ndarray],
                out: Optional[np.ndarray] = None) -> np.ndarray:
        """arrays -> one uint8 buffer (ref apex_C flatten)."""
        # note: ascontiguousarray promotes 0-d to 1-d, hence the
        # size-based (not shape-based) layout check
        arrays = [np.ascontiguousarray(a) for a in arrays]
        self._check(arrays)
        if out is None:
            out = np.zeros(self.total_bytes, np.uint8)
        elif (out.dtype != np.uint8 or out.size != self.total_bytes
              or not out.flags.c_contiguous):
            raise ValueError(
                f"out must be a contiguous uint8 buffer of "
                f"{self.total_bytes} bytes")
        lib = _load_library()
        if lib is not None:
            offs = (ctypes.c_int64 * len(arrays))(*self.offsets)
            szs = (ctypes.c_int64 * len(arrays))(*self.nbytes)
            lib.apex_flatten(
                ctypes.cast(out.ctypes.data, ctypes.c_char_p),
                _as_c_buffers(arrays), offs, szs, len(arrays))
        else:
            for a, off, n in zip(arrays, self.offsets, self.nbytes):
                out[off:off + n] = a.reshape(-1).view(np.uint8)
        return out

    def unflatten(self, buf: np.ndarray) -> list:
        """One uint8 buffer -> list of arrays (ref apex_C unflatten)."""
        buf = np.ascontiguousarray(buf).view(np.uint8).reshape(-1)
        if buf.size != self.total_bytes:
            raise ValueError(
                f"buffer has {buf.size} bytes, layout needs "
                f"{self.total_bytes}")
        outs = [np.empty(s, d) for s, d in zip(self.shapes, self.dtypes)]
        lib = _load_library()
        if lib is not None:
            offs = (ctypes.c_int64 * len(outs))(*self.offsets)
            szs = (ctypes.c_int64 * len(outs))(*self.nbytes)
            lib.apex_unflatten(
                ctypes.cast(buf.ctypes.data, ctypes.c_char_p),
                _as_c_buffers(outs), offs, szs, len(outs))
        else:
            for o, off, n in zip(outs, self.offsets, self.nbytes):
                o.reshape(-1).view(np.uint8)[:] = buf[off:off + n]
        return outs


def cast_f32_bf16(x: np.ndarray) -> np.ndarray:
    """fp32 -> bf16 with round-to-nearest-even."""
    import ml_dtypes  # a hard dependency of jax, always present

    x = np.ascontiguousarray(x, np.float32)
    lib = _load_library()
    if lib is None:
        return x.astype(ml_dtypes.bfloat16)
    out = np.empty(x.shape, np.uint16)
    lib.apex_cast_f32_bf16(x.ctypes.data, out.ctypes.data, x.size)
    return out.view(ml_dtypes.bfloat16)


def cast_bf16_f32(x: np.ndarray) -> np.ndarray:
    """bf16 (or its uint16 bit view) -> fp32, exact."""
    bits = np.ascontiguousarray(x).view(np.uint16)
    out = np.empty(bits.shape, np.float32)
    lib = _load_library()
    if lib is not None:
        lib.apex_cast_bf16_f32(bits.ctypes.data, out.ctypes.data, bits.size)
    else:
        out.view(np.uint32)[...] = bits.astype(np.uint32) << 16
    return out


class PrefetchLoader:
    """Background host->device pipeline (ref examples/imagenet
    main_amp.py data_prefetcher: CUDA-stream prefetch -> worker thread
    + async ``jax.device_put``).

    Wraps an iterable of numpy batches (pytrees ok). ``depth`` batches
    are staged ahead: while the device computes step N, the worker
    stages/transfers N+1..N+depth. Optional ``transform`` runs on the
    worker thread (host-side augmentation/cast).

    Transfer fault tolerance (apex_tpu/resilience): each
    ``jax.device_put`` is retried ``transfer_retries`` times with
    exponential backoff + jitter; a batch that still fails kills the
    worker, which is restarted (resuming from the SAME source iterator,
    the failed batch first) up to ``max_worker_restarts`` times; past
    that the loader **degrades to synchronous loading** — remaining
    batches are transformed and transferred inline on the consumer
    thread, with errors propagating undecorated (``degraded`` records
    that the pipeline fell back). Exceptions raised by the source
    iterable or ``transform`` are never retried: they propagate to the
    consumer unchanged, first time.

    Telemetry (apex_tpu/telemetry): the loader publishes
    ``prefetch_queue_depth`` / ``prefetch_batches`` /
    ``prefetch_device_put_retries`` / ``prefetch_worker_deaths`` /
    ``prefetch_degraded`` into the process metrics registry, and each
    consumer-side queue wait as a ``data_wait`` span when the global
    step timeline is enabled (docs/observability.md).
    """

    def __init__(self, batches: Iterable, depth: int = 2,
                 transform: Optional[Callable] = None, device=None,
                 transfer_retries: int = 3, max_worker_restarts: int = 2,
                 retry_base_delay: float = 0.05, join_timeout: float = 5.0):
        self._batches = batches
        self._depth = depth
        self._transform = transform
        self._device = device
        self._consumed = False
        self._transfer_retries = int(transfer_retries)
        self._max_worker_restarts = int(max_worker_restarts)
        self._retry_base_delay = float(retry_base_delay)
        self._join_timeout = float(join_timeout)
        self.degraded = False          # fell back to synchronous loading
        self.worker_deaths = 0

    def __iter__(self) -> Iterator:
        # eager check (a generator body would defer it to first next())
        if self._consumed:
            raise RuntimeError(
                "PrefetchLoader is single-pass: wrap a fresh iterable "
                "per epoch (two concurrent workers on one source would "
                "race and drop batches)")
        self._consumed = True
        return self._run()

    def _run(self) -> Iterator:
        import jax

        # lazy: resilience imports runtime (checkpoint payloads ride
        # HostFlatSpace), so the dependency must not be module-level
        from apex_tpu.resilience import faults
        from apex_tpu.resilience.retry import retry_call
        from apex_tpu.telemetry import metrics as _metrics
        from apex_tpu.telemetry import timeline as _timeline

        # bound once: the per-batch hot path pays dict hits only
        reg = _metrics.registry()
        m_depth = reg.gauge("prefetch_queue_depth",
                            "staged batches waiting in the prefetch queue")
        m_batches = reg.counter("prefetch_batches",
                                "batches delivered to the consumer")
        m_retries = reg.counter("prefetch_device_put_retries",
                                "device_put attempts that were retried")
        m_deaths = reg.counter("prefetch_worker_deaths",
                               "prefetch workers killed by exhausted "
                               "transfer retries")
        m_degraded = reg.gauge("prefetch_degraded",
                               "1 = loader fell back to synchronous "
                               "loading")

        src = iter(self._batches)
        q: "queue.Queue" = queue.Queue(maxsize=self._depth)
        stop = threading.Event()
        END = object()

        class _TransferFailure:
            """Worker-side transfer death notice (retries exhausted)."""

            def __init__(self, exc):
                self.exc = exc

        # the batch the dying worker had staged but not delivered: the
        # restarted worker (or the synchronous fallback) takes it first
        # so no source batch is ever dropped by a transfer failure
        pending = {"batch": None}

        def put(item) -> bool:
            """Enqueue, backing off so the worker notices a stopped
            consumer instead of blocking on a full queue forever."""
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def transfer(b):
            faults.check("device_put")
            stall = faults.data_stall_s()
            if stall:
                # goodput drill: a stalled input pipeline — the
                # consumer blocks in its data_wait span below, so the
                # injected seconds land in the ledger's data_wait
                # bucket
                time.sleep(stall)
            return jax.tree.map(
                lambda a: jax.device_put(a, self._device), b)

        def count_retry(attempt, exc, delay):  # noqa: ARG001
            m_retries.inc()

        def worker():
            try:
                while not stop.is_set():
                    if pending["batch"] is not None:
                        b, pending["batch"] = pending["batch"], None
                    else:
                        try:
                            b = next(src)
                        except StopIteration:
                            put(END)
                            return
                        if self._transform is not None:
                            b = self._transform(b)
                    pending["batch"] = b
                    try:
                        d = retry_call(
                            transfer, b,
                            retries=self._transfer_retries,
                            base_delay=self._retry_base_delay,
                            retry_on=(Exception,),
                            on_retry=count_retry,
                            site="device_put")
                    except Exception as e:  # noqa: BLE001 — death notice
                        put(_TransferFailure(e))
                        return
                    pending["batch"] = None
                    if not put(d):
                        return
            except BaseException as e:  # source/transform: propagate as-is
                put(e)

        def spawn():
            t = threading.Thread(target=worker, daemon=True)
            t.start()
            return t

        t = spawn()
        try:
            while True:
                # the blocking q.get() IS the host loop's data stall:
                # surface it as a data_wait span when anyone is looking
                t0 = time.perf_counter()
                item = q.get()
                _timeline.record_global_span(
                    "data_wait", t0, time.perf_counter() - t0)
                m_depth.set(q.qsize())
                if item is END:
                    break
                if isinstance(item, _TransferFailure):
                    t.join(timeout=self._join_timeout)
                    self.worker_deaths += 1
                    m_deaths.inc()
                    if self.worker_deaths <= self._max_worker_restarts:
                        t = spawn()
                        continue
                    # graceful degradation: no more background workers —
                    # finish the epoch synchronously (plain transfers,
                    # errors propagate; prefetch overlap is lost, data
                    # is not)
                    self.degraded = True
                    m_degraded.set(1.0)
                    if pending["batch"] is not None:
                        b, pending["batch"] = pending["batch"], None
                        m_batches.inc()
                        yield transfer(b)
                    for b in src:
                        if self._transform is not None:
                            b = self._transform(b)
                        m_batches.inc()
                        yield transfer(b)
                    break
                if isinstance(item, BaseException):
                    raise item
                m_batches.inc()
                yield item
        finally:
            # consumer stopped (exhausted, errored, or abandoned):
            # release the worker and its staged device batches. The
            # join is bounded — a worker wedged inside a dead
            # transport's device_put must not hang the consumer too
            # (it is a daemon thread; process exit stays clean).
            stop.set()
            try:
                while True:
                    q.get_nowait()
            except queue.Empty:
                pass
            t.join(timeout=self._join_timeout)


__all__ = [
    "HostFlatSpace",
    "PrefetchLoader",
    "cast_bf16_f32",
    "cast_f32_bf16",
    "native_available",
]
