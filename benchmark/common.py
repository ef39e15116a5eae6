"""What the drivers share: the seed's key, robust statistics, the
traced stretch of a window."""

from __future__ import annotations

import contextlib
import shutil
from typing import Sequence

import numpy as np


def seed_key(seed: int):
    """A jax PRNG key from any whole number (the driver's seeds pass
    2**31, more than an int32 holds)."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def iqm(values: Sequence[float]) -> float:
    """Interquartile mean: the mean of the middle half."""
    v = np.sort(np.asarray(values, float))
    cut = len(v) // 4
    return float(v[cut:len(v) - cut].mean())


def stat(values: Sequence[float], which: str) -> float:
    v = np.asarray(values, float)
    if which == "iqm":
        return iqm(v)
    if which == "median":
        return float(np.median(v))
    if which == "mean":
        return float(v.mean())
    if which.startswith("p"):
        return float(np.percentile(v, float(which[1:])))
    raise ValueError(f"unknown statistic {which!r}")


@contextlib.contextmanager
def traced(trace_dir: str):
    """Run the body under the profiler, inside one ``bench.traced``
    span: the window ``trace_reduce`` clips everything to. Python
    frames are left out; they bloat the trace and slow the host. Yields
    a dict whose ``overhead_s`` is, afterwards, the time starting and
    stopping the profiler took: no part of a measured window."""
    import time

    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    took = {}
    t_a = time.perf_counter()
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("bench.traced"):
            t_in = time.perf_counter()
            yield took
            t_out = time.perf_counter()
    finally:
        jax.profiler.stop_trace()
    took["overhead_s"] = (time.perf_counter() - t_a) - (t_out - t_in)


def span(name: str):
    """A host span on the trace's clock; free when nothing traces."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def int_tokens(rng: np.random.Generator, shape, vocab: int) -> np.ndarray:
    return rng.integers(0, vocab, shape, dtype=np.int32)


def zipf_tokens(rng: np.random.Generator, shape, vocab: int) -> np.ndarray:
    """Token ids with p(i) ~ 1 / (i + 1): text-like unigram statistics,
    so a training loss has something to fall to (uniform ids leave a
    model at ln(vocab) nothing to learn)."""
    u = rng.random(shape)
    return np.minimum(np.exp(u * np.log(vocab + 1.0)) - 1.0,
                      vocab - 1).astype(np.int32)


def gpt_config(config: dict):
    """The program's ``GPTConfig`` for a configuration file: the
    source's widths under the source's own keys, the padded vocabulary
    and the types from ``assumed``."""
    import jax.numpy as jnp

    from apex_tpu.models.gpt import GPTConfig

    assumed = config["assumed"]
    return GPTConfig(
        vocab_size=assumed["padded_vocab_size"],
        max_seq_len=config["n_positions"], hidden_size=config["n_embd"],
        num_layers=config["n_layer"], num_heads=config["n_head"],
        ffn_hidden_size=config["n_inner"], attention_backend="flash",
        dtype=jnp.dtype(assumed["dtype"]),
        param_dtype=jnp.dtype(assumed["param_dtype"]))


def init_params(cfg, seed: int):
    """The model's parameters, made on the device from the seed by one
    jitted call, in the type the configuration keeps them in."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.models.gpt import GPTModel

    return jax.jit(lambda key: GPTModel(cfg).init(
        key, jnp.zeros((1, 8), jnp.int32)))(seed_key(seed))
