"""The persistent XLA compilation cache, placed from outside.

Compiling is a large part of a cold run on the chip (the GPT-2 345M
train step and each serving program take 10-20 s apiece), so every
entry point — ``chip_smoke.py``, ``benchmark/run.py``, the examples,
the ``tools/tpu_*`` scripts — calls :func:`enable` first thing.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and
this module sets no other directory. Otherwise the cache lives at one
fixed path inside the checkout (:data:`DEFAULT_DIR`, listed in
``.gitignore``): never a temp name, a pid or a timestamp, because the
path is part of the cache's key and a directory that moves never hits.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the one place the cache lives when the environment names none
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_compilation_cache")


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR


__all__ = ["DEFAULT_DIR", "ENV_VAR", "enable"]
