"""Backend/implementation dispatch.

The reference dispatches CUDA vs ROCm at build time (ref: setup.py:160-175).
Here the choice is runtime: Pallas TPU kernels on TPU backends, pure-XLA
(jnp) reference paths elsewhere (CPU tests, simulated meshes). Every fused
op in this package has both paths and tests compare them. On a TPU a
kernel that does not compile raises: nothing downgrades to the XLA path
behind the caller's back.

Env override: ``APEX_TPU_IMPL`` = ``pallas`` | ``xla`` | ``interpret``
(``interpret`` runs the Pallas kernels in interpreter mode — used by the
kernel-parity test suite on CPU).
"""

import os
from functools import lru_cache

import jax

VALID_IMPLS = ("pallas", "xla", "interpret")


@lru_cache(maxsize=None)
def default_impl() -> str:
    """Resolve which implementation fused ops use by default."""
    env = os.environ.get("APEX_TPU_IMPL", "").strip().lower()
    if env:
        if env not in VALID_IMPLS:
            raise ValueError(
                f"APEX_TPU_IMPL={env!r} invalid; expected one of {VALID_IMPLS}"
            )
        return env
    return "pallas" if is_tpu() else "xla"


def is_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_impl(impl=None) -> str:
    """Resolve an op-level ``impl=`` kwarg against the global default."""
    if impl is None:
        return default_impl()
    if impl not in VALID_IMPLS:
        raise ValueError(f"impl={impl!r} invalid; expected one of {VALID_IMPLS}")
    return impl


def interpret_flag(impl: str) -> bool:
    """Whether a pallas_call built for ``impl`` should run interpreted."""
    return impl == "interpret"
