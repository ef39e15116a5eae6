"""The rehearsals run on the CPU: set that before jax is imported, keep
the persistent compilation cache off, and put the repo on the path."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)
