"""Package build (ref: the reference's setup.py, 871 LoC of CUDA
extension wiring — setup.py:247-855).

The TPU build needs none of that: the compute kernels are Pallas
(compiled by XLA at trace time) and the only native artifact is the
host-runtime shared library, which apex_tpu.runtime compiles lazily
with g++ on first use and keeps under apex_tpu/_build/. ``--cpp_ext``
is accepted for reference-CLI parity and pre-builds that library
eagerly."""

import sys

from setuptools import find_packages, setup

if "--cpp_ext" in sys.argv:
    sys.argv.remove("--cpp_ext")
    sys.path.insert(0, ".")
    from apex_tpu.runtime import native_available

    if not native_available():
        raise RuntimeError("failed to build the host runtime (needs g++)")
    print("apex_tpu host runtime built")

setup(
    name="apex_tpu",
    version="0.1.0",
    description=(
        "TPU-native training acceleration: mixed precision, fused "
        "kernels, and a full mesh-parallelism stack (JAX/XLA/Pallas)"
    ),
    packages=find_packages(include=["apex_tpu", "apex_tpu.*"]),
    # ship the source, never a binary: the library is built on first
    # use (read-only installs compile into ~/.cache/apex_tpu,
    # runtime._build_dir)
    package_data={"apex_tpu": ["csrc/*.cpp"]},
    python_requires=">=3.10",
    install_requires=["jax", "flax", "numpy"],
    extras_require={"test": ["pytest", "optax", "orbax-checkpoint", "torch"]},
)
