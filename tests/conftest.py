"""Test harness config.

Mirrors the reference's "multi-process on one node, no cluster needed"
strategy (ref: apex/transformer/testing/distributed_test_base.py:30-103)
the TPU way: a simulated 8-device CPU mesh via
``--xla_force_host_platform_device_count`` (SURVEY.md §4 "TPU translation").
Must run before jax initializes its backend, hence module-level in conftest.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags = (flags + " --xla_force_host_platform_device_count=8").strip()
# The tier-1 budget is wall-clock-bound and the suite is dominated by
# XLA:CPU compile time (~1000 programs); the tests assert numerics and
# program structure, not generated-code quality, so skip the backend
# optimization pipeline. Callers who want optimized code (perf smokes)
# can pre-set the flag themselves.
if "xla_backend_optimization_level" not in flags:
    flags = (flags + " --xla_backend_optimization_level=0").strip()
os.environ["XLA_FLAGS"] = flags

import jax  # noqa: E402

# No persistent compilation cache under tier-1, whatever an entry point
# under test asks for (apex_tpu/compile_cache.py): a compile for a
# described TPU (tests/test_tpu_compile.py) is written to the cache but
# cannot be read back without a chip, and the next one warns.
jax.config.update("jax_enable_compilation_cache", False)

import gc  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# Keep the cyclic-GC young: the suite compiles thousands of programs, and
# the jaxpr/executable graphs the jit caches keep alive push the gen-2
# heap into the millions of objects — every full collection then scans
# all of them, and by mid-suite each test runs ~3x slower than it does
# standalone (the tier-1 budget is wall-clock-bound on 1-core CPU
# runners). Freeze the import graph out of collection now, and have the
# module-scope fixture below drop each module's compiled programs and
# re-freeze the survivors, so gen-2 scans stay proportional to one
# module's allocations rather than the whole session's.
gc.freeze()


@pytest.fixture(autouse=True, scope="module")
def _jax_cache_hygiene():
    yield
    jax.clear_caches()
    gc.collect()
    gc.freeze()


@pytest.fixture
def rng():
    return np.random.RandomState(42)


@pytest.fixture(autouse=True)
def _a_trace_directory_of_its_own(request, monkeypatch, tmp_path_factory):
    """A traced rehearsal of the benchmark empties ``<ROOT>/.bench_trace``
    and fills it again (``benchmark/common.py`` ``traced``). Four test
    files make such runs, and under several workers one emptied the
    directory another was about to read (``no .xplane.pb``). Each test
    of those files gets a root of its own for what ``run.py`` writes;
    the files it reads are found from ``BENCH_DIR`` and the manifest's
    path as before."""
    if request.module.__name__.startswith("test_benchmark"):
        from benchmark import run

        monkeypatch.setattr(run, "ROOT", str(tmp_path_factory.mktemp("run")))


@pytest.fixture(params=["xla", "interpret"])
def impl(request):
    """Every fused op runs both the XLA reference path and the Pallas
    kernel (interpreter mode on CPU), mirroring the reference's
    kernel-vs-reference test style (ref: tests/L0/run_amp/test_multi_tensor_scale.py)."""
    return request.param


@pytest.fixture
def rehearsal_manifest(tmp_path):
    """``(name, cell, real_cell) -> path``: a rehearsal manifest of
    ``benchmark/tests/rehearsal/`` whose toy ``cell`` also reports
    every per-layer metric that ``BENCHMARK.json`` lists for
    ``real_cell`` and the rehearsal manifest lacks: what came to the
    benchmark as data (a specification under
    ``benchmark/layer_metrics/``) after the rehearsal was written."""
    import json

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def make(name, cell, real_cell):
        rehearsal = os.path.join(root, "benchmark", "tests", "rehearsal")
        with open(os.path.join(rehearsal, name)) as f:
            manifest = json.load(f)
        for config in manifest["configs"]:    # found from the manifest's
            config["file"] = os.path.join(rehearsal, config["file"])
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            real = json.load(f)["per_layer"]
        have = {m["name"] for m in manifest["per_layer"]}
        manifest["per_layer"] += [
            dict(m, workloads=[cell]) for m in real
            if m["name"] not in have
            and real_cell in m.get("workloads", (real_cell,))]
        path = tmp_path / name
        path.write_text(json.dumps(manifest))
        return str(path)

    return make


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "l1: cross-product integration tier (ref tests/L1/cross_product)")
    config.addinivalue_line(
        "markers",
        "slow: long-running integration tests excluded from the tier-1 "
        "budget (-m 'not slow'); run with -m slow before release")
