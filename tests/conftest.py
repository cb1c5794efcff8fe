"""Test configuration — force a virtual 8-device CPU platform.

Mirrors the reference's strategy of testing multi-device semantics
without multi-device hardware (tests/python/unittest/test_model_parallel.py
runs group2ctx on two *cpu* contexts).  Here: all sharding/collective
tests run on 8 virtual CPU devices via XLA host platform flags, which
must be set before jax initializes.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"  # force: a chip host defaults to tpu
# tier-1 is hermetic: no persistent compile cache unless a test places
# one itself — neither an operator's JAX_COMPILATION_CACHE_DIR nor the
# in-checkout default (<repo>/.jax_cache), which 1,000+ CPU tests
# would otherwise fill (docs/faq/compile_cache.md "Placement")
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
os.environ.setdefault("MXNET_COMPILE_CACHE_DIR", "")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")
# full-precision matmuls for numeric checks (bench keeps the TPU bf16 default)
os.environ.setdefault("JAX_DEFAULT_MATMUL_PRECISION", "float32")

# some environments pre-import jax via pytest plugins before this conftest
# runs; the backend is still uninitialized then, so config.update applies.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "float32")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running tests (multi-process, convergence)")


@pytest.fixture(autouse=True)
def _seed_rng():
    """Reference: tests/python/unittest/common.py with_seed() — fixed,
    logged seeds so failures reproduce."""
    import mxnet_tpu as mx
    np.random.seed(0)
    mx.random.seed(0)
    yield
