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


def _equations(jaxpr):
    """Every equation of a jaxpr, the bodies of its ``scan`` /
    ``checkpoint`` / ``pjit`` equations included (each body once)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


def kernel_calls(jaxpr):
    """``pallas_call`` equations of a jaxpr by kernel name."""
    import collections
    return collections.Counter(
        eqn.params["name"] for eqn in _equations(jaxpr)
        if eqn.primitive.name == "pallas_call")


def primitive_calls(jaxpr):
    """Equations of a jaxpr by primitive name."""
    import collections
    return collections.Counter(
        eqn.primitive.name for eqn in _equations(jaxpr))


@pytest.fixture
def check_flash_kept(monkeypatch):
    """The shared check of the LM blocks whose layers keep their flash
    results (``moe_lm``, ``latent_moe_lm``): with the flash path forced
    (interpret mode), the gradient of ``loss(params)`` over a block of
    ``layers`` attention layers holds ``layers`` forward kernels — the
    backward pass runs none again — and ``layers`` of each backward
    kernel, where a bare ``jax.checkpoint`` holds the forward twice; and
    loss and every gradient leaf are the bare checkpoint's bit for bit:
    the kept values are the ones the second run would make."""
    from mxnet_tpu.gluon.contrib import transformer
    from mxnet_tpu.parallel import attention
    monkeypatch.setattr(attention, "_flash_eligible", lambda *a: True)

    def check(loss, params, layers):
        def run():
            counts = kernel_calls(jax.make_jaxpr(jax.grad(loss))(params).jaxpr)
            return counts, jax.jit(jax.value_and_grad(loss))(params)
        kept, (value, grads) = run()
        monkeypatch.setattr(transformer, "_layer_keeps", lambda: None)
        bare, (bare_value, bare_grads) = run()
        assert kept["_flash_fwd_kernel"] == layers, kept
        assert bare["_flash_fwd_kernel"] == 2 * layers, bare
        for name in ("_flash_bwd_dq_kernel", "_flash_bwd_dkv_kernel"):
            assert kept[name] == bare[name] == layers, (name, kept, bare)
        assert np.array_equal(np.asarray(value), np.asarray(bare_value))
        for k in grads:
            assert np.array_equal(np.asarray(grads[k]),
                                  np.asarray(bare_grads[k])), k
    return check


@pytest.fixture
def check_route_kept(monkeypatch):
    """The shared check of the LM blocks whose layers keep their expert
    layer's routing (``moe_lm``, ``latent_moe_lm``): the gradient of
    ``loss(params)`` over a block of ``layers`` routed layers, each of
    ``tokens`` tokens taking ``top_k`` of the ``held`` experts' slots,
    holds ``layers`` top-k's and ``layers`` pairs of routing's sorts —
    the backward pass routes nothing again — where a bare
    ``jax.checkpoint`` holds twice that (and, in both, a pair of sorts a
    layer in the combine's transpose); what the backward pass is handed
    grows by the tables' bytes (``parallel.moe.ROUTE_KEPT``: the choice,
    ``src``, ``dst``, ``is_held``, ``tile_group``, ``used``, ``counts``,
    ``order``, ``rank``, ``runs``) and nothing else, less the ``bias``
    floats of a selection bias, which only the choice read; and loss and
    every gradient leaf are the bare checkpoint's bit for bit: the kept
    choice is the one the second run would make."""
    from mxnet_tpu.gluon.contrib import transformer
    from mxnet_tpu.ops.pallas_kernels import GROUPED_TILE_ROWS as tm

    def check(loss, params, layers, tokens, top_k, held, bias=0):
        def run():
            calls = primitive_calls(
                jax.make_jaxpr(jax.grad(loss))(params).jaxpr)
            handed = jax.tree_util.tree_leaves(jax.eval_shape(
                lambda p: jax.vjp(loss, p)[1], params))
            return (calls, sum(a.size * a.dtype.itemsize for a in handed),
                    jax.jit(jax.value_and_grad(loss))(params))
        kept, kept_bytes, (value, grads) = run()
        monkeypatch.setattr(transformer, "_layer_keeps", lambda: None)
        bare, bare_bytes, (bare_value, bare_grads) = run()
        # a routing pass sorts twice; the combine's transpose twice more
        assert (kept["top_k"], kept["sort"]) == (layers, 4 * layers), kept
        assert (bare["top_k"], bare["sort"]) == (2 * layers, 6 * layers), bare
        slots = tokens * top_k
        tiles = -(-slots // tm) + held
        tables = (4 * slots + 4 * tiles * tm + 4 * slots + slots
                  + 4 * tiles + 4 + 4 * tiles + 8 * slots + 12 * held)
        assert kept_bytes - bare_bytes == layers * (tables - 4 * bias)
        assert np.array_equal(np.asarray(value), np.asarray(bare_value))
        for k in grads:
            assert np.array_equal(np.asarray(grads[k]),
                                  np.asarray(bare_grads[k])), k
    return check
