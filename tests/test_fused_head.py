"""The fused head's Pallas pair (``pallas_kernels.head_cross_entropy``:
``_head_ce_fwd_kernel`` / ``_head_ce_bwd_kernel``) in interpret mode
against the XLA rule it replaces on the TPU, ``jax.checkpoint`` of the
plain logits — the terms and both gradients.

On the CPU ``F.contrib.linear_cross_entropy`` takes the XLA rule; a case
reaches the kernels by pointing ``contrib._head_kernel_eligible`` at
``pallas_kernels.head_ce_ok`` alone (the kernels then run in interpret
mode).  What the chip's compiler makes of them is in
``tests/test_flash_tpu_compile.py``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops import contrib
from mxnet_tpu.ops import pallas_kernels as pk

UNITS = 128
ROWS = (2, 16)          # (batch, positions): 32 rows


def _case(v, dtype, kind, seed=0):
    """``(x, table, labels, cotangent)``; labels at column 0 and V - 1 in
    the first two rows, a cotangent of its own a row (exit weights give
    one).  ``extreme``: four rows whose logits reach about +-60."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal(ROWS + (UNITS,)).astype("f")
    if kind == "extreme":
        x[0, 2:6] *= 20.0
    w = (rng.standard_normal((v, UNITS)) / np.sqrt(UNITS)).astype("f")
    y = rng.integers(0, v, ROWS).astype("int32")
    y[0, 0], y[0, 1] = 0, v - 1
    g = rng.uniform(0.1, 2.0, ROWS).astype("f")
    return (jnp.asarray(x, dtype), jnp.asarray(w, dtype), jnp.asarray(y),
            jnp.asarray(g))


def _objective(kind, y, g):
    """The loss of ``(x, table)``: the head over ``x`` (``tied``: over
    ``x`` plus the table's rows at the labels, so the table is read by a
    look-up and as the head; ``scaled``: the head's weight under
    ``scale_gradient``, as a prediction module's term passes it)."""
    def loss(x, w):
        if kind == "tied":
            x = x + jnp.take(w, y, axis=0)
        if kind == "scaled":
            w = contrib._scale_gradient(w, scale=0.3)
        ce = contrib._linear_cross_entropy(x, w, y)
        return jnp.sum(ce * g), ce
    return jax.value_and_grad(loss, (0, 1), has_aux=True)


@pytest.mark.parametrize("v,dtype,kind", [
    (1000, "float32", "plain"),         # ragged: 512 + 488 columns
    (1000, "bfloat16", "plain"),
    (2064, "float32", "plain"),         # 16 x 129: 4 x 512 + 16
    (2064, "bfloat16", "plain"),
    (100, "float32", "plain"),          # one block, narrower than a lane tile
    (100, "bfloat16", "plain"),
    (1000, "float32", "extreme"),
    (2064, "bfloat16", "extreme"),
    (1000, "float32", "tied"),
    (2064, "bfloat16", "tied"),
    (1000, "float32", "scaled"),
    (100, "bfloat16", "scaled"),
])
def test_head_kernels_match_the_xla_rule(monkeypatch, v, dtype, kind):
    x, w, y, g = _case(v, jnp.dtype(dtype), kind)
    step = jax.jit(_objective(kind, y, g))
    (_, want_ce), want = step(x, w)
    monkeypatch.setattr(contrib, "_head_kernel_eligible", pk.head_ce_ok)
    (_, got_ce), got = jax.jit(_objective(kind, y, g))(x, w)
    jaxpr = str(jax.make_jaxpr(_objective(kind, y, g))(x, w))
    assert "_head_ce_fwd_kernel" in jaxpr and "_head_ce_bwd_kernel" in jaxpr
    # float32: the summation order alone; bfloat16: the gradients are
    # rounded to it (twice under scale_gradient), so two units in the
    # last place of the largest entry
    tol = 2e-5 if dtype == "float32" else 2 ** -6
    assert got_ce.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got_ce), np.asarray(want_ce),
                               rtol=2e-5, atol=2e-5)
    for name, a, b in zip(("dx", "dW"), got, want):
        assert a.dtype == b.dtype, name
        a, b = (np.asarray(t, np.float32) for t in (a, b))
        scale = np.abs(b).max()
        assert scale > 0, name
        assert np.abs(a - b).max() <= tol * scale, (name, np.abs(a - b).max(),
                                                    scale)


def test_the_counter_says_which_path_a_head_took(monkeypatch):
    """``mxnet_linear_ce_calls_total{path}``: the kernel pair where the
    gate lets it, the XLA rule where it does not and for a weighted
    call, one a traced call."""
    from mxnet_tpu import telemetry
    x, w, y, _g = _case(100, jnp.float32, "plain")
    pw = jnp.ones(y.shape, jnp.float32)
    counter = lambda path: telemetry.counter(
        "mxnet_linear_ce_calls_total").labels(path=path).value
    telemetry.enable()
    try:
        before = {p: counter(p) for p in ("kernel", "xla")}
        contrib._linear_cross_entropy(x, w, y)                     # CPU
        monkeypatch.setattr(contrib, "_head_kernel_eligible", pk.head_ce_ok)
        contrib._linear_cross_entropy(x, w, y)
        contrib._linear_cross_entropy(x, w, y, pw)
        # rows the kernels cannot tile whole go to the XLA rule
        contrib._linear_cross_entropy(x[0, :5], w, y[0, :5])
        after = {p: counter(p) for p in ("kernel", "xla")}
    finally:
        telemetry.disable()
    assert after["kernel"] - before["kernel"] == 1
    assert after["xla"] - before["xla"] == 3
