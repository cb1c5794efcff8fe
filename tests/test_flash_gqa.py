"""Grouped-query attention in the flash kernels (interpret mode on the
CPU): K and V come in at their own number of heads, each query head
reads its key/value head through the index maps, and dK / dV leave at
the key/value heads, the group's sum taken in the dK/dV kernel's
float32 accumulator.  Nothing is repeated; at one query head a key/value
head every plan is the one the kernels ran before grouping existed."""
import hashlib
import itertools
import json
import math

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.parallel import attention

T, BLOCK, LENGTH, WINDOW = 128, 32, 4, 48

MASKS = {
    "causal": dict(causal=True),
    "window": dict(causal=True, window=WINDOW),
    "block_diffusion": dict(causal=False, block_diffusion=LENGTH),
}


def _mask(name):
    """The ``(T, T)`` visibility of a mask, from its definition."""
    r, c = np.arange(T)[:, None], np.arange(T)[None, :]
    if name == "block_diffusion":
        return attention.block_diffusion_mask(T, LENGTH)
    seen = r >= c
    if name == "window":
        seen &= r - c < WINDOW
    return jnp.asarray(seen)


def _reference(q, k, v, mask, group):
    """Dense float32 attention over K and V repeated to every query head
    of their group (folded batch-major: query row n reads row n //
    group): ``(o, lse)``."""
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    k, v = jnp.repeat(k, group, axis=0), jnp.repeat(v, group, axis=0)
    s = jnp.einsum("bqd,bkd->bqk", q, k) / math.sqrt(q.shape[-1])
    s = jnp.where(mask[None], s, -jnp.inf)
    lse = jax.nn.logsumexp(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", jnp.exp(s - lse[..., None]), v), lse


def _rel(a, b):
    b = np.asarray(b, np.float32)
    return float(np.abs(np.asarray(a, np.float32) - b).max()
                 / np.abs(b).max())


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("d,dv", [(128, 128), (192, 128)],
                         ids=["d128", "d192-dv128"])
@pytest.mark.parametrize("mask", sorted(MASKS))
@pytest.mark.parametrize("group", [2, 4, 8])
def test_grouped_flash_matches_the_repeated_heads(group, mask, d, dv,
                                                  dtype):
    """Output, ``lse``, dQ, dK and dV of the kernels at two key/value
    heads against the dense form over the heads repeated, at blocks of
    32 rows (four Q blocks a head: the dK/dV kernel's minor axis visits
    ``group`` x 4 of them), under the causal, window and block-diffusion
    masks; dK and dV come out at the key/value heads."""
    rng = np.random.RandomState(group)
    hkv = 2
    q = jnp.asarray(rng.randn(hkv * group, T, d), dtype)
    k = jnp.asarray(rng.randn(hkv, T, d), dtype)
    v = jnp.asarray(rng.randn(hkv, T, dv), dtype)
    cot = jnp.asarray(rng.randn(hkv * group, T, dv), jnp.float32)
    kw = MASKS[mask]
    args = (kw["causal"], None, BLOCK, BLOCK, kw.get("window"), False,
            kw.get("block_diffusion"))
    seen = _mask(mask)

    o, lse = pk._flash_fwd(q, k, v, *args[:5], args[6])
    ro, rlse = _reference(q, k, v, seen, group)
    grads = jax.grad(lambda *a: jnp.sum(
        pk.flash_attention(*a, *args).astype(jnp.float32) * cot),
        (0, 1, 2))(q, k, v)
    ref = jax.grad(lambda *a: jnp.sum(_reference(*a, seen, group)[0] * cot),
                   (0, 1, 2))(*(a.astype(jnp.float32) for a in (q, k, v)))

    assert o.shape == (hkv * group, T, dv) and o.dtype == q.dtype
    assert [g.shape for g in grads] == [q.shape, k.shape, v.shape]
    assert all(g.dtype == q.dtype for g in grads)
    out_tol, grad_tol = (1e-5, 1e-4) if dtype == "float32" else (1e-2, 3e-2)
    assert _rel(o, ro) < out_tol
    np.testing.assert_allclose(np.asarray(lse[:, :, 0]), np.asarray(rlse),
                               rtol=0, atol=1e-4 if dtype == "float32"
                               else 2e-2)
    for name, got, want in zip("qkv", grads, ref):
        assert _rel(got, want) < grad_tol, name


@pytest.mark.parametrize("mask", sorted(MASKS))
def test_grouped_heads_are_not_repeated(mask):
    """``local_attention`` on the flash path hands the kernels K and V at
    their own heads: the gradient's program holds no array of K's or V's
    repeated to the query heads, and dK / dV come out at ``Hkv`` heads,
    equal to the einsum form's over the repeat."""
    rng = np.random.RandomState(0)
    b, hq, hkv, d = 2, 8, 2, 64
    q = jnp.asarray(rng.randn(b, T, hq, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, T, hkv, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, T, hkv, d), jnp.float32)
    kw = MASKS[mask]

    def grads(impl):
        return jax.grad(lambda *a: jnp.sum(jnp.sin(attention.local_attention(
            *a, impl=impl, **kw))), (0, 1, 2))

    # the repeat is a broadcast to (B, T, Hkv, g, D) and its transpose a
    # sum over g: the einsum form's program has that array, flash's not
    repeated = "f32[%d,%d,%d,%d,%d]" % (b, T, hkv, hq // hkv, d)
    assert repeated in str(jax.make_jaxpr(grads("einsum"))(q, k, v))
    assert repeated not in str(jax.make_jaxpr(grads("flash"))(q, k, v))
    flash, einsum = grads("flash")(q, k, v), grads("einsum")(q, k, v)
    for got, want in zip(flash, einsum):
        assert got.shape == want.shape
        assert _rel(got, want) < 1e-4
    assert flash[1].shape == k.shape and flash[2].shape == v.shape


def test_uneven_groups_are_refused():
    """Query heads that are no whole multiple of the key/value heads."""
    q = jnp.zeros((6, T, 64), jnp.float32)
    k = jnp.zeros((4, T, 64), jnp.float32)
    with pytest.raises(ValueError, match="whole multiple"):
        pk.flash_attention(q, k, k, True, None, BLOCK, BLOCK)
    with pytest.raises(ValueError, match="whole multiple"):
        attention.local_attention(q.reshape(1, T, 6, 64),
                                  k.reshape(1, T, 4, 64), k.reshape(
                                      1, T, 4, 64), impl="flash")


def _canonical(plan):
    """A plan as data: its grid, every spec's block and index map at
    every grid point, its shapes, scratch, dtypes and score tiles."""
    grid = [int(g) for g in plan["grid"]]
    points = list(itertools.product(*[range(g) for g in grid]))

    def spec(s):
        return {"block": [None if b is None else int(b)
                          for b in s.block_shape],
                "index": [[int(i) for i in s.index_map(*p)]
                          for p in points]}
    return {"grid": grid,
            "in_specs": [spec(s) for s in plan["in_specs"]],
            "out_specs": [spec(s) for s in plan["out_specs"]],
            "in_shapes": [list(map(int, s)) for s in plan["in_shapes"]],
            "out_shapes": [list(map(int, s)) for s in plan["out_shapes"]],
            "scratch": [list(map(int, s)) for s in plan["scratch"]],
            "dtypes": [jnp.dtype(t).name for t in plan["dtypes"]],
            "tiles": [[list(map(int, s)), t] for s, t in plan["tiles"]]}


# (tq, tk, d, bq, bk, keywords) at 3 query heads, and the digest of each
# kernel's plan as the kernels ran before K and V could come in grouped
_MHA_CASES = [
    (512, 512, 64, 128, 128, dict(causal=False)),
    (512, 512, 64, 128, 128, dict(causal=True)),
    (256, 512, 64, 128, 64, dict(causal=True)),
    (512, 512, 64, 128, 64, dict(causal=True, window=200)),
    (512, 512, 64, 128, 128, dict(block_diffusion=4)),
    (512, 512, 96, 128, 128, dict(causal=True, dv=32,
                                  dtype=jnp.bfloat16)),
]
_MHA_DIGESTS = {
    "dkv": ["d648c80218efd85f", "0a2d2843320579e4", "2b51e73568f211ed",
            "97f36df4d943d60c", "1aa6af4599227018", "2fbdac7df73ec85a"],
    "dq": ["1ca357da968367dc", "da65b57719834fda", "deafc9b47d45336d",
           "a3847e53e50bb15c", "d2fb28725e688df0", "25bc0b4b5e168b7b"],
    "fwd": ["fa54acb71029dda5", "a6b2f80b2b010f17", "b4615200be724435",
            "e512df72e604375d", "735d3368e08921d0", "a7d01054ce34989f"],
}


@pytest.mark.parametrize("case", range(len(_MHA_CASES)))
@pytest.mark.parametrize("kernel", sorted(_MHA_DIGESTS))
def test_one_head_a_group_keeps_the_plans_it_had(kernel, case):
    """At ``group`` 1 (MHA: OPT, Ouro, JoyAI's latent heads) every plan
    is the one it was — grid, every index map at every grid point,
    shapes, scratch, dtypes and tiles — under each mask and at a value
    head narrower than the key's."""
    tq, tk, d, bq, bk, kw = _MHA_CASES[case]
    for plan in (pk._FLASH_PLANS[kernel](3, tq, tk, d, bq, bk, **kw),
                 pk._FLASH_PLANS[kernel](3, tq, tk, d, bq, bk, group=1,
                                         **kw)):
        digest = hashlib.sha256(json.dumps(
            _canonical(plan), sort_keys=True).encode()).hexdigest()[:16]
        assert digest == _MHA_DIGESTS[kernel][case]


def test_a_grouped_plan_visits_every_member_of_the_group():
    """dK/dV's grid at 4 query heads a key/value head and 4 Q blocks:
    (key/value heads, K blocks, 4 x 4); visit ``s`` of head b reads
    query head ``4 b + s // 4`` at the Q block ``s mod 4`` the causal
    schedule names, and K / V hold still along the minor axis."""
    plan = pk.flash_bwd_dkv_plan(8, 512, 512, 64, 128, 128, True, group=4)
    assert plan["grid"] == (2, 4, 16)
    assert plan["in_shapes"][1] == plan["out_shapes"][0] == (2, 512, 64)
    qmap, kmap = plan["in_specs"][0].index_map, plan["in_specs"][1].index_map
    assert [qmap(1, 2, s)[:2] for s in range(16)] == [
        (4 + m, max(i, 2)) for m in range(4) for i in range(4)]
    assert {kmap(1, 2, s) for s in range(16)} == {(1, 2, 0)}
    fwd = pk.flash_fwd_plan(8, 512, 512, 64, 128, 128, True, group=4)
    assert fwd["grid"] == (8, 4, 4)
    assert [fwd["in_specs"][1].index_map(b, 3, 0)[0]
            for b in range(8)] == [0, 0, 0, 0, 1, 1, 1, 1]


@pytest.mark.parametrize("group", [1, 4, 8])
def test_the_gauge_reads_the_group(group):
    """``mxnet_flash_kv_group{kernel}`` after a trace of the forward and
    the backward (nothing runs: eval_shape): the query heads a key/value
    head, for each of the three kernels, beside the block gauges."""
    from mxnet_tpu import telemetry
    q = jax.ShapeDtypeStruct((2 * group, 1024, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((2, 1024, 128), jnp.bfloat16)
    telemetry.enable()
    try:
        jax.eval_shape(jax.grad(lambda q, k, v: jnp.sum(pk.flash_attention(
            q, k, v, True).astype(jnp.float32)), (0, 1, 2)), q, kv, kv)
        gauge = telemetry.gauge("mxnet_flash_kv_group")
        for kernel in ("fwd", "dq", "dkv"):
            assert gauge.labels(kernel=kernel).value == group
        steps = telemetry.gauge("mxnet_flash_grid_steps")
        bq, bk = pk._flash_blocks(1024, 1024, 128, jnp.bfloat16, "dkv")
        assert steps.labels(kernel="dkv").value \
            == 2 * group * (1024 // bq) * (1024 // bk)
    finally:
        telemetry.disable()
