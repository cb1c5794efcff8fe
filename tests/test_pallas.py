"""Pallas kernel numerics (interpret mode on the CPU mesh; the same
kernel code compiles natively on TPU).

Reference analogue: the fused-kernel coverage of tests/cpp/operator/
(batchnorm_test.cc, op perf harness) — VERDICT round-1 item 3.
"""
import math

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from mxnet_tpu.ops import pallas_kernels as pk


def _ref_attn(q, k, v, causal):
    """Dense float32 attention, the causal mask top-left aligned as the
    kernels': query row r sees key c iff r >= c."""
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    s = jnp.einsum("bqd,bkd->bqk", q, k) / math.sqrt(q.shape[-1])
    if causal:
        mask = jnp.tril(jnp.ones((q.shape[1], k.shape[1]), bool))
        s = jnp.where(mask[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v)


def _qkv(seed, bh, tq, tk, d, dtype):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(bh, tq, d), dtype),
            jnp.asarray(rng.randn(bh, tk, d), dtype),
            jnp.asarray(rng.randn(bh, tk, d), dtype))


def _rel(a, b):
    b = np.asarray(b, np.float32)
    return float(np.abs(np.asarray(a, np.float32) - b).max()
                 / np.abs(b).max())


# (Tq, Tk, D, dtype, block_q, block_k): explicit small blocks, and the
# blocks picked from the shape (None) — a T of several picked blocks
# (2048 = 2..4 blocks a side), one that fits a single block, Tq != Tk
_FLASH_CASES = [
    (64, 64, 16, "float32", 16, 16),
    (48, 48, 8, "float32", 16, 8),
    (2048, 2048, 64, "bfloat16", None, None),
    (2048, 2048, 128, "float32", None, None),
    (256, 256, 64, "float32", None, None),
    (256, 256, 128, "bfloat16", None, None),
    (128, 384, 64, "float32", None, None),
    (384, 128, 64, "bfloat16", None, None),
]
_FLASH_IDS = ["%dx%d-d%d-%s-%s" % (tq, tk, d, dt, "picked" if bq is None
                                   else "b%dx%d" % (bq, bk))
              for tq, tk, d, dt, bq, bk in _FLASH_CASES]


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("Tq,Tk,D,dtype,bq,bk", _FLASH_CASES,
                         ids=_FLASH_IDS)
def test_flash_attention_forward(causal, Tq, Tk, D, dtype, bq, bk):
    q, k, v = _qkv(0, 2 if Tq >= 2048 else 3, Tq, Tk, D, dtype)
    o = pk.flash_attention(q, k, v, causal, None, bq, bk)
    assert o.dtype == q.dtype
    r = _ref_attn(q, k, v, causal)
    if dtype == "float32":
        assert float(jnp.abs(o - r).max()) < 1e-5
    else:
        assert _rel(o, r) < 1e-2


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("Tq,Tk,D,dtype,bq,bk",
                         [(32, 32, 8, "float32", 8, 8)] + _FLASH_CASES[2:],
                         ids=["32x32-d8-float32-b8x8"] + _FLASH_IDS[2:])
def test_flash_attention_grads(causal, Tq, Tk, D, dtype, bq, bk):
    """dq, dk and dv against the dense reference's, at explicit blocks
    and at the blocks each backward kernel picks from the shape."""
    q, k, v = _qkv(1, 2, Tq, Tk, D, dtype)

    def loss_flash(q, k, v):
        o = pk.flash_attention(q, k, v, causal, None, bq, bk)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_ref_attn(q, k, v, causal) ** 2)

    gf = jax.grad(loss_flash, (0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, (0, 1, 2))(
        *(a.astype(jnp.float32) for a in (q, k, v)))
    for a, b in zip(gf, gr):
        assert a.dtype == q.dtype
        if dtype == "float32":
            assert _rel(a, b) < 1e-4
        else:
            assert _rel(a, b) < 3e-2


def test_flash_attention_numerically_stable():
    """Large logits: online softmax must not overflow."""
    T, D = 16, 8
    q = jnp.full((1, T, D), 30.0)
    k = jnp.full((1, T, D), 30.0)
    v = jnp.ones((1, T, D))
    o = pk.flash_attention(q, k, v, False, None, 8, 8)
    assert np.isfinite(np.asarray(o)).all()
    assert np.allclose(np.asarray(o), 1.0, atol=1e-5)


def test_fused_scale_bias_relu():
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(128, 24).astype(np.float32))
    s = jnp.asarray(rng.rand(24).astype(np.float32) + 0.5)
    b = jnp.asarray(rng.randn(24).astype(np.float32))
    y = pk.fused_scale_bias_relu(x, s, b, relu=True)
    assert float(jnp.abs(y - jnp.maximum(x * s + b, 0)).max()) < 1e-6
    y2 = pk.fused_scale_bias_relu(x, s, b, relu=False)
    assert float(jnp.abs(y2 - (x * s + b)).max()) < 1e-6


def test_contrib_fused_bn_relu_op():
    from mxnet_tpu import nd
    rng = np.random.RandomState(3)
    x = rng.randn(2, 6, 5, 5).astype(np.float32)
    gamma = rng.rand(6).astype(np.float32) + 0.5
    beta = rng.randn(6).astype(np.float32)
    mean = rng.randn(6).astype(np.float32) * 0.1
    var = rng.rand(6).astype(np.float32) + 0.5
    out = nd.contrib.fused_bn_relu(
        nd.array(x), nd.array(gamma), nd.array(beta), nd.array(mean),
        nd.array(var), eps=1e-5).asnumpy()
    scale = gamma / np.sqrt(var + 1e-5)
    ref = np.maximum(x * scale[None, :, None, None]
                     + (beta - mean * scale)[None, :, None, None], 0)
    assert np.abs(out - ref).max() < 1e-5


def test_local_attention_flash_impl_matches_einsum():
    """The integration point ulysses uses: impl='flash' (interpret on
    CPU) must match the einsum path."""
    from mxnet_tpu.parallel import attention as att
    rng = np.random.RandomState(4)
    q = jnp.asarray(rng.randn(2, 32, 4, 8).astype(np.float32))
    k = jnp.asarray(rng.randn(2, 32, 4, 8).astype(np.float32))
    v = jnp.asarray(rng.randn(2, 32, 4, 8).astype(np.float32))
    for causal in (False, True):
        a = att.local_attention(q, k, v, causal=causal, impl="flash")
        b = att.local_attention(q, k, v, causal=causal, impl="einsum")
        assert float(jnp.abs(a - b).max()) < 1e-5


# ---------------------------------------------------------------------------
# One-sweep fused optimizer: bit parity vs the per-array tree_map path
# ---------------------------------------------------------------------------
def _buckets(rng, sizes):
    """Flat fp32 'buckets' with awkward sizes (sub-lane, odd, padded)."""
    return {"b%d" % i: jnp.asarray(rng.randn(n).astype(np.float32))
            for i, n in enumerate(sizes)}


def _drive(opt, params, grad_stream, state, knob, monkeypatch):
    """N apply() steps, fused sweep on/off, both JITTED (the trainer's
    context — bit parity is a jit-vs-jit claim; eager XLA groups
    differently)."""
    monkeypatch.setenv("MXNET_PALLAS_FUSED_OPT", knob)
    step = jax.jit(lambda p, g, s: opt.apply(p, g, s, flat=True))
    p = dict(params)
    for g in grad_stream:
        p, state = step(p, g, state)
    return p, state


@pytest.mark.parametrize("momentum,clip", [(0.0, None), (0.9, None),
                                           (0.9, 0.05)])
def test_fused_sgd_sweep_bitwise_vs_treemap(momentum, clip, monkeypatch):
    """ACCEPTANCE: the fused SGD(+momentum)(+clip) sweep is EXACTLY the
    per-array tree_map path after N steps — params and slots, bit for
    bit, on buckets smaller than a lane, odd-sized, and multi-tile."""
    from mxnet_tpu.parallel.optimizer import PureSGD
    rng = np.random.RandomState(0)
    params = _buckets(rng, [48, 1000, 4096])
    grads = [_buckets(rng, [48, 1000, 4096]) for _ in range(4)]
    opt = PureSGD(0.1, momentum=momentum, wd=0.01, clip_gradient=clip)
    pf, sf = _drive(opt, params, grads, opt.init(params), "1", monkeypatch)
    pu, su = _drive(opt, params, grads, opt.init(params), "0", monkeypatch)
    for k in params:
        assert np.array_equal(np.asarray(pf[k]), np.asarray(pu[k])), k
    for a, b in zip(jax.tree_util.tree_leaves(sf),
                    jax.tree_util.tree_leaves(su)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_fused_adam_sweep_bitwise_vs_treemap(monkeypatch):
    from mxnet_tpu.parallel.optimizer import PureAdam
    rng = np.random.RandomState(1)
    params = _buckets(rng, [130, 2048])
    grads = [_buckets(rng, [130, 2048]) for _ in range(5)]
    opt = PureAdam(1e-3, wd=0.01)
    pf, sf = _drive(opt, params, grads, opt.init(params), "1", monkeypatch)
    pu, su = _drive(opt, params, grads, opt.init(params), "0", monkeypatch)
    for k in params:
        assert np.array_equal(np.asarray(pf[k]), np.asarray(pu[k])), k
    for a, b in zip(jax.tree_util.tree_leaves(sf),
                    jax.tree_util.tree_leaves(su)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_fused_sweep_padded_tail_stays_zero():
    """Bucket padding must not perturb real params: a zero tail (the
    mesh-divisibility pad of parallel/collectives.py) stays EXACTLY
    zero through both kernels, and the real prefix matches the
    unpadded sweep bit for bit."""
    rng = np.random.RandomState(2)
    n, pad = 100, 28
    w = rng.randn(n).astype(np.float32)
    g = rng.randn(n).astype(np.float32)
    m = rng.randn(n).astype(np.float32)
    z = np.zeros(pad, np.float32)
    wp, gp, mp = (jnp.asarray(np.concatenate([a, z]))
                  for a in (w, g, m))
    nw_p, nm_p = pk.fused_sgd_momentum(wp, gp, mp, lr=0.1, momentum=0.9,
                                       wd=0.01)
    assert np.all(np.asarray(nw_p[n:]) == 0)
    assert np.all(np.asarray(nm_p[n:]) == 0)
    nw, nm = pk.fused_sgd_momentum(jnp.asarray(w), jnp.asarray(g),
                                   jnp.asarray(m), lr=0.1, momentum=0.9,
                                   wd=0.01)
    assert np.array_equal(np.asarray(nw_p[:n]), np.asarray(nw))
    va = jnp.asarray(np.abs(rng.randn(n + pad)).astype(np.float32)
                     * np.concatenate([np.ones(n), z]).astype(np.float32))
    aw, am, av = pk.fused_adam(wp, gp, mp * 0, va, lr_eff=0.01)
    assert np.all(np.asarray(aw[n:]) == 0)
    assert np.all(np.asarray(am[n:]) == 0)
    assert np.all(np.asarray(av[n:]) == 0)


def test_fused_sweep_bitwise_under_zero_shardings(monkeypatch):
    """The ZeRO layouts: flat buckets placed replicated (the zero=1
    all-gathered form) AND 1/mesh-sharded (zero=2 shards) over the
    8-device mesh — the sweep stays bit-identical to tree_map in both
    placements (zero=0 never hands the optimizer flat views, so the
    fused path is exercised exactly where the trainer uses it)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from mxnet_tpu.parallel.mesh import make_mesh
    from mxnet_tpu.parallel.optimizer import PureSGD
    mesh = make_mesh(dp=8)
    rng = np.random.RandomState(3)
    for spec in (P(tuple(mesh.axis_names)), P()):
        ns = NamedSharding(mesh, spec)
        place = lambda t: jax.tree_util.tree_map(
            lambda a: jax.device_put(a, ns), t)
        params = place(_buckets(rng, [1024, 512]))
        grads = [place(_buckets(rng, [1024, 512])) for _ in range(3)]
        opt = PureSGD(0.1, momentum=0.9, wd=0.01)
        state = opt.init(params, {k: ns for k in params})
        pf, sf = _drive(opt, params, grads, state, "1", monkeypatch)
        state = opt.init(params, {k: ns for k in params})
        pu, su = _drive(opt, params, grads, state, "0", monkeypatch)
        for k in params:
            assert np.array_equal(np.asarray(pf[k]), np.asarray(pu[k])), \
                (spec, k)
        for a, b in zip(jax.tree_util.tree_leaves(sf),
                        jax.tree_util.tree_leaves(su)):
            assert np.array_equal(np.asarray(a), np.asarray(b))


# -- native buckets (PR 30): one leaf swept in its own (rows, C) layout ------
def _native_buckets(rng, shapes):
    return {"b%d" % i: jnp.asarray(rng.randn(*s).astype(np.float32))
            for i, s in enumerate(shapes)}


@pytest.mark.parametrize("cols,block_elems,block_rows", [
    (2048, 0, 64), (8192, 0, 16), (128, 0, 1024), (256, 4096, 16),
    (16384, 0, 8)])
def test_native_sweep_plan_blocks(cols, block_elems, block_rows,
                                  monkeypatch):
    """A grid step holds about MXNET_PALLAS_OPT_BLOCK_ELEMS elements in
    whole rows; the grid covers rows the blocks do not divide."""
    monkeypatch.setenv("MXNET_PALLAS_OPT_BLOCK_ELEMS", str(block_elems))
    plan = pk.sweep_plan((50272, cols), 4, 3)
    assert plan["block_rows"] == block_rows
    assert plan["in_specs"][0].block_shape == (block_rows, cols)
    assert plan["out_shapes"] == [(50272, cols)] * 3
    assert plan["grid"] == (-(-50272 // block_rows),)
    assert plan["in_specs"][0].index_map(3, None) == (3, 0)
    # a flat bucket's plan is what it was
    flat = pk.sweep_plan((50272 * cols,), 4, 3)
    assert flat["in_specs"][0].block_shape[1] == pk.LANES


@pytest.mark.parametrize("shape", [(12, 128), (64, 100), (8, 8, 128),
                                   (16, 32768)])
def test_native_sweep_refuses_what_it_cannot_tile(shape):
    w = jnp.zeros(shape, jnp.float32)
    with pytest.raises(ValueError, match="whole"):
        pk.fused_adam(w, w, w, w, lr_eff=0.01)


@pytest.mark.parametrize("optimizer", ["sgd", "sgd_momentum", "adam"])
@pytest.mark.parametrize("block_elems", [0, 4096])
def test_native_sweep_bitwise_vs_treemap(optimizer, block_elems,
                                         monkeypatch):
    """ACCEPTANCE: a (rows, C) bucket swept as it stands is EXACTLY the
    per-array tree_map path after N steps — params and slots — with one
    block, with several, and with a last block that overhangs the rows
    (72 rows in blocks of 16)."""
    from mxnet_tpu.parallel.optimizer import PureAdam, PureSGD
    monkeypatch.setenv("MXNET_PALLAS_OPT_BLOCK_ELEMS", str(block_elems))
    rng = np.random.RandomState(6)
    shapes = [(8, 128), (72, 256), (64, 512)]
    params = _native_buckets(rng, shapes)
    grads = [_native_buckets(rng, shapes) for _ in range(4)]
    opt = PureAdam(1e-3, wd=0.01, clip_gradient=0.5) \
        if optimizer == "adam" else \
        PureSGD(0.1, momentum=0.9 * (optimizer == "sgd_momentum"), wd=0.01)
    pf, sf = _drive(opt, params, grads, opt.init(params), "1", monkeypatch)
    pu, su = _drive(opt, params, grads, opt.init(params), "0", monkeypatch)
    for k in params:
        assert pf[k].shape == params[k].shape
        assert np.array_equal(np.asarray(pf[k]), np.asarray(pu[k])), k
    for a, b in zip(jax.tree_util.tree_leaves(sf),
                    jax.tree_util.tree_leaves(su)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("sharded", [True, False])
@pytest.mark.parametrize("optimizer", ["sgd_momentum", "adam"])
def test_native_sweep_bitwise_under_zero_shardings(optimizer, sharded,
                                                   monkeypatch):
    """The ZeRO layouts of a native bucket over four devices — rows
    sharded 1/mesh (the slot shards, shard_map'd sweep) and replicated
    — stay bit-identical to tree_map."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from mxnet_tpu.parallel.mesh import make_mesh
    from mxnet_tpu.parallel.optimizer import PureAdam, PureSGD
    mesh = make_mesh(dp=4, devices=jax.devices()[:4])
    ns = NamedSharding(mesh, P(tuple(mesh.axis_names)) if sharded else P())
    place = lambda t: jax.tree_util.tree_map(
        lambda a: jax.device_put(a, ns), t)
    rng = np.random.RandomState(7)
    shapes = [(32, 128), (96, 256)]
    params = place(_native_buckets(rng, shapes))
    grads = [place(_native_buckets(rng, shapes)) for _ in range(3)]
    opt = PureAdam(1e-3, wd=0.01) if optimizer == "adam" \
        else PureSGD(0.1, momentum=0.9, wd=0.01)

    def drive(knob):
        monkeypatch.setenv("MXNET_PALLAS_FUSED_OPT", knob)
        step = jax.jit(lambda p, g, s: opt.apply(
            p, g, s, flat=True, mesh=mesh if sharded else None))
        p, state = dict(params), opt.init(params, {k: ns for k in params})
        for g in grads:
            p, state = step(p, g, state)
        return p, state

    pf, sf = drive("1")
    pu, su = drive("0")
    for k in params:
        assert np.array_equal(np.asarray(pf[k]), np.asarray(pu[k])), k
    for a, b in zip(jax.tree_util.tree_leaves(sf),
                    jax.tree_util.tree_leaves(su)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_native_sweep_keeps_its_kernels_name_and_its_inputs():
    """The benchmark finds the sweep by the ``name=`` of its
    ``pallas_call``, native or flat; and the outputs that take their
    inputs' buffers never cost a caller an array it still holds."""
    rng = np.random.RandomState(8)
    w, g, m = (jnp.asarray(rng.randn(16, 256).astype(np.float32))
               for _ in range(3))
    v = jnp.abs(m)
    keep = [np.asarray(a).copy() for a in (w, g, m, v)]

    def names(fn, *args):
        found = []

        def walk(jaxpr):
            for eqn in jaxpr.eqns:
                if eqn.primitive.name == "pallas_call":
                    found.append((eqn.params["name"],
                                  tuple(eqn.params["input_output_aliases"])))
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)

        walk(jax.make_jaxpr(fn)(*args).jaxpr)
        return found

    adam = lambda *a: pk.fused_adam(*a, lr_eff=0.01)
    mom = lambda *a: pk.fused_sgd_momentum(*a, lr=0.1, momentum=0.9)
    assert names(adam, w, g, m, v) == \
        [("_adam_kernel", ((1, 0), (3, 1), (4, 2)))]
    assert names(mom, w, g, m) == [("_sgd_mom_kernel", ((1, 0), (3, 1)))]
    flat = [a.reshape(-1) for a in (w, g, m, v)]
    assert names(adam, *flat) == [("_adam_kernel", ())]
    nw, nm, nv = jax.jit(adam)(w, g, m, v)
    fw, fm, fv = jax.jit(adam)(*flat)
    for a, b in ((nw, fw), (nm, fm), (nv, fv)):
        assert np.array_equal(np.asarray(a).reshape(-1), np.asarray(b))
    for a, b in zip((w, g, m, v), keep):
        assert np.array_equal(np.asarray(a), b)


def test_fused_sweep_scalar_prefetch_no_recompile_on_lr_change():
    """The scalar-prefetch claim at kernel level: a changed lr/wd value
    reuses the SAME compiled program — the jit cache does not grow."""
    rng = np.random.RandomState(4)
    w = jnp.asarray(rng.randn(512).astype(np.float32))
    g = jnp.asarray(rng.randn(512).astype(np.float32))
    m = jnp.zeros(512, jnp.float32)

    @jax.jit
    def step(w, g, m, lr, wd):
        return pk.fused_sgd_momentum(w, g, m, lr=lr, momentum=0.9, wd=wd)

    step(w, g, m, jnp.float32(0.1), jnp.float32(0.01))
    before = step._cache_size()
    for lr in (0.05, 0.025, 0.0125):
        step(w, g, m, jnp.float32(lr), jnp.float32(0.001))
    assert step._cache_size() == before


# ---------------------------------------------------------------------------
# Fused layernorm / bias-softmax vs pure-jnp references
# ---------------------------------------------------------------------------
def _ref_layernorm(x, gamma, beta, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * gamma + beta


@pytest.mark.parametrize("shape", [(6, 33), (2, 5, 64), (3, 128)])
def test_fused_layernorm_fwd_bwd_parity(shape):
    rng = np.random.RandomState(5)
    c = shape[-1]
    x = jnp.asarray(rng.randn(*shape).astype(np.float32))
    gamma = jnp.asarray((rng.rand(c) + 0.5).astype(np.float32))
    beta = jnp.asarray(rng.randn(c).astype(np.float32))
    o = pk.fused_layernorm(x, gamma, beta, 1e-5)
    r = _ref_layernorm(x, gamma, beta)
    assert float(jnp.abs(o - r).max()) < 1e-5
    gf = jax.grad(lambda *a: jnp.sum(pk.fused_layernorm(*a, 1e-5) ** 2),
                  (0, 1, 2))(x, gamma, beta)
    gr = jax.grad(lambda *a: jnp.sum(_ref_layernorm(*a) ** 2),
                  (0, 1, 2))(x, gamma, beta)
    for a, b in zip(gf, gr):
        assert float(jnp.abs(a - b).max()) < 2e-4


def test_fused_bias_softmax_fwd_bwd_parity():
    rng = np.random.RandomState(6)
    x = jnp.asarray(rng.randn(4, 10, 17).astype(np.float32))
    bias = jnp.where(jnp.tril(jnp.ones((10, 17), bool)), 0.0,
                     pk.NEG_INF).astype(jnp.float32)
    p = pk.fused_bias_softmax(x, bias)
    r = jax.nn.softmax(x + bias[None], axis=-1)
    assert float(jnp.abs(p - r).max()) < 1e-6
    gf = jax.grad(lambda x: jnp.sum(pk.fused_bias_softmax(x, bias) ** 2))(x)
    gr = jax.grad(
        lambda x: jnp.sum(jax.nn.softmax(x + bias[None], -1) ** 2))(x)
    assert float(jnp.abs(gf - gr).max()) < 1e-6
    # no-bias form (the SoftmaxOutput core shape)
    x2 = jnp.asarray(rng.randn(9, 21).astype(np.float32))
    assert float(jnp.abs(pk.fused_bias_softmax(x2)
                         - jax.nn.softmax(x2, -1)).max()) < 1e-6


def test_layer_norm_op_routes_through_fused(monkeypatch):
    """The LayerNorm operator: fused and jnp paths agree (fwd); the
    knob falls back."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    rng = np.random.RandomState(7)
    d = rng.randn(4, 12).astype(np.float32)
    g = (rng.rand(12) + 0.5).astype(np.float32)
    b = rng.randn(12).astype(np.float32)
    outs = {}
    for knob in ("1", "0"):
        monkeypatch.setenv("MXNET_PALLAS_NORM", knob)
        outs[knob] = nd.LayerNorm(nd.array(d), nd.array(g),
                                  nd.array(b)).asnumpy()
    assert np.abs(outs["1"] - outs["0"]).max() < 1e-5


def test_softmax_output_routes_through_fused(monkeypatch):
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    rng = np.random.RandomState(8)
    d = rng.randn(6, 10).astype(np.float32)
    lbl = rng.randint(0, 10, 6).astype(np.float32)
    outs = {}
    for knob in ("1", "0"):
        monkeypatch.setenv("MXNET_PALLAS_SOFTMAX", knob)
        outs[knob] = nd.SoftmaxOutput(nd.array(d),
                                      nd.array(lbl)).asnumpy()
    assert np.abs(outs["1"] - outs["0"]).max() < 1e-6


def test_local_attention_fused_softmax_parity(monkeypatch):
    """Non-flash attention path: fused bias+softmax vs the einsum/
    jax.nn.softmax form, plain and causal, forward and backward."""
    from mxnet_tpu.parallel import attention as att
    rng = np.random.RandomState(9)
    q = jnp.asarray(rng.randn(2, 24, 4, 8).astype(np.float32))
    k = jnp.asarray(rng.randn(2, 24, 4, 8).astype(np.float32))
    v = jnp.asarray(rng.randn(2, 24, 4, 8).astype(np.float32))
    for causal in (False, True):
        outs, grads = {}, {}
        for knob in ("1", "0"):
            monkeypatch.setenv("MXNET_PALLAS_SOFTMAX", knob)
            outs[knob] = att.local_attention(q, k, v, causal=causal,
                                             impl="einsum")
            grads[knob] = jax.grad(lambda q: jnp.sum(att.local_attention(
                q, k, v, causal=causal, impl="einsum") ** 2))(q)
        assert float(jnp.abs(outs["1"] - outs["0"]).max()) < 1e-5, causal
        assert float(jnp.abs(grads["1"] - grads["0"]).max()) < 1e-4, causal


def test_fused_bn_relu_eval_peephole(monkeypatch):
    """The inference BatchNorm→relu peephole (fused_scale_bias_relu
    call site): executor eval forward matches the per-op path; train
    mode keeps batch stats + aux writeback."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    data = mx.sym.var("data")
    net = mx.sym.Convolution(data, num_filter=8, kernel=(3, 3),
                             pad=(1, 1), name="c1")
    net = mx.sym.BatchNorm(net, name="bn1", fix_gamma=False)
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(mx.sym.Flatten(net), num_hidden=4,
                                name="fc")
    rng = np.random.RandomState(10)
    x = rng.rand(2, 3, 8, 8).astype(np.float32)
    probe = net.simple_bind(ctx=mx.cpu(), grad_req="null", data=(2, 3, 8, 8))
    args = {n: rng.randn(*a.shape).astype(np.float32) * 0.1
            for n, a in probe.arg_dict.items() if n != "data"}
    aux = {n: ((np.abs(rng.randn(*a.shape)) + 0.5) if "var" in n
               else rng.randn(*a.shape) * 0.1).astype(np.float32)
           for n, a in probe.aux_dict.items()}

    def fwd(knob, is_train=False):
        monkeypatch.setenv("MXNET_PALLAS_BN_RELU", knob)
        exe = net.simple_bind(ctx=mx.cpu(),
                              grad_req="write" if is_train else "null",
                              data=(2, 3, 8, 8))
        for n, a in exe.arg_dict.items():
            if n != "data":
                a[:] = nd.array(args[n])
        for n, a in exe.aux_dict.items():
            a[:] = nd.array(aux[n])
        exe.arg_dict["data"][:] = nd.array(x)
        out = exe.forward(is_train=is_train)[0].asnumpy()
        return out, exe
    fused, _ = fwd("1")
    plain, _ = fwd("0")
    assert np.abs(fused - plain).max() < 1e-4
    _, exe = fwd("1", is_train=True)
    assert not np.allclose(exe.aux_dict["bn1_moving_mean"].asnumpy(),
                           aux["bn1_moving_mean"]), \
        "train-mode BN must keep its aux writeback (no fusion)"


def test_pallas_kernel_calls_counter():
    """mxnet_pallas_kernel_calls_total{kernel} advances per wrapper
    call when telemetry is on."""
    from mxnet_tpu import telemetry
    telemetry.enable()
    try:
        rng = np.random.RandomState(11)
        w = jnp.asarray(rng.randn(64).astype(np.float32))
        pk.fused_sgd_momentum(w, w, w, lr=0.1, momentum=0.9)
        pk.fused_adam(w, w, w, jnp.abs(w), lr_eff=0.01)
        fam = telemetry.snapshot()["mxnet_pallas_kernel_calls_total"]
        labeled = {dict(v["labels"])["kernel"]: v["value"]
                   for v in fam["values"]}
        assert labeled["fused_sgd_momentum"] >= 1
        assert labeled["fused_adam"] >= 1
    finally:
        telemetry.disable()


def test_fused_bias_softmax_shape_and_dtype_contracts():
    """Mis-sized bias raises instead of silently re-associating rows;
    a non-f32 bias gets its cotangent back in its own dtype."""
    rng = np.random.RandomState(12)
    x = jnp.asarray(rng.randn(4, 10, 17).astype(np.float32))
    bad = jnp.zeros((20, 17), jnp.float32)
    with pytest.raises(ValueError, match="bias rows"):
        pk.fused_bias_softmax(x, bad)
    bias16 = jnp.zeros((10, 17), jnp.bfloat16)
    _, dbias = jax.grad(
        lambda x, b: jnp.sum(pk.fused_bias_softmax(x, b) ** 2),
        (0, 1))(x, bias16)
    assert dbias.dtype == jnp.bfloat16


def test_local_attention_empty_causal_rows_keep_loud_path(monkeypatch):
    """q_offset < kv_offset under a causal mask can leave query rows
    with NO visible key; the fused kernel's finite NEG_INF would
    silently return uniform attention there, so the gate must keep the
    einsum path (whose NaN surfaces the misuse) — knob on and off must
    agree."""
    from mxnet_tpu.parallel import attention as att
    rng = np.random.RandomState(13)
    q = jnp.asarray(rng.randn(1, 8, 2, 4).astype(np.float32))
    k = jnp.asarray(rng.randn(1, 8, 2, 4).astype(np.float32))
    v = jnp.asarray(rng.randn(1, 8, 2, 4).astype(np.float32))
    outs = {}
    for knob in ("1", "0"):
        monkeypatch.setenv("MXNET_PALLAS_SOFTMAX", knob)
        outs[knob] = np.asarray(att.local_attention(
            q, k, v, causal=True, q_offset=0, kv_offset=4, impl="einsum"))
    np.testing.assert_array_equal(np.isnan(outs["1"]), np.isnan(outs["0"]))
    m = ~np.isnan(outs["0"])
    assert np.allclose(outs["1"][m], outs["0"][m], atol=1e-5)
    # aligned offsets still ride the fused path and agree
    for knob in ("1", "0"):
        monkeypatch.setenv("MXNET_PALLAS_SOFTMAX", knob)
        outs[knob] = np.asarray(att.local_attention(
            q, k, v, causal=True, q_offset=4, kv_offset=0, impl="einsum"))
    assert np.allclose(outs["1"], outs["0"], atol=1e-5)


# ---------------------------------------------------------------------------
# Block choice: what native Mosaic needs that interpret mode never checks
# ---------------------------------------------------------------------------
def test_row_block_is_width_and_dtype_aware():
    """ResNet-50's last stage at serving bucket 16 is a (784, 2048)
    array: one 784-row fp32 block is 6.4 MB, and in + out
    double-buffered is 25 MiB against 16 MiB of scoped VMEM.  The
    block must shrink with the width, stay a whole number of sublane
    tiles of the dtype, and prefer a divisor (no padding)."""
    for dtype, sub in ((np.float32, 8), (jnp.bfloat16, 16), (np.int8, 32)):
        assert pk._sublane(dtype) == sub
        bn = pk._row_block(784, 2048, dtype, 1024)
        assert bn * 2048 * 4 <= pk._BLOCK_BYTES and bn % sub == 0
        assert 784 % bn == 0 or dtype is np.int8
    assert pk._row_block(784, 2048, np.float32, 1024) == 56
    # narrow and long: the preferred block, which divides
    assert pk._row_block(256 * 56 * 56, 64, np.float32, 1024) == 1024
    # fits one block: taken whole, whatever its row count
    assert pk._row_block(49, 2048, jnp.bfloat16, 1024) == 49
    # no usable divisor (2 x prime): the cap, and the caller pads
    x = jnp.asarray(np.random.RandomState(0).randn(2 * 1009, 512)
                    .astype(np.float32))
    assert pk._row_block(x.shape[0], 512, x.dtype, 1024) == 256
    y = pk.fused_scale_bias_relu(x, jnp.ones(512), jnp.zeros(512))
    assert y.shape == x.shape
    np.testing.assert_array_equal(np.asarray(y),
                                  np.maximum(np.asarray(x), 0))


def test_norm_block_rows_follow_the_dtype_sublane(monkeypatch):
    knob = "MXNET_PALLAS_SOFTMAX_BLOCK_ROWS"
    assert pk._norm_block_rows(1, 1024, knob) == 8
    assert pk._norm_block_rows(1, 1024, knob, dtype=jnp.bfloat16) == 16
    assert pk._norm_block_rows(256, 1024, knob, dtype=jnp.bfloat16) == 128
    # an explicit value is clamped to whole tiles too
    monkeypatch.setenv(knob, "8")
    assert pk._norm_block_rows(256, 1024, knob, dtype=jnp.bfloat16) == 16


@pytest.mark.parametrize("value,enabled", [
    (None, False), ("auto", False), ("1", True), ("0", False)])
def test_family_enabled_reads_the_environment(value, enabled, monkeypatch):
    """``auto`` (and unset) is native-only, so off on the CPU; ``1``
    forces the family on in interpret mode; ``0`` turns it off."""
    knob = "MXNET_PALLAS_NORM"
    if value is None:
        monkeypatch.delenv(knob, raising=False)
    else:
        monkeypatch.setenv(knob, value)
    assert pk.family_enabled(knob) is enabled


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("kernel", ["fwd", "dq", "dkv"])
def test_flash_blocks_picked_from_the_shape(kernel, d):
    """The two LM cells' shapes: blocks in whole sublane tiles that
    divide T, inside the scoped-VMEM budget, and at most 1,024 grid
    steps a call at 64 (batch x heads) where 128-row blocks took
    16,384."""
    from mxnet_tpu import config
    T, dtype = 2048, jnp.bfloat16
    bq, bk = pk._flash_blocks(T, T, d, dtype, kernel)
    for b in (bq, bk):
        assert T % b == 0 and b % pk._sublane(dtype) == 0
    plan = pk._FLASH_PLANS[kernel](64, T, T, d, bq, bk, True, dtype)
    assert pk._flash_vmem_bytes(plan) <= config.get("MXNET_KERN_VMEM_BYTES")
    assert math.prod(plan["grid"]) <= 1024
    # a short sequence is one block, whatever its length
    assert pk._flash_blocks(48, 48, d, jnp.float32, kernel) == (48, 48)
    assert pk._flash_blocks(8, 136, d, dtype, kernel) == (8, 136)


def test_flash_explicit_blocks_are_honoured():
    def blocks(kernel, tq, d, dtype, block_q, block_k):
        a = jax.ShapeDtypeStruct((2, tq, d), dtype)
        return pk._flash_plan(kernel, a, a, True, block_q, block_k)[:2]
    assert blocks("fwd", 2048, 64, jnp.bfloat16, 128, 256) == (128, 256)
    # halved until they divide T, as ever
    assert blocks("dkv", 48, 8, jnp.float32, 16, 32) == (16, 16)
    # one side given: the other is picked
    bq, bk = pk._flash_blocks(2048, 2048, 64, jnp.bfloat16, "dq")
    assert blocks("dq", 2048, 64, jnp.bfloat16, None, 128) == (bq, 128)


def test_flash_causal_index_maps_hold_at_the_diagonal():
    """A masked step names the block the step before it named, so
    Pallas copies nothing for it: K blocks stop at the last one a Q row
    needs, Q blocks of dK/dV start at the first one a K column needs."""
    fwd = pk.flash_fwd_plan(1, 1024, 1024, 64, 256, 128, True)
    kmap = fwd["in_specs"][1].index_map
    assert [kmap(0, 1, j)[1] for j in range(8)] == [0, 1, 2, 3, 3, 3, 3, 3]
    assert [kmap(0, 3, j)[1] for j in range(8)] == list(range(8))
    dkv = pk.flash_bwd_dkv_plan(1, 1024, 1024, 64, 256, 128, True)
    qmap, rowmap = (dkv["in_specs"][i].index_map for i in (0, 4))
    assert [qmap(0, 5, i)[1] for i in range(4)] == [2, 2, 2, 3]
    assert [rowmap(0, 5, i)[1] for i in range(4)] == [2, 2, 2, 3]
    # keys past the last query row (Tk > Tq) stay inside the array
    wide = pk.flash_bwd_dkv_plan(1, 256, 512, 64, 128, 128, True)
    assert [wide["in_specs"][0].index_map(0, 3, i)[1]
            for i in range(2)] == [1, 1]
    # without the mask the maps are the identity
    plain = pk.flash_fwd_plan(1, 1024, 1024, 64, 256, 128)
    assert [plain["in_specs"][1].index_map(0, 1, j)[1]
            for j in range(8)] == list(range(8))


def test_flash_gauges_read_the_picked_blocks():
    """mxnet_flash_block_rows / mxnet_flash_grid_steps after a trace of
    the forward and the backward (nothing runs: eval_shape)."""
    from mxnet_tpu import telemetry
    bh, T, d = 4, 2048, 64
    a = jax.ShapeDtypeStruct((bh, T, d), jnp.bfloat16)
    telemetry.enable()
    try:
        jax.eval_shape(jax.grad(
            lambda q, k, v: jnp.sum(pk.flash_attention(
                q, k, v, True).astype(jnp.float32)), (0, 1, 2)), a, a, a)
        rows = telemetry.gauge("mxnet_flash_block_rows")
        steps = telemetry.gauge("mxnet_flash_grid_steps")
        for kernel in ("fwd", "dq", "dkv"):
            bq, bk = pk._flash_blocks(T, T, d, jnp.bfloat16, kernel)
            assert rows.labels(kernel=kernel, side="q").value == bq
            assert rows.labels(kernel=kernel, side="k").value == bk
            assert steps.labels(kernel=kernel).value \
                == bh * (T // bq) * (T // bk)
        # the newest instantiation wins: explicit 128-row blocks
        jax.eval_shape(lambda q, k, v: pk.flash_attention(
            q, k, v, True, None, 128, 128), a, a, a)
        assert rows.labels(kernel="fwd", side="q").value == 128
        assert steps.labels(kernel="fwd").value == bh * 16 * 16
    finally:
        telemetry.disable()


def test_flash_eligibility_knows_the_sublane_tile():
    assert pk.flash_seq_ok(2048, jnp.bfloat16)
    assert pk.flash_seq_ok(8, jnp.bfloat16)          # whole sequence
    assert pk.flash_seq_ok(136, jnp.float32)         # 8-row blocks, f32
    assert not pk.flash_seq_ok(136, jnp.bfloat16)    # 8 rows < bf16 tile
