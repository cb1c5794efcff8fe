"""Table-driven operator sweep vs numpy oracles.

Reference analogue: tests/python/unittest/test_operator.py's long tail
of per-op numeric checks (147 tests).  Each case invokes the op through
the public mx.nd surface and compares against a numpy reference;
gradient coverage for the differentiable ones comes from the
finite-difference sweep (test_numeric_gradient.py).
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd

rng = np.random.RandomState(7)
A = rng.rand(3, 4).astype(np.float32) * 0.8 + 0.1       # (0.1, 0.9)
B = rng.rand(3, 4).astype(np.float32) * 0.8 + 0.1
S = rng.randn(3, 4).astype(np.float32)                  # signed
P = rng.rand(3, 4).astype(np.float32) * 4 - 2           # (-2, 2)


UNARY_CASES = [
    ("arccos", A, lambda x: np.arccos(x)),
    ("arcsinh", S, lambda x: np.arcsinh(x)),
    ("arccosh", 1.0 + A, lambda x: np.arccosh(x)),
    ("arctanh", A * 0.9, lambda x: np.arctanh(x)),
    ("degrees", S, lambda x: np.degrees(x)),
    ("radians", S, lambda x: np.radians(x)),
    ("rint", P, lambda x: np.rint(x)),
    ("fix", P, lambda x: np.fix(x)),
    ("trunc", P, lambda x: np.trunc(x)),
    ("rcbrt", A, lambda x: 1.0 / np.cbrt(x)),
    ("erf", S, None),          # oracle via math.erf below
    ("erfinv", A * 0.9, None),
    ("gammaln", A * 4 + 0.5, None),
    ("logical_not", np.array([[0.0, 1.0], [2.0, 0.0]], np.float32),
     lambda x: (x == 0).astype(np.float32)),
    ("reverse", S, lambda x: x[::-1], {"axis": 0}),
    ("nansum", np.where(A > 0.5, np.nan, A).astype(np.float32),
     lambda x: np.nansum(x)),
    ("nanprod", np.where(A > 0.5, np.nan, A).astype(np.float32),
     lambda x: np.nanprod(x)),
]


@pytest.mark.parametrize("case", UNARY_CASES, ids=lambda c: c[0])
def test_unary_ops(case):
    name, x, oracle = case[0], case[1], case[2]
    attrs = case[3] if len(case) > 3 else {}
    got = getattr(nd, name)(nd.array(x), **attrs).asnumpy()
    if oracle is None:
        import math
        fn = {"erf": math.erf,
              "erfinv": __import__("statistics").NormalDist().inv_cdf,
              "gammaln": math.lgamma}[name]
        if name == "erfinv":
            # erfinv(x) = inv_cdf((x+1)/2) / sqrt(2)
            want = np.vectorize(
                lambda v: fn((v + 1) / 2) / np.sqrt(2))(x)
        else:
            want = np.vectorize(fn)(x)
    else:
        want = oracle(x)
    assert np.allclose(got, want, rtol=1e-4, atol=1e-5), name


def test_elemwise_and_scalar_variants():
    a, b = nd.array(A), nd.array(B)
    assert np.allclose(nd.elemwise_mul(a, b).asnumpy(), A * B)
    assert np.allclose(nd.elemwise_sub(a, b).asnumpy(), A - B)
    assert np.allclose(nd.elemwise_div(a, b).asnumpy(), A / B, rtol=1e-5)
    assert np.allclose(nd.add_n(a, b, a).asnumpy(), A + B + A, rtol=1e-5)
    # reflected scalar sugar lowers to the *_scalar ops
    assert np.allclose((3.0 - a).asnumpy(), 3.0 - A)
    assert np.allclose((3.0 / a).asnumpy(), 3.0 / A, rtol=1e-5)
    assert np.allclose((2.0 ** a).asnumpy(), 2.0 ** A, rtol=1e-5)
    assert np.allclose((a % 0.3).asnumpy(), A % 0.3, rtol=1e-4, atol=1e-5)
    assert np.allclose((0.7 % a).asnumpy(), 0.7 % A, rtol=1e-4, atol=1e-5)
    assert np.allclose(nd.maximum(a, b).asnumpy(), np.maximum(A, B))
    assert np.allclose(nd.minimum(a, 0.5).asnumpy(), np.minimum(A, 0.5))
    assert np.array_equal(nd.logical_and(a, nd.zeros_like(a)).asnumpy(),
                          np.zeros_like(A))
    assert np.array_equal(nd.logical_or(a, nd.zeros_like(a)).asnumpy(),
                          np.ones_like(A))
    assert np.array_equal(nd.logical_xor(a, a).asnumpy(),
                          np.zeros_like(A))
    assert np.array_equal((a != b).asnumpy(), (A != B).astype(np.float32))


def test_shape_and_layout_ops():
    x = nd.array(S)
    assert np.array_equal(nd.shape_array(x).asnumpy(), [3, 4])
    assert int(nd.size_array(x).asnumpy()) == 12
    img = nd.array(rng.rand(1, 4, 2, 2).astype(np.float32))
    d2s = nd.depth_to_space(img, block_size=2)
    assert d2s.shape == (1, 1, 4, 4)
    back = nd.space_to_depth(d2s, block_size=2)
    assert np.allclose(back.asnumpy(), img.asnumpy())
    big = nd.array(rng.rand(5, 6).astype(np.float32))
    like = nd.array(np.zeros((3, 4), np.float32))
    sl = nd.slice_like(big, like)
    assert np.allclose(sl.asnumpy(), big.asnumpy()[:3, :4])
    bx = nd.broadcast_axis(nd.array(np.ones((1, 4), np.float32)),
                           axis=0, size=3)
    assert bx.shape == (3, 4)


def test_indexing_ops():
    data = nd.array(rng.rand(3, 4).astype(np.float32))
    idx = nd.array(np.array([1, 0, 2], np.float32))
    bt = nd.batch_take(data, idx.astype("int32"))
    want = data.asnumpy()[np.arange(3), [1, 0, 2]]
    assert np.allclose(bt.asnumpy(), want)
    sc = nd.scatter_nd(nd.array(np.array([9.0, 8.0], np.float32)),
                       nd.array(np.array([[0, 1], [2, 3]], np.float32)),
                       shape=(3, 4))
    out = np.zeros((3, 4), np.float32)
    out[0, 2], out[1, 3] = 9.0, 8.0
    assert np.allclose(sc.asnumpy(), out)
    am = nd.argmax_channel(data)
    assert np.array_equal(am.asnumpy(), data.asnumpy().argmax(1))


def test_loss_helper_ops():
    logits = nd.array(rng.randn(4, 5).astype(np.float32))
    labels = nd.array(np.array([0, 2, 4, 1], np.float32))
    sce = nd.softmax_cross_entropy(logits, labels)
    l = logits.asnumpy()
    p = np.exp(l - l.max(1, keepdims=True))
    p /= p.sum(1, keepdims=True)
    want = -np.log(p[np.arange(4), labels.asnumpy().astype(int)]).sum()
    assert np.allclose(sce.asnumpy(), want, rtol=1e-4)
    x = nd.array(S)
    sm = nd.smooth_l1(x, scalar=1.0)
    a = S
    want = np.where(np.abs(a) < 1, 0.5 * a * a, np.abs(a) - 0.5)
    assert np.allclose(sm.asnumpy(), want, rtol=1e-5)


def test_khatri_rao():
    a = rng.rand(2, 3).astype(np.float32)
    b = rng.rand(4, 3).astype(np.float32)
    out = nd.khatri_rao(nd.array(a), nd.array(b)).asnumpy()
    want = np.vstack([np.kron(a[:, i], b[:, i]) for i in range(3)]).T
    assert out.shape == (8, 3)
    assert np.allclose(out, want, rtol=1e-5)


def test_linalg_family():
    """linalg ops vs numpy.linalg (reference: tensor/la_op.h)."""
    a = rng.rand(3, 3).astype(np.float32)
    spd = a @ a.T + 3 * np.eye(3, dtype=np.float32)
    A_ = nd.array(spd)
    B_ = nd.array(rng.rand(3, 2).astype(np.float32))
    # gemm2 / gemm
    g2 = nd.linalg.gemm2(A_, B_).asnumpy()
    assert np.allclose(g2, spd @ B_.asnumpy(), rtol=1e-4)
    C_ = nd.array(rng.rand(3, 2).astype(np.float32))
    g = nd.linalg.gemm(A_, B_, C_, alpha=2.0, beta=0.5).asnumpy()
    assert np.allclose(g, 2.0 * spd @ B_.asnumpy() + 0.5 * C_.asnumpy(),
                       rtol=1e-4)
    # potrf: lower cholesky
    L = nd.linalg.potrf(A_).asnumpy()
    assert np.allclose(L @ L.T, spd, atol=1e-3)
    assert np.allclose(L, np.tril(L), atol=1e-6)
    # potri: inverse from cholesky
    inv = nd.linalg.potri(nd.array(L)).asnumpy()
    assert np.allclose(inv, np.linalg.inv(spd), atol=1e-3)
    # trsm solves L X = alpha B
    X = nd.linalg.trsm(nd.array(L), B_).asnumpy()
    assert np.allclose(np.tril(L) @ X, B_.asnumpy(), atol=1e-4)
    # trmm multiplies by the triangle
    M = nd.linalg.trmm(nd.array(L), B_).asnumpy()
    assert np.allclose(M, np.tril(L) @ B_.asnumpy(), rtol=1e-4)
    # syrk
    K = nd.linalg.syrk(A_).asnumpy()
    assert np.allclose(K, spd @ spd.T, rtol=1e-4)
    # sumlogdiag
    sld = nd.linalg.sumlogdiag(nd.array(L)).asnumpy()
    assert np.allclose(sld, np.log(np.diag(L)).sum(), rtol=1e-4)
    # syevd: eigendecomposition of symmetric matrix
    U, lam = nd.linalg.syevd(A_)
    recon = U.asnumpy().T @ np.diag(lam.asnumpy()) @ U.asnumpy()
    assert np.allclose(recon, spd, atol=1e-3)
    # gelqf: LQ factorization
    R_ = nd.array(rng.rand(2, 3).astype(np.float32))
    Lq, Q = nd.linalg.gelqf(R_)
    assert np.allclose(Lq.asnumpy() @ Q.asnumpy(), R_.asnumpy(), atol=1e-4)
    assert np.allclose(Q.asnumpy() @ Q.asnumpy().T, np.eye(2), atol=1e-4)


def test_random_distributions_statistics():
    """Sampling ops: moments within tolerance (reference test_random.py)."""
    mx.random.seed(99)
    n = 40000
    cases = [
        # the python wrapper takes scale=1/lam (reference random.py)
        ("exponential", {"scale": 0.5}, 1 / 2.0, 1 / 4.0),
        ("gamma", {"alpha": 3.0, "beta": 2.0}, 6.0, 12.0),
        ("poisson", {"lam": 4.0}, 4.0, 4.0),
        ("negative_binomial", {"k": 5, "p": 0.5}, 5.0, 10.0),
        ("generalized_negative_binomial", {"mu": 3.0, "alpha": 0.2},
         3.0, 3.0 + 0.2 * 9.0),
    ]
    for name, kw, mean, var in cases:
        s = getattr(nd.random, name)(shape=(n,), **kw).asnumpy()
        assert abs(s.mean() - mean) < 0.15 * max(1.0, mean), (name, s.mean())
        assert abs(s.var() - var) < 0.25 * max(1.0, var), (name, s.var())
    r = nd.random.randint(2, 9, shape=(n,)).asnumpy()
    assert r.min() >= 2 and r.max() <= 8
    sh = nd.shuffle(nd.array(np.arange(100, dtype=np.float32)))
    assert sorted(sh.asnumpy().tolist()) == list(range(100))
    assert not np.array_equal(sh.asnumpy(), np.arange(100))


def test_optimizer_update_kernels():
    """Direct kernels (reference src/operator/optimizer_op-inl.h)."""
    w0 = rng.rand(6).astype(np.float32)
    g0 = rng.randn(6).astype(np.float32) * 0.1

    # signsgd: w -= lr * sign(g)
    w = nd.array(w0)
    nd.signsgd_update(w, nd.array(g0), lr=0.1, out=w)
    assert np.allclose(w.asnumpy(), w0 - 0.1 * np.sign(g0), rtol=1e-5)

    # signum: momentum of sign
    w = nd.array(w0)
    m = nd.zeros((6,))
    nd.signum_update(w, nd.array(g0), m, lr=0.1, momentum=0.9, out=w)
    assert np.allclose(w.asnumpy(), w0 - 0.1 * np.sign(0.1 * g0), rtol=1e-4)

    # rmsprop: n = (1-g1) g^2; w -= lr g / (sqrt(n)+eps)
    w = nd.array(w0)
    n_ = nd.zeros((6,))
    nd.rmsprop_update(w, nd.array(g0), n_, lr=0.01, gamma1=0.9,
                      epsilon=1e-8, out=w)
    nexp = 0.1 * g0 ** 2
    # reference kernel divides by sqrt(n + eps) (optimizer_op-inl.h)
    assert np.allclose(w.asnumpy(), w0 - 0.01 * g0 / np.sqrt(nexp + 1e-8),
                       rtol=1e-4)

    # ftrl keeps |w| small for tiny grads with l1
    w = nd.array(w0)
    z = nd.zeros((6,))
    n2 = nd.zeros((6,))
    nd.ftrl_update(w, nd.array(g0 * 1e-3), z, n2, lr=0.1, lamda1=1.0,
                   out=w)
    assert np.abs(w.asnumpy()).max() < np.abs(w0).max() + 1e-6

    # mp_sgd: bf16 weights with fp32 master
    w16 = nd.array(w0.astype(np.float16))
    w32 = nd.array(w0)
    nd.mp_sgd_update(w16, nd.array(g0.astype(np.float16)), w32, lr=0.5,
                     out=w16)
    assert np.allclose(w32.asnumpy(), w0 - 0.5 * g0, rtol=1e-2)
    assert np.allclose(w16.asnumpy(), (w0 - 0.5 * g0).astype(np.float16),
                       rtol=1e-2)


# -- typed-parameter tables (dmlc::Parameter parity) ------------------------
# Reference: every op declares a dmlc::Parameter struct whose Init()
# throws on unknown keys (src/operator/nn/convolution-inl.h:50-100,
# dmlc-core parameter.h).  Here every registered op must carry a
# parameter table (hand-declared entries merged over signature-derived
# ones) and reject unknown kwargs naming the nearest valid parameter.

def test_every_op_has_param_table():
    import inspect
    from mxnet_tpu.ops.registry import _OP_REGISTRY, OPTIONAL_ARRAY_INPUTS
    ops = {o.name: o for o in _OP_REGISTRY.values()}
    # completeness: every keyword attr the op fn accepts is in the table
    incomplete = []
    for n, o in ops.items():
        sig_attrs = {
            p.name for p in inspect.signature(o.fn).parameters.values()
            if p.kind not in (inspect.Parameter.VAR_POSITIONAL,
                              inspect.Parameter.VAR_KEYWORD)
            and p.default is not inspect.Parameter.empty
            and not p.name.startswith("__")
            and p.name not in OPTIONAL_ARRAY_INPUTS
            and p.name not in o.mutate_aux}
        if not sig_attrs <= set(o.params):
            incomplete.append((n, sorted(sig_attrs - set(o.params))))
    assert not incomplete, "ops with attrs missing from table: %s" % incomplete
    free = [n for n, o in ops.items() if o.free_attrs]
    assert not free, "unexpected free-attr ops (must be documented): %s" % free


def test_every_op_rejects_unknown_kwarg():
    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.ops.registry import _OP_REGISTRY
    ops = {o.name: o for o in _OP_REGISTRY.values()}
    accepted = []
    for n, op in ops.items():
        try:
            op.validate_attrs({"zz_bogus_attr": 1})
            accepted.append(n)
        except MXNetError as e:
            assert n in str(e) and "zz_bogus_attr" in str(e)
    assert not accepted, "ops silently accepting unknown kwargs: %s" % accepted


def test_unknown_kwarg_suggests_nearest_param():
    from mxnet_tpu.base import MXNetError
    # imperative path
    with pytest.raises(MXNetError, match=r"no_bias"):
        nd.FullyConnected(nd.ones((2, 3)), nd.ones((4, 3)), nd.ones((4,)),
                          num_hidden=4, no_bais=True)
    # symbolic path fails at graph-construction time, same message
    import mxnet_tpu.symbol as sym
    with pytest.raises(MXNetError, match=r"no_bias"):
        sym.FullyConnected(sym.var("d"), num_hidden=4, no_bais=True)
    # typo'd kernel on Convolution names the op
    with pytest.raises(MXNetError, match=r"Convolution.*kernal.*kernel"):
        sym.Convolution(sym.var("d"), kernal=(3, 3), num_filter=8)


def test_derived_params_type_checked():
    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.ops.registry import get_op
    conv = get_op("Convolution")
    # cudnn-compat kwargs come from the signature, not the declared table
    assert "cudnn_off" in conv.params and conv.params["cudnn_off"].derived
    # bool-typed derived entry rejects a non-boolean
    with pytest.raises(MXNetError, match=r"cudnn_off"):
        conv.validate_attrs({"kernel": (3, 3), "num_filter": 8,
                             "cudnn_off": "sometimes"})
    # scope/framework attrs still pass through untouched
    conv.validate_attrs({"kernel": (3, 3), "num_filter": 8,
                         "name": "c0", "__lr_mult__": "2.0"})


# -- reference-transcribed range/enum overlay (constraints.py) --------------
# Reference: dmlc fields with set_range/set_lower_bound/add_enum
# (e.g. src/operator/roi_pooling-inl.h spatial_scale.set_range(0, 1));
# the overlay table transcribes every such bound and THIS sweep walks
# the same table, so transcription and enforcement cannot drift.

def test_constraint_overlay_fully_applied():
    from mxnet_tpu.ops import constraints
    assert constraints.UNAPPLIED == (), \
        "constraint entries with no matching op/param: %s" % (
            constraints.UNAPPLIED,)


def test_every_transcribed_bound_is_enforced():
    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.ops.registry import _OP_REGISTRY
    from mxnet_tpu.ops.constraints import CONSTRAINTS
    soft = []
    for opname, fields in CONSTRAINTS.items():
        op = _OP_REGISTRY[opname]
        for pname, c in fields.items():
            p = op.params[pname]
            # the live bound must be at least as tight as the reference's
            if "low" in c and (p.low is None or p.low < c["low"]):
                soft.append((opname, pname, "low"))
            if "high" in c and (p.high is None or p.high > c["high"]):
                soft.append((opname, pname, "high"))
            # and actually enforced: an out-of-range value raises
            for bad in ([c["low"] - 1] if "low" in c else []) + \
                       ([c["high"] + 1] if "high" in c else []):
                try:
                    p.check(opname, (bad,) if p.ptype is tuple else bad)
                    soft.append((opname, pname, "accepted %r" % bad))
                except MXNetError:
                    pass
    assert not soft, "reference-bounded params not enforced: %s" % soft


def test_judge_probe_values_raise():
    from mxnet_tpu.base import MXNetError
    with pytest.raises(MXNetError, match="spatial_scale"):
        nd.ROIPooling(nd.ones((1, 3, 8, 8)), nd.array([[0, 0, 0, 4, 4]]),
                      pooled_size=(2, 2), spatial_scale=-3)
    with pytest.raises(MXNetError, match="kernel_size"):
        nd.Correlation(nd.ones((1, 1, 8, 8)), nd.ones((1, 1, 8, 8)),
                       kernel_size=-5)
    with pytest.raises(MXNetError, match="axis"):
        nd.SequenceMask(nd.ones((4, 2, 3)), axis=7)
    with pytest.raises(MXNetError, match="ord"):
        nd.norm(nd.ones((3, 3)), ord=99)
    # stabilizer/name-based defaults: eps and lr are non-negative
    with pytest.raises(MXNetError, match="eps"):
        nd.BatchNorm(nd.ones((2, 3, 4, 4)), nd.ones(3), nd.zeros(3),
                     nd.zeros(3), nd.ones(3), eps=-1e-3)
    with pytest.raises(MXNetError, match="lr"):
        nd.sgd_update(nd.ones((3,)), nd.ones((3,)), lr=-0.1)
