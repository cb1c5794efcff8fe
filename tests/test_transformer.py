"""gluon.contrib.transformer: the long-context model family.

No reference analogue (MXNet 1.2 predates attention, SURVEY §5.7);
these layers consume the TPU-native attention stack: contrib
flash_attention op single-device, ring attention transparently under an
'sp' mesh scope.
"""
import numpy as np
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd, parallel
from mxnet_tpu.gluon.contrib.transformer import (MultiHeadAttention,
                                                 TransformerEncoderCell,
                                                 TransformerLM)


def _dense_ref(q, k, v, causal):
    d = q.shape[-1]
    logits = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    if causal:
        t = q.shape[1]
        mask = np.tril(np.ones((t, t), bool))
        logits = np.where(mask[None, None], logits, -np.inf)
    logits = logits - logits.max(-1, keepdims=True)
    p = np.exp(logits)
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p, v)


def test_flash_attention_op_matches_dense():
    rng = np.random.RandomState(0)
    q = rng.randn(2, 16, 4, 8).astype(np.float32)
    k = rng.randn(2, 16, 4, 8).astype(np.float32)
    v = rng.randn(2, 16, 4, 8).astype(np.float32)
    for causal in (False, True):
        out = mx.nd.contrib.flash_attention(
            nd.array(q), nd.array(k), nd.array(v), causal=causal)
        assert np.allclose(out.asnumpy(), _dense_ref(q, k, v, causal),
                           atol=1e-5)


def test_flash_attention_op_gqa():
    rng = np.random.RandomState(1)
    q = rng.randn(1, 8, 4, 8).astype(np.float32)
    kv = rng.randn(1, 8, 2, 8).astype(np.float32)
    out = mx.nd.contrib.flash_attention(nd.array(q), nd.array(kv),
                                        nd.array(kv), causal=True)
    k_full = np.repeat(kv, 2, axis=2)
    assert np.allclose(out.asnumpy(), _dense_ref(q, k_full, k_full, True),
                       atol=1e-5)


def test_mha_shapes_and_grad():
    mha = MultiHeadAttention(32, 4, num_kv_heads=2, causal=True)
    mha.initialize(mx.init.Xavier())
    x = nd.array(np.random.RandomState(2).randn(2, 10, 32).astype(np.float32))
    out = mha(x)
    assert out.shape == (2, 10, 32)
    trainer = gluon.Trainer(mha.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    with autograd.record():
        loss = (mha(x) ** 2).sum()
    loss.backward()
    trainer.step(1)
    assert any(float((p.grad() ** 2).sum().asnumpy()) > 0
               for p in mha.collect_params().values())


def test_transformer_lm_trains_and_hybridizes():
    rng = np.random.RandomState(3)
    lm = TransformerLM(vocab_size=20, units=32, hidden_size=64,
                       num_layers=2, num_heads=4, max_len=32)
    lm.initialize(mx.init.Xavier())
    toks = nd.array(rng.randint(0, 20, (4, 16)).astype(np.float32))
    ref = lm(toks).asnumpy()
    assert ref.shape == (4, 16, 20)
    lm.hybridize()
    hyb = lm(toks).asnumpy()
    assert np.allclose(ref, hyb, atol=1e-4)
    # causality: changing a later token must not affect earlier logits
    toks2 = toks.asnumpy().copy()
    toks2[:, -1] = (toks2[:, -1] + 1) % 20
    out2 = lm(nd.array(toks2)).asnumpy()
    assert np.allclose(ref[:, :-1], out2[:, :-1], atol=1e-4)
    assert not np.allclose(ref[:, -1], out2[:, -1], atol=1e-4)


@pytest.mark.slow      # 85 s: ring attention on the 8-device CPU mesh
def test_transformer_sp_mesh_transparent():
    """Entering an sp mesh scope reroutes attention through ring
    attention with identical results — the long-context path."""
    lm = TransformerLM(vocab_size=16, units=32, hidden_size=64,
                       num_layers=2, num_heads=4, max_len=80)
    lm.initialize(mx.init.Xavier())
    toks = nd.array(np.random.RandomState(4).randint(0, 16, (1, 72))
                    .astype(np.float32))   # 72 % 8 != 0: auto-pad path
    dense = lm(toks).asnumpy()
    mesh = parallel.make_mesh(dp=1, sp=8)
    with parallel.mesh_scope(mesh):
        sharded = lm(toks).asnumpy()
    assert np.allclose(dense, sharded, atol=2e-4)


def test_transformer_lm_pays_nothing_for_the_flash_names(monkeypatch):
    """``TransformerLM`` has no checkpoint and keeps every activation:
    the names the flash forward gives its results are identities there.
    Its gradient holds one forward and one of each backward kernel a
    layer, as ever, and what the backward keeps of a layer's row
    statistics is the kernel's own lane-broadcast output, which dQ reads
    as it is — not a one-value-a-row copy beside or in place of it (that
    form is for a layer that says ``kept``: it costs a broadcast a
    layer).  371,417 bytes of residuals is the reading before the names
    (PR 34's tree, this block, these sizes)."""
    import collections

    import jax.numpy as jnp
    from conftest import kernel_calls
    from mxnet_tpu.ops.pallas_kernels import LANES
    from mxnet_tpu.parallel import attention
    from mxnet_tpu.parallel.trainer import pure_block_apply
    monkeypatch.setattr(attention, "_flash_eligible", lambda *a: True)
    layers, heads, batch, t = 2, 4, 2, 16
    lm = TransformerLM(vocab_size=64, units=32, hidden_size=64,
                       num_layers=layers, num_heads=heads, max_len=t,
                       dropout=0.0)
    lm.initialize(mx.init.Xavier())
    toks = np.random.RandomState(3).randint(0, 64, (batch, t)) \
        .astype(np.float32)
    lm(nd.array(toks))                  # materialise the deferred shapes
    apply = pure_block_apply(lm, list(lm.collect_params()), True)
    params = {n: p.data()._data for n, p in lm.collect_params().items()}

    def loss(p):
        return jnp.mean(apply(p, jax.random.PRNGKey(0),
                              jnp.asarray(toks)) ** 2)

    calls = kernel_calls(jax.make_jaxpr(jax.grad(loss))(params).jaxpr)
    assert calls == {"_flash_fwd_kernel": layers,
                     "_flash_bwd_dq_kernel": layers,
                     "_flash_bwd_dkv_kernel": layers}
    kept = [a for a in jax.tree_util.tree_leaves(jax.vjp(loss, params)[1])
            if hasattr(a, "nbytes")]
    shapes = collections.Counter(a.shape for a in kept)
    assert shapes[(batch * heads, t, LANES)] == layers
    assert shapes[(batch * heads, t)] == 0
    assert sum(a.nbytes for a in kept) <= 371417
