"""Block diffusion on the CPU (small sizes, float32, seeded weights):

- the three-part block mask: the einsum form and the three flash kernels
  in interpret mode — forward, dQ, dK/dV — against brute force over the
  boolean array, block lengths 4 and others, tiles of every case (full,
  on the diagonal, a block diagonal, skipped);
- the kernels' grids visit ``n (n + 2)`` of ``(2 n)^2`` tiles and hold
  where a row has none left, and the gauges say so;
- the noising operator against the reference's own noising for the same
  draws (ids and weights equal), and from the step's key;
- the fused head with a position's weight: the plain form's value and
  gradients, and the weight reaching them in float32, behind the
  bfloat16 products;
- ``MoELM(qk_norm, block_length)`` with ``diffusion_loss()`` against the
  plain reference ``perfbench/reference/sdar_moe_lm.py``: loss and every
  leaf's gradient, whole and walked in blocks, with the einsum attention
  and with the kernels;
- the eight shares of a 128-expert top-8 layer add up to the uncut
  reference layer;
- the sequence- and context-parallel paths refuse the mask in words, and
  a rotary with positions refuses a length that does not fit.
"""
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd, telemetry
from mxnet_tpu.gluon.contrib.transformer import FULL, MoELM
from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.ops.contrib import _linear_cross_entropy, _rotary_embedding
from mxnet_tpu.parallel import make_mesh
from mxnet_tpu.parallel.attention import (block_diffusion_mask,
                                          local_attention, ring_attention,
                                          ulysses_attention)
from mxnet_tpu.parallel.moe import routed_experts

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PB = os.path.join(ROOT, "perfbench")

CONFIG = {
    "vocab_size": 256, "hidden_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "moe_intermediate_size": 64,
    "num_experts": 2, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "num_hidden_layers": 2, "rms_norm_eps": 1e-6, "rope_theta": 1000000,
    "qk_norm": True, "block_length": 4, "noise_eps": 0.001,
    "mask_token_id": 255,
    "deployment": {"experts_held": [2, 4], "mask_experts_held": 0},
    "published": {"num_experts": 8},
    "init_std": 0.05, "embed_init_std": 0.05, "residual_init_std": 0.05,
    "seq_len": 64, "batch_size": 2}


@pytest.fixture(scope="module")
def ref():
    sys.path.insert(0, PB)
    try:
        spec = importlib.util.spec_from_file_location(
            "reference_sdar_moe_lm",
            os.path.join(PB, "reference", "sdar_moe_lm.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        yield mod
    finally:
        sys.path.remove(PB)


# ---------------------------------------------------------------------------
# the mask
# ---------------------------------------------------------------------------
def _brute(q, k, v, mask):
    s = jnp.einsum("bqd,bkd->bqk", q, k) * q.shape[-1] ** -0.5
    p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v)


def _by_hand(t, length):
    """The mask from the issue's words, row by row."""
    half = t // 2
    see = np.zeros((t, t), bool)
    for r in range(t):
        for c in range(t):
            br, bc = (r % half) // length, (c % half) // length
            if (r < half) == (c < half) and br == bc:
                see[r, c] = True            # a block sees itself
            elif r < half <= c and br > bc:
                see[r, c] = True            # noised: the clean blocks before
            elif r >= half and c >= half and br >= bc:
                see[r, c] = True            # clean: block-causal
    return see


@pytest.mark.parametrize("half,length", [(16, 4), (24, 6), (8, 8), (12, 1)])
def test_the_mask_is_the_three_part_definition(half, length):
    mask = np.asarray(block_diffusion_mask(2 * half, length))
    assert (mask == _by_hand(2 * half, length)).all()
    assert mask.sum() == half * half + half * length
    assert not mask[half:, :half].any()     # no clean row sees a noised one
    with pytest.raises(ValueError, match="whole blocks"):
        block_diffusion_mask(2 * half + 2, 4 if length != 1 else 8)


# (L, block length, tile rows): four tiles a half (every case: full, the
# two diagonals, the block diagonal, skipped), a block as long as a tile
# (the tile below the diagonal is visited and empty), a length that is no
# power of two, one tile a half, and tiles of two lane tiles, whose
# diagonals are worked in slabs and whose block diagonal in lane squares
@pytest.mark.parametrize("half,length,block", [
    (64, 4, 16), (64, 8, 16), (32, 8, 8), (48, 6, 24), (64, 4, 64),
    (512, 4, 256)])
def test_flash_kernels_match_brute_force_under_the_mask(half, length, block):
    t, d = 2 * half, 16
    rng = np.random.default_rng(half + length)
    q, k, v, do = (jnp.asarray(rng.standard_normal((2, t, d)), jnp.float32)
                   for _ in range(4))
    mask = block_diffusion_mask(t, length)
    flash = lambda q, k, v: pk.flash_attention(
        q, k, v, False, None, block, block, None, False, length)
    got, got_vjp = jax.vjp(flash, q, k, v)
    want, want_vjp = jax.vjp(lambda *a: _brute(*a, mask), q, k, v)
    assert float(jnp.abs(got - want).max()) <= 2e-6
    for name, g, w in zip("qkv", got_vjp(do), want_vjp(do)):
        assert float(jnp.abs(g - w).max()) <= 1e-5, name
    # a kept lse (a layer under _layer_keeps) moves no number
    kept = jax.vjp(lambda q, k, v: pk.flash_attention(
        q, k, v, False, None, block, block, None, True, length), q, k, v)
    assert (kept[0] == got).all()
    for g, w in zip(kept[1](do), got_vjp(do)):
        assert (g == w).all()


@pytest.mark.parametrize("half,length", [(32, 4), (24, 8)])
def test_einsum_attention_matches_brute_force_under_the_mask(half, length):
    t = 2 * half
    rng = np.random.default_rng(length)
    q = jnp.asarray(rng.standard_normal((2, t, 4, 8)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((2, t, 2, 8)), jnp.float32)
            for _ in range(2))
    fold = lambda a: jnp.repeat(a, 4 // a.shape[2], axis=2).transpose(
        0, 2, 1, 3).reshape(8, t, 8)
    mask = jnp.asarray(_by_hand(t, length))
    f = lambda q, k, v: local_attention(q, k, v, block_diffusion=length)
    g = lambda q, k, v: _brute(fold(q), fold(k), fold(v), mask).reshape(
        2, 4, t, 8).transpose(0, 2, 1, 3)
    got, got_vjp = jax.vjp(f, q, k, v)
    want, want_vjp = jax.vjp(g, q, k, v)
    assert float(jnp.abs(got - want).max()) <= 2e-6
    for a, b in zip(got_vjp(got), want_vjp(got)):
        assert float(jnp.abs(a - b).max()) <= 1e-5
    for bad in ({"causal": True}, {"window": 4, "causal": True},
                {"q_offset": 8}, {"kv_len": 8}):
        with pytest.raises(ValueError, match="mask of its own"):
            local_attention(q, k, v, block_diffusion=length, **bad)


def test_the_grids_visit_24_of_64_tiles_and_hold_after():
    """L = 4096 in 1024-row tiles: a Q tile's visits name its own noised
    tile, then the clean tiles up to its own; the steps after the last
    hold at it (no fetch).  Evaluated with plain ints, as graftkern does."""
    n = 4
    run = {"q": [], "k": []}
    for i in range(2 * n):
        seen, last = [], None
        for s in range(n + 1):
            tile, case, lo = pk._bd_q_visit(i, s, n)
            if case == pk._BD_NONE:
                assert tile == last     # held: nothing fetched
            else:
                seen.append((tile, case, lo))
            last = tile
        run["q"].append(seen)
    assert [len(v) for v in run["q"]] == [2, 3, 4, 5, 1, 2, 3, 4]
    assert run["q"][2] == [(2, pk._BD_SELF, 1), (4, pk._BD_FULL, 1),
                           (5, pk._BD_FULL, 1), (6, pk._BD_DIAG, 1)]
    assert run["q"][6] == [(4, pk._BD_FULL, 0), (5, pk._BD_FULL, 0),
                           (6, pk._BD_DIAG, 0)]
    for j in range(2 * n):
        seen, last = [], None
        for s in range(2 * n):
            tile, case, lo = pk._bd_k_visit(j, s, n)
            if case == pk._BD_NONE:
                assert tile == last
            else:
                seen.append((tile, case, lo))
            last = tile
        run["k"].append(seen)
    assert [len(v) for v in run["k"]] == [1, 1, 1, 1, 8, 6, 4, 2]
    assert run["k"][5] == [(1, pk._BD_DIAG, 1), (2, pk._BD_FULL, 1),
                           (3, pk._BD_FULL, 1), (5, pk._BD_DIAG, 0),
                           (6, pk._BD_FULL, 0), (7, pk._BD_FULL, 0)]
    # both orders visit the same 24 (Q tile, K tile) pairs
    pairs_q = {(i, t) for i, seen in enumerate(run["q"]) for t, _c, _l in seen}
    pairs_k = {(t, j) for j, seen in enumerate(run["k"]) for t, _c, _l in seen}
    assert pairs_q == pairs_k and len(pairs_q) == 24 == n * (n + 2)
    mask = _by_hand(64, 2)     # 8 tiles of 8 rows a side
    holds = {(i, j) for i in range(8) for j in range(8)
             if mask[8 * i:8 * i + 8, 8 * j:8 * j + 8].any()}
    assert holds == pairs_q
    for kernel in ("fwd", "dq", "dkv"):
        plan = pk._FLASH_PLANS[kernel](32, 8192, 8192, 128, 1024, 1024,
                                       block_diffusion=4)
        assert plan["grid"] == ((32, 8, 5) if kernel != "dkv"
                                else (32, 8, 8))
        assert pk._flash_blocks(8192, 8192, 128, jnp.bfloat16, kernel,
                                None, 4) == (1024, 1024)


def test_the_plans_refuse_what_the_mask_cannot_tile():
    with pytest.raises(ValueError, match="mask of its own"):
        pk.flash_fwd_plan(1, 64, 64, 16, 16, 16, True, block_diffusion=4)
    with pytest.raises(ValueError, match="whole blocks"):
        pk.flash_fwd_plan(1, 64, 64, 16, 16, 8, block_diffusion=4)
    with pytest.raises(ValueError, match="whole blocks"):
        pk.flash_bwd_dkv_plan(1, 48, 48, 16, 16, 16, block_diffusion=4)
    assert pk.flash_block_diffusion_ok(8192, 4, jnp.bfloat16)
    assert not pk.flash_block_diffusion_ok(8190, 4, jnp.bfloat16)
    assert not pk.flash_block_diffusion_ok(8192, 3, jnp.bfloat16)


def test_the_flash_gauges_carry_the_mask_and_its_tiles():
    telemetry.enable()
    try:
        a = jnp.ones((1, 128, 16), jnp.float32)
        jax.grad(lambda q: pk.flash_attention(
            q, a, a, False, None, 16, 16, None, False, 4).sum())(a)
        tiles = telemetry.gauge("mxnet_flash_mask_tiles")
        steps = telemetry.gauge("mxnet_flash_grid_steps")
        for kernel, grid in (("fwd", 8 * 5), ("dq", 8 * 5), ("dkv", 8 * 8)):
            own = {"kernel": kernel, "mask": "block_diffusion"}
            assert steps.labels(**own).value == grid
            assert [tiles.labels(which=w, **own).value
                    for w in ("all", "run", "visible")] == [64, 24, 24]
    finally:
        telemetry.disable()
    assert pk._bd_tiles(4, 16, 16) == (24, 20)   # a tile of one block


# ---------------------------------------------------------------------------
# the noise
# ---------------------------------------------------------------------------
def test_noising_matches_the_reference_for_the_same_draws(ref):
    rng = np.random.default_rng(5)
    ids = rng.integers(0, 256, (3, 64))
    u, t = ref.draws(ids, 4)
    assert u.shape == (3, 64) and t.shape == (3, 16)
    assert u.dtype == t.dtype == np.int32 and 0 <= u.min() \
        and u.max() < 2 ** 24
    again = ref.draws(ids.copy(), 4)
    assert (again[0] == u).all() and (again[1] == t).all()
    assert (ref.draws(ids + 1, 4)[0] != u).any()
    want_ids, want_w, masked = ref.noise(ids, u, t, CONFIG)
    got_ids, got_w = nd.contrib.block_diffusion_noise(
        nd.array(ids, dtype="int32"), nd.array(u, dtype="int32"),
        nd.array(t, dtype="int32"), block_length=4, mask_id=255, eps=0.001)
    assert (got_ids.asnumpy() == want_ids).all()
    assert (got_w.asnumpy() == want_w).all()
    # the rate is the formula's to within the grid, the weight m / p
    p = (1 - 1e-3) * (t / 2.0 ** 24) + 1e-3
    assert np.abs(1.0 / want_w[masked]
                  - np.repeat(p, 4, axis=1)[masked]).max() <= 2.0 ** -23
    assert (want_w[~masked] == 0).all() and (want_ids[masked] == 255).all()
    assert (want_ids[~masked] == ids[~masked]).all()
    assert 0.3 < masked.mean() < 0.7


def test_noising_draws_from_the_key_when_handed_no_draws():
    ids = nd.array(np.arange(512).reshape(2, 256) % 200, dtype="int32")
    mx.random.seed(7)
    a_ids, a_w = nd.contrib.block_diffusion_noise(ids, mask_id=255)
    b_ids, b_w = nd.contrib.block_diffusion_noise(ids, mask_id=255)
    masked = a_ids.asnumpy() == 255
    assert 0.2 < masked.mean() < 0.8 and (a_ids.asnumpy() != b_ids.asnumpy()
                                          ).any()
    w = a_w.asnumpy()
    assert ((w > 0) == masked).all() and (w[masked] >= 1.0).all()
    # one rate a block: the masked positions of a block weigh alike
    for row in w.reshape(-1, 4):
        assert len(set(row[row > 0])) <= 1
    with pytest.raises(Exception, match="whole number of blocks"):
        nd.contrib.block_diffusion_noise(ids, block_length=7, mask_id=1)


# ---------------------------------------------------------------------------
# the head under a position's weight
# ---------------------------------------------------------------------------
def _head_case(dtype, positions=48, units=64, vocab=96):
    k = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.normal(k[0], (2, positions, units), jnp.float32)
    w = 0.2 * jax.random.normal(k[1], (vocab, units), jnp.float32)
    y = jax.random.randint(k[2], (2, positions), 0, vocab)
    return x.astype(dtype), w.astype(dtype), y, k[3]


def test_the_weighted_head_is_the_plain_form_in_float32():
    x, w, y, key = _head_case(jnp.float32)
    u = jax.random.uniform(key, y.shape, minval=1e-3)
    pw = jnp.where(u < 0.5, 1.0 / u, 0.0)

    def fused(x, w, pw):
        return jnp.mean(_linear_cross_entropy(x, w, y, pw))

    def plain(x, w, pw):
        return jnp.mean(_linear_cross_entropy(x, w, y) * pw)

    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(fused, argnums=(0, 1, 2))(x, w, pw)
        want = jax.value_and_grad(plain, argnums=(0, 1, 2))(x, w, pw)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-6)
    for a, b in zip(got[1], want[1]):
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-5 * float(
            jnp.max(jnp.abs(b)))
    # no weight: the operator it was (the other losses' call)
    assert jnp.array_equal(_linear_cross_entropy(x, w, y, pw * 0 + 1),
                           _linear_cross_entropy(x, w, y))


@pytest.mark.parametrize("weight", [1.0038, 263.3, 973.0])
def test_a_positions_weight_reaches_the_gradients_in_float32(weight):
    """ONE position carries the step (a diffusion objective's ``m / p``
    can: 973 of a sum of 4096).  The weight multiplies behind the head's
    bfloat16 products, so the gradients are the unit weight's times it;
    rounded with the cotangent, as the TPU rounds a product's operands,
    1.0038 becomes 1 and every leaf's gradient is 0.4 % short."""
    x, w, y, _key = _head_case(jnp.bfloat16)
    at = jnp.zeros(y.shape, jnp.float32).at[0, 5].set(1.0)

    def grads(pw):
        return jax.grad(lambda x_, w_: jnp.sum(_linear_cross_entropy(
            x_, w_, y, pw)), argnums=(0, 1))(x.astype(jnp.float32), w)

    def norm(a):
        return float(jnp.linalg.norm(a.astype(jnp.float32)))

    one, heavy = grads(at), grads(weight * at)
    assert norm(heavy[0]) == pytest.approx(weight * norm(one[0]), rel=1e-6)
    # the head's own gradient takes the weight on the state, an operand
    # of its product: rounded element by element, not all at once
    assert norm(heavy[1]) == pytest.approx(weight * norm(one[1]), rel=2e-3)
    if weight == 1.0038:
        # the form this replaces, with the TPU's rounding written out
        logits = jnp.einsum("btu,vu->btv", x, w,
                            preferred_element_type=jnp.float32)
        d = (weight * at)[..., None] * (
            jax.nn.softmax(logits, -1) - jax.nn.one_hot(y, w.shape[0]))
        tilted = jnp.einsum("btv,vu->btu", d.astype(jnp.bfloat16), w,
                            preferred_element_type=jnp.float32)
        assert norm(tilted) < (weight - 0.002) * norm(one[0])


# ---------------------------------------------------------------------------
# block and objective against the reference
# ---------------------------------------------------------------------------
def _block(ref, config, weights):
    s = ref.sizes(config)
    net = MoELM(s["vocab"], units=s["units"], expert_width=s["expert_width"],
                layer_types=[FULL] * s["layers"], num_heads=s["heads"],
                num_kv_heads=s["kv_heads"], head_dim=s["head_dim"],
                num_routed=s["routed"], held=s["held"], top_k=s["top_k"],
                rope={FULL: {"rope_theta": s["rope_theta"]}},
                qk_norm=True, block_length=s["block"],
                mask_token_id=s["mask_id"], noise_eps=s["noise_eps"])
    net.initialize(mx.init.Zero(), ctx=mx.cpu())
    params = net.collect_params()
    assert len(list(params.values())) == len(weights)
    for (pname, p), (rname, w) in zip(params.items(), weights.items()):
        assert pname.endswith(rname) and tuple(p.shape) == w.shape
        p.set_data(nd.array(w))
    return net


def _against_reference(ref):
    weights = ref.init_weights(CONFIG, 2 ** 31 + 5)
    net = _block(ref, CONFIG, weights)
    ids = np.random.default_rng(3).integers(0, 256, (2, 64))
    u, t = ref.draws(ids, 4)
    packed = np.stack([ids, u, np.repeat(t, 4, axis=1)], axis=1)
    with autograd.record():
        states, weight = net(nd.array(packed, dtype="int32"))
        loss = net.diffusion_loss()(states, weight,
                                    nd.array(ids, dtype="int32")).mean()
    loss.backward()
    params = {k: jnp.asarray(v) for k, v in weights.items()}
    rows, w, clean = (jnp.asarray(a) for a in ref.noised_batch(ids, CONFIG))
    with jax.default_matmul_precision("highest"):
        want, grads = jax.value_and_grad(ref.loss_fn)(params, rows, w, clean,
                                                      CONFIG)
        walked, walked_grads = ref.loss_and_grads(params, rows, w, clean,
                                                  CONFIG)
    got = {r: p.grad().asnumpy()
           for p, r in zip(net.collect_params().values(), weights)}
    return {"loss": (float(loss.asnumpy()), float(want), float(walked)),
            "weight": (weight.asnumpy(), np.asarray(w)),
            "states": states.shape,
            "grads": (got, grads, walked_grads)}


@pytest.fixture(scope="module")
def against_reference(ref):
    return _against_reference(ref)


def _check(result):
    got, want, walked = result["loss"]
    assert got == pytest.approx(want, rel=2e-6)
    assert walked == pytest.approx(want, rel=2e-6)
    assert (result["weight"][0] == result["weight"][1]).all()
    assert result["states"] == (2, 64, 128)     # the noised half alone
    got, want, walked = result["grads"]
    assert set(got) == set(want) and len(want) == 2 * 12 + 3
    for name, g in want.items():
        scale = float(jnp.abs(g).max())
        assert scale > 0, name
        assert np.abs(got[name] - np.asarray(g)).max() <= 3e-5 * scale, name
        # the reference's walk in blocks is its whole loss's gradient
        assert np.abs(np.asarray(walked[name] - g)).max() <= 3e-5 * scale


def test_block_and_objective_match_the_reference(against_reference):
    _check(against_reference)


def test_block_and_objective_match_the_reference_through_the_kernels(
        ref, monkeypatch):
    """The same comparison with the flash kernels (interpret mode) in the
    einsum's place: L = 64 in 16-row tiles, four a half."""
    from mxnet_tpu.parallel import attention
    monkeypatch.setattr(attention, "_flash_eligible", lambda *a: True)
    monkeypatch.setattr(pk, "_FLASH_MAX_ROWS", 16)
    calls = []
    real = pk.flash_attention
    monkeypatch.setattr(pk, "flash_attention",
                        lambda *a: calls.append(a[3:]) or real(*a))
    _check(_against_reference(ref))
    assert calls and all(c == (False, None, None, None, None, True, 4)
                         for c in calls)


def test_a_causal_moelm_is_untouched_by_the_new_arguments():
    net = MoELM(64, units=32, expert_width=16, num_heads=4, num_kv_heads=2,
                num_routed=4, held=(0, 2), top_k=2, window=4)
    names = list(net.collect_params())
    assert not any("q_norm" in n or "k_norm" in n for n in names)
    assert len(names) == 2 * 10 + 3
    with pytest.raises(ValueError, match="mask_token_id"):
        MoELM(64, units=32, expert_width=16, num_heads=4, num_kv_heads=2,
              num_routed=4, top_k=2, block_length=4)
    with pytest.raises(ValueError, match="mask_token_id"):
        MoELM(64, units=32, expert_width=16, num_heads=4, num_kv_heads=2,
              num_routed=4, top_k=2, block_length=4, mask_token_id=64)


# ---------------------------------------------------------------------------
# the shares add up
# ---------------------------------------------------------------------------
def test_the_eight_shares_add_up_to_the_uncut_layer(ref):
    """128 routed / top-8 at small widths: each of the eight chips'
    16 experts' partial result, summed, is the whole reference layer."""
    rng = np.random.default_rng(11)
    u, f, routed, rows = 64, 32, 128, 192
    cfg = dict(CONFIG, hidden_size=u, moe_intermediate_size=f,
               num_experts_per_tok=8, published={"num_experts": routed})
    h = jnp.asarray(rng.normal(size=(1, rows, u)), jnp.float32)
    p = {"router_weight": jnp.asarray(rng.normal(size=(routed, u)) * 0.3,
                                      jnp.float32),
         "gate_weight": jnp.asarray(rng.normal(size=(routed, f, u)) * 0.2,
                                    jnp.float32),
         "up_weight": jnp.asarray(rng.normal(size=(routed, f, u)) * 0.2,
                                  jnp.float32),
         "down_weight": jnp.asarray(rng.normal(size=(routed, u, f)) * 0.2,
                                    jnp.float32)}
    with jax.default_matmul_precision("highest"):
        whole = ref.expert_layer(h, p, dict(
            cfg, num_experts=routed,
            deployment={"experts_held": [0, routed]}))
        total, loads = 0.0, []
        for chip in range(8):
            first = 16 * chip
            mine = tuple(p[k][first:first + 16] for k in
                         ("gate_weight", "up_weight", "down_weight"))
            part = routed_experts(h[0], p["router_weight"], mine, 8,
                                  (first, 16))
            share = ref.expert_layer(h, {**{k: p[k][first:first + 16]
                                            for k in ("gate_weight",
                                                      "up_weight",
                                                      "down_weight")},
                                         "router_weight":
                                             p["router_weight"]},
                                     dict(cfg, num_experts=16, deployment={
                                         "experts_held": [first,
                                                          first + 16]}))
            assert float(jnp.abs(part - share[0]).max()) <= 2e-5
            loads.append(float(jnp.abs(part).max()))
            total = total + part
    assert float(jnp.abs(total - whole[0]).max()) \
        <= 2e-5 * float(jnp.abs(whole).max())
    assert min(loads) > 0       # every chip's experts take some rows


# ---------------------------------------------------------------------------
# what refuses
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("attend", [ring_attention, ulysses_attention])
def test_sequence_parallel_paths_refuse_the_mask_in_words(attend):
    q = jnp.ones((1, 16, 2, 8), jnp.float32)
    mesh = make_mesh(sp=2, devices=jax.devices()[:2])
    with pytest.raises(NotImplementedError,
                       match="no block-diffusion mask.*another model"):
        attend(q, q, q, mesh=mesh, block_diffusion=4)
    # one member on the axis: the local form serves it
    alone = make_mesh(sp=1, devices=jax.devices()[:1])
    out = attend(q, q, q, mesh=alone, block_diffusion=4)
    assert out.shape == q.shape


def test_rotary_with_positions_turns_by_them_and_refuses_a_mismatch():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((1, 8, 2, 16)), jnp.float32)
    twice = jnp.concatenate([x, x], axis=1)
    pos = jnp.tile(jnp.arange(8), 2)
    got = _rotary_embedding(twice, pos, base=1e6)
    want = _rotary_embedding(x, base=1e6)
    assert float(jnp.abs(got[:, :8] - want).max()) == 0
    assert float(jnp.abs(got[:, 8:] - want).max()) == 0
    assert float(jnp.abs(_rotary_embedding(twice, base=1e6)[:, 8:]
                         - want).max()) > 1e-3
    got = nd.contrib.rotary_embedding(nd.array(np.asarray(twice)),
                                      nd.array(np.asarray(pos),
                                               dtype="int32"), base=1e6)
    assert np.abs(got.asnumpy()[:, 8:] - np.asarray(want)).max() == 0
    with pytest.raises(ValueError, match="positions hold"):
        _rotary_embedding(twice, jnp.arange(8), base=1e6)
