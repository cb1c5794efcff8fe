"""The sparse-expert LM on the CPU (small sizes, float32, seeded weights):

- ``MoELM`` against the plain reference ``perfbench/reference/moe_lm.py``:
  loss, last-position logits, every leaf's gradient;
- the shares add up: the four shares' expert outputs sum to the uncut
  reference layer's;
- no token is dropped: a router that sends every token to the same
  experts still matches the dense masked reference;
- the window in the flash kernels (interpret mode: forward, dQ, dK/dV)
  and in the einsum path against a mask built from positions, and
  ``window=None`` the call it has always been;
- YaRN's frequency table and cos / sin scale against the closed form;
- a stacked ``(E, F, U)`` leaf rides a native ZeRO bucket and updates to
  the values E separate 2-D leaves take.
"""
import importlib.util
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd
from mxnet_tpu.gluon.contrib.transformer import MoELM, moe_lm_forward
from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.ops.contrib import _rotary_embedding, yarn_inv_freq
from mxnet_tpu.parallel.attention import (local_attention, ring_attention,
                                          ulysses_attention)
from mxnet_tpu.parallel.moe import routed_experts

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PB = os.path.join(ROOT, "perfbench")

YARN = {"rope_type": "yarn", "rope_theta": 500000, "factor": 4,
        "original_max_position_embeddings": 32, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 0.1 * math.log(4) + 1}
CONFIG = {
    "vocab_size": 256, "hidden_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "moe_intermediate_size": 64,
    "num_experts": 2, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "num_hidden_layers": 4, "sliding_window": 24, "rms_norm_eps": 1e-6,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "rope_parameters": {"full_attention": YARN, "sliding_attention": {
        "rope_type": "default", "rope_theta": 500000}},
    "deployment": {"experts_held": [2, 4]}, "published": {"num_experts": 8},
    "init_std": 0.05, "embed_init_std": 0.05, "residual_init_std": 0.05, "seq_len": 96,
    "batch_size": 2}


@pytest.fixture(scope="module")
def ref():
    sys.path.insert(0, PB)
    try:
        spec = importlib.util.spec_from_file_location(
            "reference_moe_lm", os.path.join(PB, "reference", "moe_lm.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        yield mod
    finally:
        sys.path.remove(PB)


def _block(ref, config, weights):
    s = ref.sizes(config)
    net = MoELM(s["vocab"], units=s["units"], expert_width=s["expert_width"],
                layer_types=ref.layer_kinds(config), num_heads=s["heads"],
                num_kv_heads=s["kv_heads"], head_dim=s["head_dim"],
                num_routed=s["routed"], held=s["held"], top_k=s["top_k"],
                window=s["window"], rope=config["rope_parameters"])
    net.initialize(mx.init.Zero(), ctx=mx.cpu())
    params = net.collect_params()
    assert len(list(params.values())) == len(weights)
    for (pname, p), (rname, w) in zip(params.items(), weights.items()):
        assert pname.endswith(rname) and tuple(p.shape) == w.shape
        p.set_data(nd.array(w))
    return net


@pytest.fixture(scope="module")
def against_reference(ref):
    weights = ref.init_weights(CONFIG, 2 ** 31 + 5)
    net = _block(ref, CONFIG, weights)
    tokens = np.random.default_rng(3).integers(0, 256, (2, 97))
    x, y = tokens[:, :-1], tokens[:, 1:]
    with autograd.record():
        states = net(nd.array(x, dtype="int32"))
        loss = net.lm_loss()(states, nd.array(y.astype("f"))).mean()
    loss.backward()
    params = {k: jnp.asarray(v) for k, v in weights.items()}
    with jax.default_matmul_precision("highest"):
        want, grads = jax.value_and_grad(ref.loss_fn)(
            params, jnp.asarray(x), jnp.asarray(y), CONFIG)
        walked, walked_grads = ref.loss_and_grads(
            params, jnp.asarray(x), jnp.asarray(y), CONFIG)
        logits = ref.forward(params, jnp.asarray(x), jnp.asarray(y),
                             CONFIG)[0]
    got = {r: p.grad().asnumpy()
           for p, r in zip(net.collect_params().values(), weights)}
    return {"loss": (float(loss.asnumpy()), float(want), float(walked)),
            "logits": (net.logits(states).asnumpy()[:, -1],
                       np.asarray(logits)[:, -1]),
            "grads": (got, grads, walked_grads)}


def test_moe_lm_loss_matches_the_reference(against_reference):
    got, want, walked = against_reference["loss"]
    assert got == pytest.approx(want, rel=2e-6)
    assert walked == pytest.approx(want, rel=2e-6)


def test_moe_lm_last_position_logits_match_the_reference(against_reference):
    got, want = against_reference["logits"]
    assert np.abs(got - want).max() <= 2e-5 * np.abs(want).max()


def test_moe_lm_every_leafs_gradient_matches_the_reference(
        against_reference):
    got, want, walked = against_reference["grads"]
    assert set(got) == set(want) and len(want) == 4 * 10 + 3
    for name, g in want.items():
        scale = float(jnp.abs(g).max())
        assert scale > 0, name
        assert np.abs(got[name] - np.asarray(g)).max() <= 2e-5 * scale, name
        # the reference's walk in blocks is its whole loss's gradient
        assert np.abs(np.asarray(walked[name] - g)).max() <= 2e-5 * scale


# ---------------------------------------------------------------------------
# the expert layer alone
# ---------------------------------------------------------------------------
def _expert_layer(ref, rng, held, routed=8, tokens=96, bias=None):
    cfg = dict(CONFIG, num_experts=held[1] - held[0],
               deployment={"experts_held": list(held)},
               published={"num_experts": routed})
    u, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    h = jnp.asarray(rng.normal(size=(1, tokens, u)), jnp.float32)
    router = rng.normal(size=(routed, u)) * 0.3
    if bias is not None:
        h = h.at[..., 0].set(1.0)
        router[:, 0] = bias
    whole = {"router_weight": jnp.asarray(router, jnp.float32),
             "gate_weight": jnp.asarray(rng.normal(size=(routed, f, u)) * .1,
                                        jnp.float32),
             "up_weight": jnp.asarray(rng.normal(size=(routed, f, u)) * .1,
                                      jnp.float32),
             "down_weight": jnp.asarray(rng.normal(size=(routed, u, f)) * .1,
                                        jnp.float32)}
    return cfg, h, whole


def _share(whole, first, end):
    return {k: v if k == "router_weight" else v[first:end]
            for k, v in whole.items()}


def _program(h, p, held, top_k=2):
    return routed_experts(
        h[0], p["router_weight"],
        (p["gate_weight"], p["up_weight"], p["down_weight"]), top_k,
        (held[0], held[1] - held[0]))[None]


@pytest.fixture(params=["einsum", "pallas", "pallas+movers"])
def product(request, monkeypatch):
    """The expert layer in its forms, over tiles small enough that an
    expert's rows span several: the plain product and gathers the CPU
    takes; the product's kernels (interpret mode here); and what the TPU
    takes, the product's kernels between the row movers of dispatch and
    combine — which leave the tiles past the used ones unwritten, so
    they go with the product that skips those tiles."""
    from mxnet_tpu.parallel import moe
    monkeypatch.setattr(pk, "GROUPED_TILE_ROWS", 16)
    if request.param != "einsum":
        monkeypatch.setattr(moe, "_tile_product", pk.grouped_matmul)
    if request.param == "pallas+movers":
        _movers_on(monkeypatch)
    return request.param


def _movers_on(monkeypatch):
    from mxnet_tpu.parallel import moe
    monkeypatch.setattr(moe, "_movers_run", pk.row_words_ok)


def test_the_shares_add_up(ref, product):
    """Four chips' partial results — each its own two experts of eight,
    every one under the whole router — sum to the uncut reference
    layer's output; the router is counted once (it adds nothing of its
    own)."""
    rng = np.random.default_rng(11)
    cfg, h, whole = _expert_layer(ref, rng, (0, 8))
    with jax.default_matmul_precision("highest"):
        uncut = ref.expert_layer(h, whole, cfg)
        parts, ref_parts = [], []
        for first in range(0, 8, 2):
            share_cfg = dict(cfg, num_experts=2,
                             deployment={"experts_held": [first, first + 2]})
            p = _share(whole, first, first + 2)
            parts.append(_program(h, p, (first, first + 2)))
            ref_parts.append(ref.expert_layer(h, p, share_cfg))
    scale = float(jnp.abs(uncut).max())
    assert float(jnp.abs(sum(parts) - uncut).max()) <= 1e-5 * scale
    assert float(jnp.abs(sum(ref_parts) - uncut).max()) <= 1e-5 * scale
    for got, want in zip(parts, ref_parts):
        assert float(jnp.abs(got - want).max()) <= 1e-5 * scale


def test_no_token_is_dropped(ref, product):
    """Every token picks experts 2 and 5 (a router biased that way): all
    of a step's assignments to one held expert, none to the other, and
    the result and its gradients are still the dense masked reference's
    — nothing is cut off at a capacity."""
    rng = np.random.default_rng(12)
    bias = np.zeros(8)
    bias[[2, 5]] = 40.0
    cfg, h, whole = _expert_layer(ref, rng, (2, 4), bias=bias)
    p = _share(whole, 2, 4)

    def got(h_, p_):
        return jnp.sum(jnp.sin(_program(h_, p_, (2, 4))))

    def want(h_, p_):
        return jnp.sum(jnp.sin(ref.expert_layer(h_, p_, cfg)))

    with jax.default_matmul_precision("highest"):
        a, ga = jax.value_and_grad(got, (0, 1))(h, p)
        b, gb = jax.value_and_grad(want, (0, 1))(h, p)
        rows, _ = ref.routing_stats(h, p["router_weight"], cfg)
    assert list(np.asarray(rows)) == [96, 0]
    assert float(a) == pytest.approx(float(b), rel=1e-5)
    for x, y in zip(jax.tree_util.tree_leaves(ga),
                    jax.tree_util.tree_leaves(gb)):
        assert float(jnp.abs(x - y).max()) \
            <= 2e-5 * max(float(jnp.abs(y).max()), 1e-3)


def test_the_grouped_product_is_the_kernel_where_it_is_asked_for():
    """``grouped_matmul`` instantiates the named kernels (forward, and
    both backward products); tiles past the used ones come out zero."""
    from mxnet_tpu import telemetry
    rng = np.random.default_rng(13)
    x = jnp.asarray(rng.normal(size=(64, 128)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(3, 32, 128)), jnp.float32)
    tile_group = jnp.asarray([0, 0, 1, 2, 2, 2, 2, 2], jnp.int32)
    used = jnp.asarray([4], jnp.int32)
    telemetry.enable()
    try:
        calls = telemetry.counter("mxnet_pallas_kernel_calls_total")
        before = {k: calls.labels(kernel=k).value
                  for k in ("grouped_matmul_fwd", "grouped_matmul_bwd")}
        y, vjp = jax.vjp(lambda a, b: pk.grouped_matmul(
            a, b, tile_group, used, 8), x, w)
        dx, dw = vjp(jnp.ones_like(y))
        for k, n in before.items():
            assert calls.labels(kernel=k).value == n + 1
    finally:
        telemetry.disable()
    want = jnp.einsum("itc,ioc->ito", x.reshape(8, 8, 128), w[tile_group])
    want = want.at[4:].set(0).reshape(64, 32)
    assert float(jnp.abs(y - want).max()) < 1e-4
    assert float(jnp.abs(dx[32:]).max()) == 0.0
    dw_want = jnp.stack([x[r].sum(0)[None, :].repeat(32, 0) for r in
                         (slice(0, 16), slice(16, 24), slice(24, 32))])
    assert float(jnp.abs(dw - dw_want).max()) < 1e-4


# ---------------------------------------------------------------------------
# the row movers of dispatch and combine
# ---------------------------------------------------------------------------
def _table(case):
    """``(experts (T, k), held, published)`` of a hand-made routing."""
    if case == "no_row_and_every_row":      # expert 2 every token, 3 none
        return np.tile([2, 5], (40, 1)), (2, 2), 8
    if case == "0_and_k_held_slots":        # tokens with 2, 1 and 0 held
        return np.array([[0, 1], [4, 5], [0, 4], [5, 1]] * 9), (0, 2), 8
    if case == "whole_layer":               # held = (0, E): every slot
        rng = np.random.default_rng(21)
        return np.stack([rng.permutation(4)[:2] for _ in range(40)]), \
            (0, 4), 4
    if case == "one_valid_row_in_the_last_tile":    # 17 rows, tiles of 16
        return np.tile([0, 3], (17, 1)), (0, 2), 4
    if case == "three_tiles_and_three_empty":   # 40 rows for 1; 2 - 4 none
        return np.stack([[1, 5 + t % 3] for t in range(40)]), (1, 4), 8
    raise KeyError(case)


MOVER_CASES = ["no_row_and_every_row", "0_and_k_held_slots", "whole_layer",
               "one_valid_row_in_the_last_tile",
               "three_tiles_and_three_empty"]


def _layout_oracle(experts, held, tm):
    """``moe._layout``'s tables by a plain numpy walk: the held
    assignments sorted by expert (ties in assignment order), each
    expert's run from a whole tile on and padded to whole tiles (one
    tile where it has no row)."""
    first, count = held
    t, k = experts.shape
    flat = experts.reshape(-1) - first
    is_held = (flat >= 0) & (flat < count)
    order = np.argsort(np.where(is_held, flat, count), kind="stable")
    rank = np.argsort(order)
    tiles = -(-t * k // tm) + count
    src, valid = np.zeros(tiles * tm, int), np.zeros(tiles * tm, bool)
    dst, tile_group = np.zeros(t * k, int), np.full(tiles, count - 1)
    counts, starts, row0, sizes = np.zeros(tiles, int), [], [], []
    row = start = 0
    for e in range(count):
        mine = [j for j in order if is_held[j] and flat[j] == e]
        starts.append(start), row0.append(row), sizes.append(len(mine))
        span = max(-(-len(mine) // tm), 1)
        tile_group[row // tm:row // tm + span] = e
        for r, j in enumerate(mine):
            src[row + r], valid[row + r], dst[j] = j, True, row + r
            counts[(row + r) // tm] += 1
        start, row = start + len(mine), row + span * tm
    return (src, valid, dst.reshape(t, k), is_held.reshape(t, k),
            tile_group, np.array([row // tm]), counts, order, rank,
            np.array([starts, row0, sizes]))


@pytest.mark.parametrize("case", MOVER_CASES)
def test_the_layout_is_the_sorted_padded_buffer(case):
    """Every table ``_layout`` makes with no gather — shifts, selects and
    sorts — equals the plain walk's, element for element."""
    from mxnet_tpu.parallel import moe
    experts, held, _ = _table(case)
    got = moe._layout(jnp.asarray(experts, jnp.int32), held, 16)
    for name, a, b in zip(("src", "valid", "dst", "is_held", "tile_group",
                           "used", "counts", "order", "rank", "runs"),
                          got, _layout_oracle(experts, held, 16)):
        assert np.array_equal(np.asarray(a), b), name


@pytest.mark.parametrize("case", MOVER_CASES)
def test_the_weights_and_dots_move_as_the_gathers_did(case):
    """The combine's transpose: each buffer row's weight (a sort keyed by
    ``rank``, then a shift an expert) and each assignment's dot (a shift
    back, then a sort keyed by ``order``) are the gathers ``w[src]`` and
    ``dots[dst]`` bit for bit."""
    from mxnet_tpu.parallel import moe
    experts, held, _ = _table(case)
    src, valid, dst, is_held, _, _, _, order, rank, runs = moe._layout(
        jnp.asarray(experts, jnp.int32), held, 16)
    rng = np.random.default_rng(23)
    w = rng.uniform(.1, 1, experts.size).astype(np.float32)
    dots = rng.normal(size=src.shape[0]).astype(np.float32)
    scale = moe._to_buffer(moe._to_places(rank, jnp.asarray(w)), runs,
                           src.shape[0])
    gw = moe._to_places(order, moe._from_buffer(jnp.asarray(dots), runs,
                                                experts.size))
    held_ = np.asarray(is_held).reshape(-1)
    assert np.array_equal(np.asarray(scale),
                          np.where(valid, w[np.asarray(src)], 0))
    assert np.array_equal(np.where(held_, np.asarray(gw), 0), np.where(
        held_, dots[np.asarray(dst).reshape(-1)], 0))


@pytest.mark.parametrize("case", MOVER_CASES + ["tiles_of_128_tokens"])
def test_the_slot_fetch_is_the_gathered_table(case):
    """``moe_slots``' fetch table, one stable sort a token tile, against
    the table the gather made (``take_along_axis`` by each tile's running
    count of held slots): the same held count a tile, and the same codes
    in the same order wherever the kernel reads them."""
    from mxnet_tpu.parallel import moe
    if case == "tiles_of_128_tokens":
        rng = np.random.default_rng(24)
        experts, held = np.stack([rng.permutation(16)[:8]
                                  for _ in range(512)]), (4, 4)
    else:
        experts, held, _ = _table(case)
    _, _, dst, is_held, *_ = moe._layout(jnp.asarray(experts, jnp.int32),
                                         held, 16)
    rows = jnp.where(is_held, dst, -1).astype(jnp.int32)
    t, k = rows.shape
    bt = pk._pick_block(t, pk._SLOT_TILE_TOKENS)
    bits = (bt * k - 1).bit_length()
    flat = rows.reshape(t // bt, bt * k)
    seen = jnp.cumsum(flat >= 0, axis=1, dtype=jnp.int32)
    order = jnp.minimum(jnp.sum(
        seen[:, None, :] <= jnp.arange(bt * k, dtype=jnp.int32)[:, None],
        axis=-1, dtype=jnp.int32), bt * k - 1)
    want = (jnp.take_along_axis(flat, order, axis=1) << bits) \
        | ((order % k) * bt + order // k)
    held_, fetch = pk._slot_fetch(rows, bt, k)
    assert np.array_equal(np.asarray(held_), np.asarray(seen[:, -1]))
    for n, a, b in zip(np.asarray(held_), np.asarray(fetch),
                       np.asarray(want)):
        assert np.array_equal(a[:n], b[:n])


def _moved(case, dtype, units, movers, monkeypatch):
    """Dispatch, then combine, over one routing: values, and the
    gradients of both ``custom_vjp``s (the weights' among them)."""
    from mxnet_tpu.parallel import moe
    experts, held, _ = _table(case)
    t, k = experts.shape
    tables = moe._layout(jnp.asarray(experts, jnp.int32), held, 16)
    src, _, dst, is_held, _, used, counts, order, rank, runs = tables
    rng = np.random.default_rng(22)
    x = jnp.asarray(rng.normal(size=(t, units)), dtype)
    w = jnp.where(is_held, jnp.asarray(rng.uniform(.1, 1, (t, k)),
                                       jnp.float32), 0)
    mix = jnp.asarray(rng.normal(size=(units,)), jnp.float32)
    if movers:
        _movers_on(monkeypatch)

    def both(x_, w_):
        rows, again = moe._dispatch(x_, src // k, counts, used, dst,
                                    is_held)
        y = (jnp.tanh(rows.astype(jnp.float32)) * mix
             + again.astype(jnp.float32) / 4).astype(dtype)
        out = moe._combine(y, w_, src, counts, used, dst, is_held, order,
                           rank, runs)
        return jnp.sum(jnp.sin(out.astype(jnp.float32))), (rows, out)

    (_, (rows, out)), (gx, gw) = jax.value_and_grad(
        both, (0, 1), has_aux=True)(x, w)
    return {"rows": rows[:int(used[0]) * 16], "out": out, "gx": gx,
            "gw": jnp.where(is_held, gw, 0)}


@pytest.mark.parametrize("dtype,units", [("float32", 128),
                                         ("bfloat16", 256)])
@pytest.mark.parametrize("case", MOVER_CASES)
def test_the_movers_are_the_gathers(case, dtype, units, monkeypatch):
    """Each mover against its plain ``jnp`` form: the rows of the used
    tiles bit for bit, the tokens and every gradient to float32's (or
    one bfloat16's) rounding — an expert with no row and one with every
    row, tokens with no and with ``k`` held slots, the whole layer on
    the chip, a last tile with one valid row."""
    want = _moved(case, dtype, units, False, monkeypatch)
    got = _moved(case, dtype, units, True, monkeypatch)
    assert np.array_equal(np.asarray(got["rows"], "f"),
                          np.asarray(want["rows"], "f"))
    tol = 1e-6 if dtype == "float32" else 2 ** -8
    for name in ("out", "gx", "gw"):
        a, b = (np.asarray(v[name], "f") for v in (got, want))
        assert np.all(np.isfinite(a)), name
        assert np.abs(a - b).max() <= tol * max(np.abs(b).max(), 1.0), name


def test_the_movers_are_the_kernels_where_they_are_asked_for(monkeypatch):
    from mxnet_tpu import telemetry
    telemetry.enable()
    try:
        calls = telemetry.counter("mxnet_pallas_kernel_calls_total")
        before = {k: calls.labels(kernel=k).value
                  for k in ("moe_rows", "moe_slots")}
        _moved("0_and_k_held_slots", "float32", 128, True, monkeypatch)
        # dispatch and the combine's transpose; combine and dispatch's
        assert calls.labels(kernel="moe_rows").value \
            == before["moe_rows"] + 2
        assert calls.labels(kernel="moe_slots").value \
            == before["moe_slots"] + 2
    finally:
        telemetry.disable()
    assert pk.row_words_ok(2304, jnp.bfloat16)
    assert pk.row_words_ok(128, jnp.float32)
    assert not pk.row_words_ok(128, jnp.bfloat16)   # half a lane tile
    assert not pk.row_words_ok(256, jnp.float16)


def test_nothing_reads_the_tiles_past_the_used_ones(ref, monkeypatch):
    """The movers leave the buffer's tiles past ``used`` unwritten.
    Filled with NaN — the rows that dispatch and the combine's
    transpose make, and the rows that combine and dispatch's transpose
    read — the layer's output and every gradient are finite and the
    same: the products skip those tiles and no held slot points into
    one."""
    from mxnet_tpu.parallel import moe
    monkeypatch.setattr(pk, "GROUPED_TILE_ROWS", 16)
    monkeypatch.setattr(moe, "_tile_product", pk.grouped_matmul)
    _movers_on(monkeypatch)
    rng = np.random.default_rng(14)
    cfg, h, whole = _expert_layer(ref, rng, (2, 4))
    p = _share(whole, 2, 4)

    def loss(h_, p_):
        return jnp.sum(jnp.sin(_program(h_, p_, (2, 4))))

    with jax.default_matmul_precision("highest"):
        want = jax.value_and_grad(loss, (0, 1))(h, p)

    def poisoned(rows, used, tm):
        past = jnp.arange(rows.shape[0]) >= used[0] * tm
        return jnp.where(past[:, None], jnp.nan, rows)

    def rows_then_nan(x, src_token, counts, used, tm, scale=None, y=None):
        out = pk.moe_rows(x, src_token, counts, used, tm, scale, y)
        if y is None:
            return poisoned(out, used, tm)
        return poisoned(out[0], used, tm), out[1]

    def nan_then_slots(ys, dst, w, is_held, used, tm):
        assert int(used[0]) * tm < ys[0].shape[0]   # there ARE such tiles
        return pk.moe_slots(tuple(poisoned(y, used, tm) for y in ys), dst,
                            w, is_held, used, tm)

    monkeypatch.setattr(moe, "_move_rows", rows_then_nan)
    monkeypatch.setattr(moe, "_move_slots", nan_then_slots)
    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(loss, (0, 1))(h, p)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.all(np.isfinite(np.asarray(a)))
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_a_tiles_count_is_its_valid_rows():
    from mxnet_tpu.parallel import moe
    for case in MOVER_CASES:
        experts, held, _ = _table(case)
        _, valid, _, _, tile_group, used, counts, *_ = moe._layout(
            jnp.asarray(experts, jnp.int32), held, 16)
        by_tile = np.asarray(valid).reshape(-1, 16)
        assert list(np.asarray(counts)) == list(by_tile.sum(1))
        # a tile's valid rows are a prefix, and none lies past ``used``
        for n, row in zip(np.asarray(counts), by_tile):
            assert row[:n].all() and not row[n:].any()
        assert not np.asarray(counts)[int(used[0]):].any()


def test_the_dense_models_never_reach_the_movers(monkeypatch):
    """``TransformerLM`` and ``LoopedLM`` (the two accepted LM cells'
    blocks) trace with every entry of the expert layer poisoned: the
    movers are new functions no dense path calls."""
    from mxnet_tpu.gluon.contrib.transformer import LoopedLM, TransformerLM
    from mxnet_tpu.parallel import moe

    def never(*args, **kwargs):
        raise AssertionError("the expert layer was reached")

    for mod, names in ((moe, ("routed_experts", "_move_rows", "_move_slots",
                              "_layout")),
                       (pk, ("moe_rows", "moe_slots", "_row_words",
                             "grouped_matmul"))):
        for name in names:
            monkeypatch.setattr(mod, name, never)
    tokens = nd.array(np.random.default_rng(6).integers(0, 64, (2, 16)),
                      dtype="int32")
    for net in (TransformerLM(64, units=32, hidden_size=64, num_layers=2,
                              num_heads=2, max_len=16),
                LoopedLM(64, units=32, hidden_size=64, num_layers=2,
                         num_heads=2)):
        net.initialize(mx.init.Normal(0.1), ctx=mx.cpu())
        with autograd.record():
            out = net(tokens)
            out = out[0] if isinstance(out, (list, tuple)) else out
            loss = out.sum()
        loss.backward()
        assert np.isfinite(float(loss.asnumpy()))
    # and the executor's step (the ResNet cell's trainer)
    data = mx.sym.Variable("data")
    net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        data, num_hidden=4, name="fc"), name="softmax")
    exe = net.simple_bind(mx.cpu(), data=(2, 8), grad_req="write")
    exe.forward(is_train=True, data=nd.ones((2, 8)))
    exe.backward()
    assert np.isfinite(exe.grad_dict["fc_weight"].asnumpy()).all()


def test_the_gauges_give_the_shapes_the_movers_fetch_from():
    """Cell 4's layer: 8192 tokens, 8 slots each, 16 of 64 experts held —
    16,384 rows expected of a buffer of 71,808 (187 tiles of 384) and of
    65,536 slot rows."""
    from mxnet_tpu import telemetry
    net = MoELM(64, units=32, expert_width=16, num_heads=4, num_kv_heads=2,
                num_routed=64, held=(0, 16), top_k=8, window=4)
    telemetry.enable()
    try:
        assert net.expected_rows(8192) == 16384
        read = {name: telemetry.gauge(name).labels().value for name in (
            "mxnet_moe_expected_rows", "mxnet_moe_buffer_rows",
            "mxnet_moe_slot_rows")}
    finally:
        telemetry.disable()
    assert read == {"mxnet_moe_expected_rows": 16384,
                    "mxnet_moe_buffer_rows": 187 * 384,
                    "mxnet_moe_slot_rows": 65536}


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------
def _masked_attention(q, k, v, window):
    """Softmax attention under a mask built from positions."""
    t, d = q.shape[1], q.shape[-1]
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    see = (j <= i) if window is None else (j <= i) & (i - j < window)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(d)
    p = jax.nn.softmax(jnp.where(see[None, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _qkv(t, heads=2, d=32, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.normal(size=(1, t, heads, d)), jnp.float32)
                 for _ in range(3))


def _fold(a):
    b, t, h, d = a.shape
    return jnp.transpose(a, (0, 2, 1, 3)).reshape(b * h, t, d)


WINDOWS = [  # T, window, block_q, block_k
    (512, 100, 128, 128),     # several blocks, window not a block multiple
    (512, 1, 128, 128),       # a query sees itself alone
    (384, 130, 128, 128),     # just over a block
    (512, 200, 256, 128),     # blocks that are not square
    (512, 128, None, None),   # blocks picked from the shape (one block)
    (256, 256, 64, 64),       # window = T: the causal mask
    (256, 1000, 64, 64)]      # window > T


@pytest.mark.parametrize("t,window,bq,bk", WINDOWS)
@pytest.mark.parametrize("wrt", ["forward", "dq", "dkv"])
def test_flash_kernels_mask_by_the_window(t, window, bq, bk, wrt):
    q, k, v = _qkv(t)

    def flash(q_, k_, v_):
        o = pk.flash_attention(_fold(q_), _fold(k_), _fold(v_), True, None,
                               bq, bk, window)
        return o if wrt == "forward" else jnp.sum(jnp.sin(o))

    def masked(q_, k_, v_):
        o = _fold(_masked_attention(q_, k_, v_, window))
        return o if wrt == "forward" else jnp.sum(jnp.sin(o))

    if wrt == "forward":
        got, want = [flash(q, k, v)], [masked(q, k, v)]
    else:
        args = (0,) if wrt == "dq" else (1, 2)
        got = jax.grad(flash, args)(q, k, v)
        want = jax.grad(masked, args)(q, k, v)
    for a, b in zip(got, want):
        assert float(jnp.abs(a - b).max()) < 5e-5


@pytest.mark.parametrize("t,window", [(200, 37), (96, 1), (64, 500)])
def test_einsum_attention_masks_by_the_window(t, window):
    q, k, v = _qkv(t, seed=1)
    fn = lambda *a: local_attention(*a, causal=True, impl="einsum",
                                    window=window)
    want_fn = lambda *a: _masked_attention(*a, window)
    assert float(jnp.abs(fn(q, k, v) - want_fn(q, k, v)).max()) < 2e-6
    got = jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a))), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(jnp.sin(want_fn(*a))),
                    (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert float(jnp.abs(a - b).max()) < 5e-6


def test_no_window_is_the_call_it_has_always_been():
    """``window=None`` — and a window no shorter than the sequence —
    traces to the causal call's own program and gives its bits."""
    q, k, v = (_fold(a) for a in _qkv(256, seed=2))
    causal = lambda *a: pk.flash_attention(*a, True)
    none = lambda *a: pk.flash_attention(*a, True, None, None, None, None)
    wide = lambda *a: pk.flash_attention(*a, True, None, None, None, 256)
    step = lambda f: jax.grad(lambda *a: jnp.sum(jnp.sin(f(*a))), (0, 1, 2))
    text = str(jax.make_jaxpr(step(causal))(q, k, v))
    assert str(jax.make_jaxpr(step(none))(q, k, v)) == text
    assert str(jax.make_jaxpr(step(wide))(q, k, v)) == text
    assert "window" not in text
    assert np.array_equal(np.asarray(causal(q, k, v)),
                          np.asarray(wide(q, k, v)))
    x, kk, vv = _qkv(64, seed=3)
    assert np.array_equal(
        np.asarray(local_attention(x, kk, vv, causal=True, impl="einsum")),
        np.asarray(local_attention(x, kk, vv, causal=True, impl="einsum",
                                   window=None)))


def test_a_window_holds_the_index_maps_at_both_edges():
    """Blocks wholly outside the band are not fetched: the K side of the
    Q-major kernels holds at the first and last block a row needs, the Q
    side of dK/dV at the first and last block a column needs."""
    plan = pk.flash_fwd_plan(1, 1024, 1024, 64, 128, 128, True,
                             jnp.float32, 200)
    kmap = plan["in_specs"][1].index_map
    # rows 640..767 see keys 441..767: K blocks 3..5
    assert [kmap(0, 5, j)[1] for j in range(8)] == [3, 3, 3, 3, 4, 5, 5, 5]
    plan = pk.flash_bwd_dkv_plan(1, 1024, 1024, 64, 128, 128, True,
                                 jnp.float32, 200)
    qmap = plan["in_specs"][0].index_map
    # keys 256..383 are seen by rows 256..582: Q blocks 2..4
    assert [qmap(0, 2, i)[1] for i in range(8)] == [2, 2, 2, 3, 4, 4, 4, 4]
    # no window: the causal clamp alone
    plain = pk.flash_bwd_dkv_plan(1, 1024, 1024, 64, 128, 128, True)
    assert [plain["in_specs"][0].index_map(0, 2, i)[1]
            for i in range(8)] == [2, 2, 2, 3, 4, 5, 6, 7]


def test_a_window_needs_the_causal_mask_and_one_chip():
    q, k, v = _qkv(64)
    with pytest.raises(ValueError, match="causal"):
        local_attention(q, k, v, causal=False, window=8)
    with pytest.raises(ValueError, match="at least 1"):
        pk.flash_attention(_fold(q), _fold(k), _fold(v), True, None, None,
                           None, 0)
    from mxnet_tpu.parallel.mesh import make_mesh
    mesh = make_mesh(sp=2, devices=jax.devices()[:2])
    for fn in (ring_attention, ulysses_attention):
        with pytest.raises(NotImplementedError, match="window"):
            fn(q, k, v, mesh=mesh, causal=True, window=8)
        assert fn(q, k, v, mesh=None, causal=True, window=8).shape == q.shape


def test_flash_gauges_tell_a_windowed_instantiation_from_a_full_one():
    from mxnet_tpu import telemetry
    a = jax.ShapeDtypeStruct((4, 2048, 64), jnp.bfloat16)
    telemetry.enable()
    try:
        jax.eval_shape(lambda q, k, v: pk.flash_attention(
            q, k, v, True, None, 512, 512), a, a, a)
        jax.eval_shape(lambda q, k, v: pk.flash_attention(
            q, k, v, True, None, 256, 256, 1024), a, a, a)
        rows = telemetry.gauge("mxnet_flash_block_rows")
        steps = telemetry.gauge("mxnet_flash_grid_steps")
        assert rows.labels(kernel="fwd", side="q").value == 512
        assert rows.labels(kernel="fwd", side="q", window=1024).value == 256
        assert steps.labels(kernel="fwd").value == 4 * 4 * 4
        assert steps.labels(kernel="fwd", window=1024).value == 4 * 8 * 8
    finally:
        telemetry.disable()


def test_a_rematerialised_layer_keeps_its_flash_results(ref,
                                                        check_flash_kept):
    """One window layer and one full one (``check_flash_kept``, shared
    with the other block whose layers keep them)."""
    config = dict(CONFIG, num_hidden_layers=2,
                  layer_types=["sliding_attention", "full_attention"])
    weights = ref.init_weights(config, 2 ** 31 + 35)
    net = _block(ref, config, weights)
    tokens = jnp.asarray(np.random.default_rng(35).integers(0, 256, (2, 96)))

    def loss(params):
        return jnp.mean(moe_lm_forward(params, tokens, **net._config) ** 2)

    check_flash_kept(loss, {k: jnp.asarray(v) for k, v in weights.items()
                            if k != "head_weight"}, 2)


# ---------------------------------------------------------------------------
# YaRN
# ---------------------------------------------------------------------------
def test_yarn_table_against_the_closed_form(ref):
    """The published numbers: factor 16 over 8192 positions, theta 5e5,
    beta 32 / 1, heads of 128."""
    theta, dim, factor, orig = 5e5, 128, 16.0, 8192.0
    d = lambda beta: dim * math.log(orig / (2 * math.pi * beta)) \
        / (2 * math.log(theta))
    low, high = math.floor(d(32)), math.ceil(d(1))
    assert (low, high) == (18, 35)
    rope = {"rope_type": "yarn", "rope_theta": theta, "factor": factor,
            "original_max_position_embeddings": orig, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782}
    assert ref.yarn_correction_range(rope, dim) == (18, 35)
    got = yarn_inv_freq(dim, theta, factor, orig, 32, 1)
    for i in range(64):
        inv = theta ** (-2 * i / dim)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want = inv / factor * ramp + inv * (1 - ramp)
        assert got[i] == pytest.approx(want, rel=1e-12)
    assert got[18] == pytest.approx(theta ** (-36 / dim))      # untouched
    assert got[35] == pytest.approx(theta ** (-70 / dim) / 16)  # all of it
    assert np.allclose(ref.rope_table(rope, dim)[0], got, rtol=1e-12)
    assert 0.1 * math.log(16) + 1 == pytest.approx(1.2772588722239782)


def test_rotary_takes_a_layers_own_table_and_scale():
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(1, 40, 2, 32)), jnp.float32)
    inv = yarn_inv_freq(32, 5e5, 4.0, 32.0, 32, 1)
    scale = 0.1 * math.log(4) + 1
    got = _rotary_embedding(x, inv_freq=tuple(inv), scale=scale)
    ang = np.arange(40)[:, None] * inv[None, :]
    cos, sin = (f(ang)[None, :, None, :] * scale for f in (np.cos, np.sin))
    x1, x2 = np.asarray(x[..., :16]), np.asarray(x[..., 16:])
    want = np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    assert np.abs(np.asarray(got) - want).max() < 1e-5
    # no table: the one it has always had
    plain = 5e5 ** (-np.arange(16) * 2.0 / 32)
    assert np.array_equal(
        np.asarray(_rotary_embedding(x, base=5e5)),
        np.asarray(_rotary_embedding(x, base=5e5, inv_freq=None, scale=1.0)))
    assert np.abs(np.asarray(_rotary_embedding(x, base=5e5))
                  - np.asarray(_rotary_embedding(
                      x, inv_freq=tuple(plain)))).max() < 1e-5
    with pytest.raises(ValueError, match="frequencies"):
        _rotary_embedding(x, inv_freq=tuple(inv[:5]))


# ---------------------------------------------------------------------------
# a stacked leaf in a native bucket
# ---------------------------------------------------------------------------
class _Stacked(mx.gluon.HybridBlock):
    """``y = sum_e x W_e'`` with the E matrices one (E, F, U) leaf, or E
    leaves of (F, U)."""

    def __init__(self, experts, stacked, **kwargs):
        super().__init__(**kwargs)
        self._stacked = stacked
        with self.name_scope():
            if stacked:
                self.w = self.params.get("w_weight", shape=(experts, 8, 128))
            else:
                for e in range(experts):
                    setattr(self, "w%d" % e, self.params.get(
                        "w%d_weight" % e, shape=(8, 128)))

    def hybrid_forward(self, F, x, **params):
        if self._stacked:
            ws = [params["w"][e] for e in range(params["w"].shape[0])]
        else:
            ws = [params[k] for k in sorted(params)]
        return sum(F.dot(x, w, transpose_b=True) * (e + 1.0)
                   for e, w in enumerate(ws))


def test_a_stacked_leaf_rides_a_native_bucket(monkeypatch):
    """An ``(E, F, U)`` leaf is swept as ``(E F, U)`` in its own layout
    and updates to what E separate ``(F, U)`` leaves update to."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.parallel import ParallelTrainer, make_mesh
    monkeypatch.setenv("MXNET_PALLAS_FUSED_OPT", "1")
    rng = np.random.default_rng(5)
    w0 = rng.normal(size=(4, 8, 128)).astype("f")
    x = rng.normal(size=(6, 128)).astype("f")
    y = rng.normal(size=(6, 8)).astype("f")
    new = {}
    telemetry.enable()
    try:
        for stacked in (True, False):
            net = _Stacked(4, stacked)
            net.initialize(mx.init.Zero(), ctx=mx.cpu())
            for i, p in enumerate(net.collect_params().values()):
                p.set_data(nd.array(w0 if stacked else w0[i]))
            trainer = ParallelTrainer(
                net, mx.gluon.loss.L2Loss(), "adam",
                {"learning_rate": 1e-2},
                mesh=make_mesh(dp=1, devices=jax.devices()[:1]), zero=2,
                bucket_bytes=1024)
            for _ in range(3):
                trainer.step(nd.array(x), nd.array(y))
            vals = [np.asarray(v) for v in trainer.params.values()]
            new[stacked] = vals[0] if stacked else np.stack(vals)
            if stacked:
                (bucket,) = trainer.bucket_plan
                assert bucket.layout == "native"
                assert bucket.buffer_shape == (32, 128)
                assert bucket.shapes == [(4, 8, 128)]
                mean = trainer.opt_state["fused"]["mean"]["b0"]
                assert mean.shape == (32, 128)
                leaves = telemetry.gauge("mxnet_zero_bucket_leaves")
                assert leaves.labels(layout="native").value == 1
                assert leaves.labels(layout="flat").value == 0
    finally:
        telemetry.disable()
    assert np.abs(new[True] - w0).max() > 1e-3
    assert np.allclose(new[True], new[False], rtol=0, atol=1e-7)
