"""The flash kernels, the expert layer's row movers and the fused head's
kernels, compiled by the TPU's own compiler for a DESCRIBED v5e (no chip
attached), the flash kernels at the blocks ``_flash_blocks`` picks: what
interpret mode cannot show — a tile the compiler refuses, a block that
overruns scoped VMEM.  Nothing runs and no time comes out of it.

The topology is described inside a fixture, never at import: only one
process may hold the TPU's library, and every xdist worker imports this
file (``on-chip-measurement`` guide, section 2).  Keep such tests in
this one file.
"""
import math
import re

import jax
import jax.numpy as jnp
import pytest

from mxnet_tpu.ops import pallas_kernels as pk


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


# (BH, Tq, Tk, D, Dv, dtype, causal): the two LM cells of the benchmark, a
# longer sequence, float32 operands at a wide head (the blocks shrink to
# fit VMEM), a sequence of no whole lane tile, Tq != Tk, and latent
# attention's pair — keys of 192 (a lane tile and a half: what Mosaic
# may refuse and interpret mode cannot show) over values of 128 — at the
# fifth cell's 8192 tokens, in bfloat16 and in float32; and compressed
# convolutional attention's 8 query heads over 16384 tokens (16 key tiles
# a query tile of 1024)
@pytest.mark.parametrize("bh,tq,tk,d,dv,dtype,causal", [
    (64, 2048, 2048, 64, 64, "bfloat16", True),
    (16, 2048, 2048, 128, 128, "bfloat16", True),
    (2, 8192, 8192, 128, 128, "bfloat16", True),
    (2, 2048, 2048, 256, 256, "float32", True),
    (2, 200, 200, 64, 64, "float32", True),
    (2, 384, 128, 128, 128, "bfloat16", False),
    (2, 8192, 8192, 192, 128, "bfloat16", True),
    (2, 2048, 2048, 192, 128, "float32", True),
    (8, 16384, 16384, 128, 128, "bfloat16", True),
])
def test_flash_kernels_compile_for_the_chip(one_chip, monkeypatch, bh, tq,
                                            tk, d, dv, dtype, causal):
    monkeypatch.setattr(pk, "_interpret", lambda: False)

    def loss(q, k, v):
        o = pk.flash_attention(q, k, v, causal)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    def aval(t, width=d):
        return jax.ShapeDtypeStruct((bh, t, width), jnp.dtype(dtype),
                                    sharding=one_chip)
    # the suite asks for float32 products (conftest.py); the chip runs
    # with the default, and Mosaic takes no bf16 operand at "highest"
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
            aval(tq), aval(tk), aval(tk, dv)).compile()
    text = compiled.as_text()
    for name in ("_flash_fwd_kernel", "_flash_bwd_dq_kernel",
                 "_flash_bwd_dkv_kernel"):
        assert name in text


def _defined_shapes(text):
    """``%name -> (dtype, dims)`` of every instruction of an optimized
    module that makes one array."""
    found = re.finditer(r"^\s*(?:ROOT )?(%[\w.\-]+) = (\w+)\[([0-9,]*)\]",
                        text, re.M)
    return {m.group(1): (m.group(2), tuple(int(d) for d in m.group(3).split(
        ",") if d)) for m in found}


def _attention_module(one_chip, monkeypatch, t, hq, hkv, d, dv, mask,
                      attend=None):
    """The optimized module of one attention sub-layer's loss and
    gradient at batch 1 — ``attend(q, k, v)`` or ``local_attention`` on
    the flash path as the cells' layers call it (``kept``)."""
    from mxnet_tpu.parallel import attention
    monkeypatch.setattr(pk, "_interpret", lambda: False)
    monkeypatch.setattr(attention, "_flash_eligible", lambda *a: True)
    attend = attend or (lambda q, k, v: attention.local_attention(
        q, k, v, kept=True, **mask))

    def loss(q, k, v):
        return jnp.sum(attend(q, k, v).astype(jnp.float32) ** 2)

    def aval(h, width):
        return jax.ShapeDtypeStruct((1, t, h, width), jnp.bfloat16,
                                    sharding=one_chip)
    with jax.default_matmul_precision("default"):
        return jax.jit(jax.grad(loss, (0, 1, 2))).lower(
            aval(hq, d), aval(hkv, d), aval(hkv, dv)).compile().as_text()


# (T, query heads, key/value heads, mask): the grouped cells' attention —
# Mellum2's full and window layers (32 over 4), SDAR's block-diffusion
# layers (32 over 4 over the 8192 rows [noised ; clean]) and ZAYA1's
# compressed convolutional attention (8 over 2 at scale 1)
@pytest.mark.parametrize("t,hq,hkv,mask", [
    (8192, 32, 4, dict(causal=True)),
    (8192, 32, 4, dict(causal=True, window=1024)),
    (8192, 32, 4, dict(block_diffusion=4)),
    (16384, 8, 2, dict(causal=True, scale=1.0)),
], ids=["mellum2-full", "mellum2-window", "sdar-blockdiff", "zaya1-cca"])
def test_grouped_attention_holds_no_repeat(one_chip, monkeypatch, t, hq,
                                           hkv, mask):
    """No bf16 ``broadcast`` of K or V up to the query heads and no
    ``reduce`` of their gradients over the group: the kernels take K and
    V at the key/value heads and give dK and dV back at them."""
    d = 128
    text = _attention_module(one_chip, monkeypatch, t, hq, hkv, d, d, mask)
    shapes = _defined_shapes(text)
    for line in text.splitlines():
        if " broadcast(" in line or " reduce(" in line:
            name = re.match(r"\s*(?:ROOT )?(%[\w.\-]+)", line).group(1)
            dtype, dims = shapes[name]
            assert not (" broadcast(" in line and dtype == "bf16"
                        and math.prod(dims) == t * hq * d), line[:120]
            assert not (" reduce(" in line
                        and math.prod(dims) == t * hkv * d), line[:120]
    calls = [line for line in text.splitlines() if " custom-call(" in line]
    for kernel, operands in (("_flash_fwd_kernel", (1, 2)),
                             ("_flash_bwd_dq_kernel", (1, 2)),
                             ("_flash_bwd_dkv_kernel", (1, 2))):
        line, = [c for c in calls if kernel in c]
        args = re.search(r" custom-call\(([^)]*)\)", line).group(1)
        args = [a.strip().split(" ")[-1] for a in args.split(",")]
        for i in operands:
            assert shapes[args[i]][1][0] == hkv, (kernel, i)
    dkv, = [c for c in calls if "_flash_bwd_dkv_kernel" in c]
    assert re.search(r"= \(bf16\[%d,%d,%d\]" % (hkv, t, d), dkv)


def _instructions(text):
    """An optimized module's instructions as (opcode, result type),
    sorted: what it computes, whatever its names and source lines."""
    return sorted(re.findall(r"= ((?:\(.*?\))|\S+) ([a-z][\w\-]*)\(",
                             re.sub(r"\{[^{}]*\}", "", text)))


# (T, heads, D, Dv): OPT's 32 heads of 64 and Ouro's 16 of 128 at T 2048,
# JoyAI's latent attention, 32 heads of 192 over values of 128 at T 8192
@pytest.mark.parametrize("t,h,d,dv", [
    (2048, 32, 64, 64), (2048, 16, 128, 128), (8192, 32, 192, 128),
], ids=["opt1p3b", "ouro2p6b", "joyai-flash"])
def test_one_head_a_group_compiles_to_the_fold_it_was(one_chip,
                                                      monkeypatch, t, h, d,
                                                      dv):
    """Where every query head has its own key/value head, the sub-layer
    is the program it was before the kernels took grouped heads: the
    heads folded into the leading side, the three kernels, unfolded —
    instruction for instruction."""
    def fold_and_call(q, k, v):
        fold = lambda a: jnp.transpose(a, (0, 2, 1, 3)).reshape(
            h, t, a.shape[-1])
        o = pk.flash_attention(fold(q), fold(k), fold(v), True, None, None,
                               None, None, True)
        return jnp.transpose(o.reshape(1, h, t, dv), (0, 2, 1, 3))
    mask = dict(causal=True)
    assert _instructions(_attention_module(
        one_chip, monkeypatch, t, h, h, d, dv, mask)) == _instructions(
            _attention_module(one_chip, monkeypatch, t, h, h, d, dv, mask,
                              fold_and_call))


# (tokens, top_k, units, held experts, dtype): the benchmark's expert
# layer (8192 tokens, 8 slots, rows of 2304 bfloat16, 16 held experts: a
# buffer of 187 tiles of 384 rows), and a float32 layer of one lane tile
@pytest.mark.parametrize("tokens,top_k,units,held,dtype", [
    (8192, 8, 2304, 16, "bfloat16"),
    (512, 2, 128, 2, "float32"),
])
def test_row_movers_compile_for_the_chip(one_chip, monkeypatch, tokens,
                                         top_k, units, held, dtype):
    """Mosaic slices a DMA along whole tiles only: a one-row copy out of
    a 2-D array is refused whatever interpret mode says.  Both movers and
    the pass that makes rows fetchable, with both gradients."""
    from mxnet_tpu.parallel import moe
    monkeypatch.setattr(pk, "_interpret", lambda: False)
    monkeypatch.setattr(moe, "_movers_run", pk.row_words_ok)
    tm = pk.GROUPED_TILE_ROWS
    tiles = -(-tokens * top_k // tm) + held
    p = tiles * tm

    def loss(x, w, src, counts, used, dst, is_held, order, rank, runs):
        rows, again = moe._dispatch(x, src // top_k, counts, used, dst,
                                    is_held)
        out = moe._combine(rows * 2 + again, w, src, counts, used, dst,
                           is_held, order, rank, runs)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    def aval(shape, kind):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(kind),
                                    sharding=one_chip)
    compiled = jax.jit(jax.grad(loss, (0, 1))).lower(
        aval((tokens, units), dtype), aval((tokens, top_k), "float32"),
        aval((p,), "int32"), aval((tiles,), "int32"), aval((1,), "int32"),
        aval((tokens, top_k), "int32"), aval((tokens, top_k), "bool"),
        aval((tokens * top_k,), "int32"), aval((tokens * top_k,), "int32"),
        aval((3, held), "int32"),
    ).compile()
    text = compiled.as_text()
    for name in ("_moe_rows_kernel", "_moe_slots_kernel",
                 "_moe_words_kernel"):
        assert name in text
    assert not any(" gather(" in line for line in text.splitlines())


# (units, expert width, held experts, row tiles): the top-1 cell's experts
# of 2048 over rows of 2048 (blocks of 384 x 1024 x 1024 in the products
# and both gradients), and Mellum2's of 896 over 2304
@pytest.mark.parametrize("units,width,held,tiles", [
    (2048, 2048, 8, 32),
    (2304, 896, 16, 40),
])
def test_grouped_products_compile_for_the_chip(one_chip, monkeypatch, units,
                                               width, held, tiles):
    monkeypatch.setattr(pk, "_interpret", lambda: False)
    tm = pk.GROUPED_TILE_ROWS

    def loss(x, gate, down, tile_group, used):
        h = pk.grouped_matmul(x, gate, tile_group, used, tm)
        y = pk.grouped_matmul(h, down, tile_group, used, tm)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    def aval(shape, kind="bfloat16"):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(kind),
                                    sharding=one_chip)
    with jax.default_matmul_precision("default"):   # as the chip runs
        compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
            aval((tiles * tm, units)), aval((held, width, units)),
            aval((held, units, width)), aval((tiles,), "int32"),
            aval((1,), "int32")).compile()
    text = compiled.as_text()
    for name in ("_grouped_matmul_kernel", "_grouped_matmul_dw_kernel"):
        assert name in text


@pytest.fixture(scope="module")
def moe_steps(one_chip):
    """A two-layer ``MoELM`` step (loss, gradients, an SGD update; the
    flash path forced) registered through ``telemetry.register_program``
    twice — as ``kept``, its layers under their policy, and as ``bare``,
    under a bare ``jax.checkpoint`` — and compiled for the described
    chip by ``telemetry.program_hlo``: ``{program: (optimized text,
    gauge -> value)}``.  The expert layer takes the chip's path too:
    grouped products between the row movers (rows of 256 bfloat16)."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.gluon.contrib import transformer
    from mxnet_tpu.parallel import attention, moe
    net = transformer.MoELM(
        256, units=256, expert_width=128,
        layer_types=[transformer.SLIDING, transformer.FULL], num_heads=2,
        num_kv_heads=1, head_dim=64, num_routed=4, held=(0, 2), top_k=2,
        window=128)
    config = net._config
    short = len(net.prefix)
    params = {name[short:]: jax.ShapeDtypeStruct(
        p.shape, jnp.bfloat16, sharding=one_chip)
        for name, p in net.collect_params().items()
        if not name.endswith("head_weight")}
    tokens = jax.ShapeDtypeStruct((2, 256), jnp.int32, sharding=one_chip)

    def compiled(program):
        def step(params_, tokens_):         # a trace of its own a program
            def loss(p):
                states = transformer.moe_lm_forward(p, tokens_, **config)
                return jnp.mean(states.astype(jnp.float32) ** 2)
            grads = jax.grad(loss)(params_)
            return jax.tree_util.tree_map(lambda w, g: w - 0.1 * g,
                                          params_, grads)

        telemetry.register_program(program, jax.jit(step), (params, tokens))
        with jax.default_matmul_precision("default"):
            text = telemetry.program_hlo(program)
        return text, {name: telemetry.gauge(name).labels(
            program=program).value
            for name in ("mxnet_flash_fwd_calls", "mxnet_moe_route_passes",
                         "mxnet_moe_route_gathers")}

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(pk, "_interpret", lambda: False)
        patch.setattr(attention, "_flash_eligible", lambda *a: True)
        patch.setattr(moe, "_tile_product", pk.grouped_matmul)
        patch.setattr(moe, "_movers_run", pk.row_words_ok)
        telemetry.enable()
        try:
            steps = {"kept": compiled("kept")}
            patch.setattr(transformer, "_layer_keeps", lambda: None)
            steps["bare"] = compiled("bare")
        finally:
            telemetry.disable()
    return steps


def test_the_gauge_counts_the_forward_kernels_of_a_registered_step(
        moe_steps):
    """``mxnet_flash_fwd_calls{program}`` off the OPTIMIZED module: one
    forward kernel a layer where the layers keep the kernel's results
    across their checkpoints, two under a bare ``jax.checkpoint`` — what
    the trace-time counter cannot tell apart — and the two backward
    kernels once a layer in both."""
    for program, forward in (("kept", 2), ("bare", 4)):
        text, gauges = moe_steps[program]
        for kernel in ("_flash_bwd_dq_kernel", "_flash_bwd_dkv_kernel"):
            assert sum(1 for line in text.splitlines()
                       if " custom-call(" in line and kernel in line) == 2
        assert gauges["mxnet_flash_fwd_calls"] == forward


def test_the_gauge_counts_the_routing_passes_of_a_registered_step(
        moe_steps):
    """``mxnet_moe_route_passes{program}`` off the OPTIMIZED module: one
    top-k a routed layer where the layers keep their routing across
    their checkpoints, two under a bare ``jax.checkpoint`` — and the
    comparison sorts (two a pass; the chip's compiler writes the top-k
    as a third) fall with it.  ``mxnet_moe_route_gathers{program}``
    reads 0 in both: no table, weight or row index under the expert
    layer is an XLA gather."""
    routed = re.compile(r"\bmx_moe\)*/")     # not mx_moe_experts
    for program, passes in (("kept", 2), ("bare", 4)):
        text, gauges = moe_steps[program]
        assert gauges["mxnet_moe_route_passes"] == passes
        assert gauges["mxnet_moe_route_gathers"] == 0
        # three a routing pass; two a layer in the combine's transpose and
        # one in each slot mover's fetch table (the combine's, dispatch's
        # transpose)
        assert sum(1 for line in text.splitlines()
                   if " sort(" in line and routed.search(line)) \
            == 3 * passes + 4 * 2


def _f32_arrays_of(text, elements, but):
    """Lines of an optimized module holding a float32 array of exactly
    ``elements`` elements, whatever its shape (the chip's compiler may
    fold an (N, V) array into four dimensions), other than of the shape
    ``but``."""
    shapes = re.compile(r"f32\[([0-9,]+)\]")
    return [line for line in text.splitlines()
            if any(math.prod(dims) == elements and dims != but
                   for dims in (tuple(int(d) for d in found.split(","))
                                for found in shapes.findall(line)))]


# (rows, vocabulary, units, weighted): the fused head as the cells take it
# — ZAYA1's 16384 x 32784 x 2048, Mellum2's 8192 x 24576 x 2304, JoyAI's
# 8192 x 16160 x 2048 (ZAYA1's and JoyAI's vocabularies no whole number
# of 512-column blocks) and an Ouro exit's 2048 x 49152 x 2048 — and
# SDAR's weighted rule at 4096 x 18992 x 2048, which keeps its own module
@pytest.mark.parametrize("n,v,u,weighted", [
    (16384, 32784, 2048, False),
    (8192, 24576, 2304, False),
    (8192, 16160, 2048, False),
    (2048, 49152, 2048, False),
    (4096, 18992, 2048, True),
])
def test_the_head_kernels_compile_for_the_chip(one_chip, monkeypatch, n, v,
                                               u, weighted):
    """The unweighted head compiles to the two kernels and one XLA
    product, and its optimized module holds no float32 array of N x V
    elements: the logits stay in VMEM both ways.  The weighted rule takes
    no kernel and keeps its float32 logits."""
    from mxnet_tpu.ops import contrib
    monkeypatch.setattr(pk, "_on_tpu", lambda: True)

    def loss(x, w, y, g):
        ce = contrib._linear_cross_entropy(x, w, y, g if weighted else None)
        return jnp.sum(ce * g)

    def aval(shape, kind="bfloat16"):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(kind),
                                    sharding=one_chip)
    with jax.default_matmul_precision("default"):   # as the chip runs
        compiled = jax.jit(jax.value_and_grad(loss, (0, 1))).lower(
            aval((n, u)), aval((v, u)), aval((n,), "int32"),
            aval((n,), "float32")).compile()
    text = compiled.as_text()
    kernels = [name for name in ("_head_ce_fwd_kernel", "_head_ce_bwd_kernel")
               if name in text]
    # the weights' gradient is float32 (V, U) inside its product's fusion,
    # of N x V elements where U is N (an Ouro exit)
    logits = _f32_arrays_of(text, n * v, but=(v, u))
    if weighted:
        assert kernels == [] and logits
    else:
        assert len(kernels) == 2
        assert logits == [], logits[:3]


# the selective scan's pair at the Phi-4-mini-flash cell's width (8192
# rows, 5120 channels, a state of 16; u in bfloat16, delta in float32)
# and at a ragged length of bfloat16 steps
@pytest.mark.parametrize("bs,t,e,delta_dtype", [
    (1, 8192, 5120, "float32"), (2, 1000, 1024, "bfloat16")])
def test_the_scan_kernels_compile_for_the_chip(one_chip, monkeypatch, bs, t,
                                               e, delta_dtype):
    monkeypatch.setattr(pk, "_interpret", lambda: False)
    n = 16
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)

    def grads(u, delta, a, b, c, d):
        y, back = jax.vjp(pk.selective_scan, u, delta, a, b, c, d)
        return back(y)

    compiled = jax.jit(grads).lower(
        sds((bs, t, e), jnp.bfloat16), sds((bs, t, e), delta_dtype),
        sds((e, n), jnp.float32), sds((bs, t, n), jnp.bfloat16),
        sds((bs, t, n), jnp.bfloat16), sds((e,), jnp.float32)).compile()
    text = compiled.as_text()
    for kernel in ("_ssm_fwd_kernel", "_ssm_bwd_kernel"):
        assert kernel in text
    # the state over every row is never written: what the pair keeps in
    # HBM beside its operands is the states entering the chunks
    chunks = -(-t // pk.SSM_CHUNK_ROWS)
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 8 * bs * t * e * 4 + 2 * 4 * bs * chunks * n * e


# differential attention's one flash call: 40 query heads of 64 over 20
# key heads, values of 128 (each serving both key heads of its pair), a
# 512-key window and the full causal mask, at 8192 rows
@pytest.mark.parametrize("window", [512, None])
def test_the_differential_flash_call_compiles_for_the_chip(
        one_chip, monkeypatch, window):
    monkeypatch.setattr(pk, "_interpret", lambda: False)
    sds = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                              sharding=one_chip)

    def loss(q, k, v):
        o = pk.flash_attention(q, k, v, True, None, None, None, window)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    # the chip runs with the default precision (the suite asks for float32
    # products, which Mosaic takes from no bf16 operand)
    with jax.default_matmul_precision("default"):
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            sds(40, 8192, 64), sds(20, 8192, 64),
            sds(20, 8192, 128)).compile().as_text()
    for kernel in ("_flash_fwd_kernel", "_flash_bwd_dq_kernel",
                   "_flash_bwd_dkv_kernel"):
        assert kernel in text
