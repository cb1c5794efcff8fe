"""graftkern catalog — the in-tree Pallas kernels abstractly
interpreted into pure-data reports.

Every kernel family in ``ops/pallas_kernels.py`` is instantiated here
at representative shapes and its PLAN — the grid/BlockSpec dict the
dispatch itself consumes (``sweep_plan``, ``flash_fwd_plan``, ...) —
is evaluated into a report: grid, per-operand block shapes and the
index-map table over every grid point (index maps called with plain
Python ints — nothing traces, nothing compiles, no jit), the
scalar-prefetch transport, Python-level closure constants, padded-tail
contract, per-instance VMEM bytes, and the shard facts the
``kern-shard-safety`` verdict judges.  Because the dispatch and the
analysis read the SAME plan objects, the verifier cannot drift from
the kernels it verifies.

Report schema (mirrored by the seeded fixtures in
``tests/fixtures/analysis/kern_bad_kernels.json``)::

    {"name": "_adam_kernel", "family": "MXNET_PALLAS_FUSED_OPT",
     "origin": "mxnet_tpu/ops/pallas_kernels.py",
     "grid": [8],
     "operands": [{"name": "w", "role": "in|out|scalar_prefetch",
                   "dtype": "float32", "block": [1024, 128],
                   "shape": [8192, 128],          # padded shape
                   "index": [[0, 0], [1, 0], ...]},  # one row per
                  ...],                           # grid point (row-
     "scratch": [{"shape": [128, 64], "dtype": "float32"}],  # major)
     "hyper": {"transport": "scalar_prefetch", "names": [...]},
     "python_constants": [{"name": "use_clip", "detail": "..."}],
     "tail": {"logical_elems": N, "padded_elems": M, "masked": true,
              "how": "..."},
     "shard": {"axis": 0, "operands": [...], "why": "...",
               "safe": true, "grid_dim": 0},      # verdict attached
     "vmem": {"bytes_per_instance": B, "budget": L}}
"""
from __future__ import annotations

import itertools

__all__ = ["kernel_reports", "sweep_reports", "flash_reports",
           "flash_cell_reports", "flash_group_reports",
           "grouped_matmul_reports",
           "moe_mover_reports", "head_ce_reports", "scale_bias_relu_reports",
           "layernorm_reports", "softmax_reports", "ORIGIN"]

ORIGIN = "mxnet_tpu/ops/pallas_kernels.py"


def _dtype_name(dtype):
    import numpy as np
    return np.dtype(dtype).name


def _eval_index(spec, grid, n_prefetch, prefetch=None):
    """The index map evaluated at every grid point (row-major), with
    one dummy argument per scalar-prefetch operand — block-local maps
    never touch the prefetch ref, so abstract evaluation works on
    plain ints; a data-dependent map would raise here, which is
    exactly a not-statically-analyzable kernel.  A kernel whose maps DO
    read a prefetched table (the grouped product: row tile -> group) is
    analysed at one representative table, handed in as ``prefetch``
    (plain lists): the verdict is about that table's grid."""
    extra = tuple(prefetch) if prefetch is not None \
        else (None,) * n_prefetch
    return [[int(v) for v in spec.index_map(*pt, *extra)]
            for pt in itertools.product(*[range(int(g)) for g in grid])]


def _operand(name, role, spec, shape, grid, n_prefetch,
             dtype="float32", prefetch=None):
    if spec.block_shape is None:
        # left in HBM whole (``memory_space=pl.ANY``): the kernel
        # fetches from it by DMA, no block of it lives in VMEM
        return {"name": name, "role": role, "dtype": dtype, "block": None,
                "shape": [int(s) for s in shape], "index": None}
    return {"name": name, "role": role, "dtype": dtype,
            "block": [None if b is None else int(b)
                      for b in spec.block_shape],
            "shape": [int(s) for s in shape],
            "index": _eval_index(spec, grid, n_prefetch, prefetch)}


def _report(name, family, plan, in_names, out_names, *, hyper=None,
            python_constants=(), shard=None, tail=None, prefetch=None,
            revisit=None, sums=None):
    from mxnet_tpu import config as _config

    from ..checkers.kern_rules import shard_safety, vmem_bytes
    grid = [int(g) for g in plan["grid"]]
    npf = int(plan.get("num_scalar_prefetch", 0))
    operands = []
    if npf:
        operands.append({
            "name": "hyper", "role": "scalar_prefetch",
            "dtype": "float32", "block": None,
            "shape": [len((hyper or {}).get("names") or ())],
            "index": None})
    # a plan that names its operands' dtypes (the flash plans) is
    # counted at them; every other kernel's operands are float32
    dtypes = map(_dtype_name,
                 plan.get("dtypes") or itertools.repeat("float32"))
    for nm, spec, shape in zip(in_names, plan["in_specs"],
                               plan["in_shapes"]):
        operands.append(_operand(nm, "in", spec, shape, grid, npf,
                                 next(dtypes), prefetch))
    for nm, spec, shape in zip(out_names, plan["out_specs"],
                               plan["out_shapes"]):
        operands.append(_operand(nm, "out", spec, shape, grid, npf,
                                 next(dtypes), prefetch))
        if revisit:
            # how the grid comes back to an output block, where it is
            # not "once per unused grid step" (kern_rules
            # coverage_problems)
            operands[-1]["revisit"] = revisit
        if sums:
            # each block the sum over a group of heads of another
            # operand (kern_rules group_problems)
            operands[-1]["sums"] = dict(sums)
    report = {
        "name": name, "family": family, "origin": ORIGIN,
        "grid": grid,
        "operands": operands,
        # a flash plan's score tiles live in VMEM beside the declared
        # scratch: the budget counts both
        "scratch": [{"shape": [int(s) for s in sh],
                     "dtype": _dtype_name(dt)}
                    for sh, dt in (
                        *((sh, "float32")
                          for sh in plan.get("scratch", ())),
                        *plan.get("tiles", ()))],
        "hyper": hyper or {"transport": None, "names": []},
        "python_constants": list(python_constants),
        "tail": tail,
        "shard": dict(shard) if shard else None,
    }
    report["vmem"] = {
        "bytes_per_instance": vmem_bytes(report),
        "budget": int(_config.get("MXNET_KERN_VMEM_BYTES")),
    }
    if shard:
        # attach the verdict for display/consumption; the checker
        # re-derives it from the raw facts, never trusts this field
        v = shard_safety(report)
        report["shard"]["safe"] = v["safe"]
        report["shard"]["grid_dim"] = v["grid_dim"]
    return report


# -- one-sweep fused optimizer ---------------------------------------------

_SWEEPS = (
    ("_sgd_kernel", ("w", "g"), ("ow",),
     ("lr", "wd", "rescale", "clip")),
    ("_sgd_mom_kernel", ("w", "g", "mom"), ("ow", "om"),
     ("lr", "momentum", "wd", "rescale", "clip")),
    ("_adam_kernel", ("w", "g", "mean", "var"), ("ow", "om", "ov"),
     ("lr_eff", "beta1", "beta2", "one_minus_beta1",
      "one_minus_beta2", "epsilon", "wd", "rescale", "clip")),
)


def sweep_reports(n=None):
    """The three optimizer-sweep kernels over both bucket layouts: a
    flat bucket at a representative size — a NON-lane-divisible element
    count, so the padded-tail contract is part of what gets verified —
    and a native ``(rows, C)`` bucket whose rows the blocks do not
    divide (OPT-1.3B's embedding), so the clipped last block is."""
    from mxnet_tpu.ops import pallas_kernels as pk
    if n is None:
        n = 8 * pk._OPT_BLOCK_ELEMS - 37
    rows, cols = 50272, 2048
    reports = []
    for (name, ins, outs, hyper_names), shape in itertools.product(
            _SWEEPS, ((n,), (rows, cols))):
        plan = pk.sweep_plan(shape, len(ins), len(outs))
        flat = len(shape) == 1
        swept = plan["grid"][0] * plan["block_rows"] \
            * plan["out_shapes"][0][1]
        reports.append(_report(
            name, "MXNET_PALLAS_FUSED_OPT", plan, ins, outs,
            hyper={"transport": "scalar_prefetch",
                   "names": list(hyper_names)},
            python_constants=[
                {"name": "use_clip",
                 "detail": "structural branch (presence of clipping "
                           "changes the kernel body; the clip VALUE "
                           "rides scalar prefetch)"}],
            shard={"axis": 0,
                   "operands": list(ins) + list(outs),
                   "why": "ZeRO buckets, flat and native, shard the "
                          "rows axis 1/mesh across the trainer mesh "
                          "(parallel/trainer.py _make_step_zero)"},
            tail={"logical_elems": int(n if flat else rows * cols),
                  "padded_elems": int(swept),
                  "masked": True,
                  "how": "host zero-pad (_to_rows); every sweep "
                         "update maps 0 -> 0 exactly, pad sliced "
                         "away on return" if flat else
                         "no padding: Pallas clips the last block at "
                         "the array's edge, and an elementwise update "
                         "of rows past it is never written"}))
    return reports


# -- flash attention -------------------------------------------------------

def flash_reports(bh=8, tq=512, tk=512, d=64, bq=128, bk=128,
                  causal=False, dtype="float32", dv=None,
                  block_diffusion=None, window=None, group=1):
    """The three flash kernels at one shape.  ``bq`` / ``bk`` None:
    the blocks ``_flash_blocks`` picks for each kernel from the shape,
    as a call without explicit blocks runs them.  ``dv``: the values'
    head size where it is not the keys'.  ``block_diffusion``: the block
    length of that mask, under which a grid's minor axis counts visits.
    ``window`` (causal): a query's last keys.  ``group``: query heads a
    key/value head (``bh`` counts the query heads); dK / dV's blocks
    are then each the sum over the query heads of their group, visited
    along the minor axis — revisited ``group`` x the Q visits, which the
    coverage verdict's uniform revisit count admits, and declared as
    sums so that a plan that leaves a member unread is refused."""
    from mxnet_tpu.ops import pallas_kernels as pk
    structural = [
        {"name": "scale", "detail": "architecture constant (1/sqrt(d) "
                                    "unless overridden)"},
        {"name": "causal", "detail": "structural branch: masking "
                                     "changes the kernel body and "
                                     "clamps the index maps"},
        {"name": "bq", "detail": "block size"},
        {"name": "bk", "detail": "block size"},
    ]
    elems = bh * tq * d
    sums = {"of": "q", "heads": group}
    tail = {"logical_elems": elems, "padded_elems": elems,
            "masked": True,
            "how": "no padding: the picked (or halved explicit) "
                   "blocks divide T exactly"}
    # flash has no MXNET_PALLAS_* family knob: parallel/attention.py
    # selects it per call via impl="auto"/"flash" — label the family
    # by that entry point, not a fabricated knob name
    family = "flash_attention(impl=...)"
    reports = []
    for name, kernel, ins, outs, extent in (
            ("_flash_fwd_kernel", "fwd", ("q", "k", "v"), ("o", "lse"),
             "nk"),
            ("_flash_bwd_dq_kernel", "dq",
             ("q", "k", "v", "do", "lse", "delta"), ("dq",), "nk"),
            ("_flash_bwd_dkv_kernel", "dkv",
             ("q", "k", "v", "do", "lse", "delta"), ("dk", "dv"), "nq")):
        pq, pk_ = pk._flash_blocks(tq, tk, d, dtype, kernel, dv,
                                   block_diffusion)
        reports.append(_report(
            name, family,
            pk._FLASH_PLANS[kernel](bh, tq, tk, d, bq or pq, bk or pk_,
                                    causal, dtype, window, dv,
                                    block_diffusion, group),
            ins, outs,
            python_constants=structural + [
                {"name": extent, "detail": "grid extent"}],
            tail=tail, sums=sums if kernel == "dkv" else None))
    return reports


def flash_cell_reports():
    """The flash kernels as the benchmark's LM cells run them: causal
    bf16 with the blocks picked from the shape — OPT-1.3B's 2 x 32 heads
    of 64 and Ouro-2.6B's 1 x 16 heads of 128 at T 2048, JoyAI-LLM-
    Flash's 32 heads of latent attention at T 8192, keys of 192 over
    values of 128 — and the grouped ones: SDAR's 32 query heads of 128
    over 4 key/value heads and the 8192 rows ``[noised ; clean]`` under
    the block-diffusion mask, blocks of 4; Mellum2's 32 over 4 at T 8192,
    causal and under a 1024-key window; ZAYA1's 8 over 2 at T 16384."""
    return (flash_reports(64, 2048, 2048, 64, None, None, True,
                          "bfloat16")
            + flash_reports(16, 2048, 2048, 128, None, None, True,
                            "bfloat16")
            + flash_reports(32, 8192, 8192, 192, None, None, True,
                            "bfloat16", dv=128)
            + flash_reports(32, 8192, 8192, 128, None, None, False,
                            "bfloat16", block_diffusion=4, group=8)
            + flash_reports(32, 8192, 8192, 128, None, None, True,
                            "bfloat16", group=8)
            + flash_reports(32, 8192, 8192, 128, None, None, True,
                            "bfloat16", window=1024, group=8)
            + flash_reports(8, 16384, 16384, 128, None, None, True,
                            "bfloat16", group=4))


def flash_group_reports():
    """The flash kernels with 4 and 8 query heads a key/value head
    under each mask — causal, a window, block diffusion — at a small
    shape: two key/value heads, 512 rows in blocks of 128, so dK/dV's
    minor axis visits 4 Q blocks of each member of a group."""
    masks = (dict(causal=True), dict(causal=True, window=200),
             dict(block_diffusion=4))
    return [r for group in (4, 8) for mask in masks
            for r in flash_reports(2 * group, 512, 512, 64, 128, 128,
                                   group=group, **mask)]


# -- grouped matrix product (sparse experts) --------------------------------

def grouped_matmul_reports(rows=3840, c=2304, o=896, groups=4, tm=None,
                           dtype="bfloat16"):
    """The grouped product's three instantiations — forward, the
    gradient for the rows, the gradient for the weights — at the
    benchmark's expert widths (2304 -> 896) over a representative row
    layout: four groups of 3, 1 (an EMPTY group's one zero tile), 2 and
    1 tiles, and two unused tiles past them.  The index maps read the
    prefetched table, so the reports hold for that table's grid."""
    from mxnet_tpu.ops import pallas_kernels as pk
    tm = tm or pk.GROUPED_TILE_ROWS
    tiles = rows // tm + groups
    tile_group = [0, 0, 0, 1, 2, 2] + [3] * (tiles - 6)
    used = [7]
    hyper = {"transport": "scalar_prefetch",
             "names": ["tile_group", "used"]}
    structural = [
        {"name": "nk", "detail": "grid extent"},
        {"name": "w_out_in", "detail": "structural branch: which side "
                                       "of the weight is contracted"},
        {"name": "tiles", "detail": "grid extent"}]
    elems = used[0] * tm * o
    tail = {"logical_elems": elems, "padded_elems": tiles * tm * o,
            "masked": True,
            "how": "a group's last tile is padded with ZERO rows by the "
                   "layout (parallel/moe.py _layout) and the tiles past "
                   "`used` run no product and are written as zeros; "
                   "nothing gathers a padding row back"}
    family = "routed_experts"
    reports = []
    for w_out_in, ins in ((True, ("x", "w")), (False, ("dy", "w"))):
        reports.append(_report(
            "_grouped_matmul_kernel", family,
            pk.grouped_matmul_plan(rows + groups * tm, c if w_out_in else o,
                                   o if w_out_in else c, groups, tm,
                                   w_out_in, dtype),
            ins, ("y" if w_out_in else "dx",), hyper=hyper,
            python_constants=structural[:2], tail=tail,
            prefetch=(tile_group, used)))
    reports.append(_report(
        "_grouped_matmul_dw_kernel", family,
        pk.grouped_matmul_dw_plan(rows + groups * tm, c, o, groups, tm,
                                  dtype),
        ("dy", "x"), ("dw",), hyper=hyper,
        python_constants=structural[2:], tail=tail,
        prefetch=(tile_group, used), revisit="runs"))
    return reports


# -- row movers (sparse experts' dispatch and combine) ----------------------

def moe_mover_reports(tokens=1024, top_k=8, units=2304, groups=4, tm=None,
                      dtype="bfloat16"):
    """The expert layer's row movers at the benchmark's width (rows of
    2304 bfloat16) over a representative table: 1024 tokens, 8 slots
    each, a buffer of ``8192 / tm + 4`` tiles of which 7 are used, one of
    them an EMPTY group's tile with no valid row.  The buffer-side mover
    plain (dispatch) and with a factor a row and the row dots (the
    combine's transpose), the token-side mover, and the pass that makes
    a source's rows fetchable (here of the sum of two buffers, as
    dispatch's transpose asks).  A source the kernels fetch from by row
    DMA is an un-blocked HBM operand; the landing buffers are scratch.
    Outputs blocked by buffer tile hold at the last used tile."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_kernels as pk
    tm = tm or pk.GROUPED_TILE_ROWS
    slots = tokens * top_k
    tiles = -(-slots // tm) + groups
    p = tiles * tm
    used = [7]
    counts = ([tm, tm, 100, 0, tm, 50, tm] + [0] * tiles)[:tiles]
    tables = {"transport": "scalar_prefetch",
              "names": ["used", "counts", "src_token"]}
    tail = {"logical_elems": used[0] * tm * units,
            "padded_elems": p * units, "masked": True,
            "how": "a tile's rows past its count are written as zeros; "
                   "the tiles past `used` are not written at all, and "
                   "every reader (the grouped product, the token-side "
                   "mover) skips them by the same `used`"}
    family = "routed_experts"
    reports = []
    for scaled, dotted, ins, outs in (
            (False, False, ("x_words",), ("rows",)),
            (True, True, ("g_words", "scale", "y"), ("gy", "dots"))):
        reports.append(_report(
            "_moe_rows_kernel", family,
            pk.moe_rows_plan(p, tokens, units, tm, jnp.dtype(dtype),
                             scaled, dotted),
            ins, outs, hyper=tables, tail=tail,
            python_constants=[
                {"name": "scaled", "detail": "structural branch"},
                {"name": "dotted", "detail": "structural branch"}],
            prefetch=(used, counts, None), revisit="used"))
    bt = pk._pick_block(tokens, pk._SLOT_TILE_TOKENS)
    reports.append(_report(
        "_moe_slots_kernel", family,
        pk.moe_slots_plan(tokens, top_k, p, units, bt, jnp.dtype(dtype)),
        ("y_words", "held", "w"), ("out",),
        hyper={"transport": "scalar_prefetch", "names": ["held", "fetch"]},
        tail={"logical_elems": tokens * units,
              "padded_elems": tokens * units, "masked": True,
              "how": "no padding: every token's row is written; a slot "
                     "that is not held is never fetched and is selected "
                     "away, not multiplied away"},
        prefetch=(None, None)))
    reports.append(_report(
        "_moe_words_kernel", family,
        pk.moe_words_plan(p, units, tm, jnp.dtype(dtype), sources=2),
        ("g_gate", "g_up"), ("g_words",),
        hyper={"transport": "scalar_prefetch", "names": ["used"]},
        tail=tail, prefetch=(used,), revisit="used"))
    return reports


# -- fused head (a projection with its softmax cross-entropy) --------------

def head_ce_reports(n=16384, v=32784, u=2048, dtype="bfloat16"):
    """The fused head's two kernels as ZAYA1's cell runs them, at the
    blocks ``_head_blocks`` picks: 16384 rows of 2048 over a vocabulary
    of 32784 — no whole number of blocks, so the last vocabulary block is
    ragged and its columns past V are masked in the kernels."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_kernels as pk
    dt = jnp.dtype(dtype)
    family = "linear_cross_entropy(on a TPU)"
    structural = [{"name": "tv", "detail": "block size"},
                  {"name": "v", "detail": "the vocabulary's end, which "
                                          "masks a ragged last block"},
                  {"name": "nv", "detail": "grid extent"}]
    reports = []
    for kernel, name, ins, outs in (
            ("fwd", "_head_ce_fwd_kernel", ("x", "w", "y"),
             ("lse", "picked")),
            ("bwd", "_head_ce_bwd_kernel", ("x", "w", "y", "lse", "g"),
             ("dx", "d"))):
        tm, tv = pk._head_blocks(n, v, u, dt, kernel)
        plan = pk._HEAD_PLANS[kernel](n, v, u, tm, tv, dt)
        padded = plan["grid"][1] * tv
        reports.append(_report(
            name, family, plan, ins, outs, python_constants=structural,
            tail={"logical_elems": n * v, "padded_elems": n * padded,
                  "masked": True,
                  "how": "no padding in HBM: Pallas clips the last "
                         "vocabulary block at the array's edge; in VMEM "
                         "its columns past V are NEG_INF in the logits "
                         "and its rows of w past V zero in the "
                         "backward's product"}))
    return reports


# -- inference BatchNorm+ReLU epilogue -------------------------------------

def scale_bias_relu_reports(n=16 * 7 * 7, c=2048, block=1024):
    """The epilogue at ResNet-50's widest eval shape (last stage,
    serving bucket 16): a (784, 2048) fp32 array, where a block choice
    blind to the width would not fit VMEM."""
    import numpy as np

    from mxnet_tpu.ops import pallas_kernels as pk
    bn = pk._row_block(n, c, np.float32, block)
    npad = n + (-n) % bn
    return [_report(
        "_scale_bias_relu_kernel", "MXNET_PALLAS_BN_RELU",
        pk.scale_bias_relu_plan(npad, c, bn),
        ("x", "scale", "bias"), ("y",),
        python_constants=[
            {"name": "relu", "detail": "structural branch: the "
                                       "epilogue with/without "
                                       "activation"}],
        tail={"logical_elems": n * c, "padded_elems": npad * c,
              "masked": True,
              "how": "_row_block prefers a divisor of N; otherwise "
                     "zero pad rows (_pad_rows) — a row-wise "
                     "elementwise pass, pad sliced away on return"})]


# -- fused layernorm -------------------------------------------------------

def layernorm_reports(r=1024, c=256):
    from mxnet_tpu.ops import pallas_kernels as pk
    br = pk._norm_block_rows(r, c, "MXNET_PALLAS_NORM_BLOCK_ROWS")
    rp = r + (-r) % br
    eps = [{"name": "eps", "detail": "architecture constant fixed at "
                                     "layer construction, not a "
                                     "schedule value"}]
    tail = {"logical_elems": r * c, "padded_elems": rp * c,
            "masked": True,
            "how": "zero pad rows (_pad_rows); pad-row stats never "
                   "mix into real rows (row-wise kernel), pad sliced "
                   "away on return"}
    return [
        _report("_layernorm_fwd_kernel", "MXNET_PALLAS_NORM",
                pk.layernorm_fwd_plan(rp, c, br),
                ("x", "gamma", "beta"), ("o", "mu", "rstd"),
                python_constants=eps, tail=tail),
        _report("_layernorm_bwd_kernel", "MXNET_PALLAS_NORM",
                pk.layernorm_bwd_plan(rp, c, br),
                ("x", "do", "gamma", "mu", "rstd"), ("dx",),
                tail=tail),
    ]


# -- fused bias+softmax ----------------------------------------------------

def softmax_reports(b=8, r=128, c0=1000):
    from mxnet_tpu.ops import pallas_kernels as pk
    c = c0 + (-c0) % pk.LANES
    br = pk._norm_block_rows(r, c, "MXNET_PALLAS_SOFTMAX_BLOCK_ROWS")
    rp = r + (-r) % br
    tail = {"logical_elems": b * r * c0, "padded_elems": b * rp * c,
            "masked": True,
            "how": "per-operand identity column fills (NEG_INF "
                   "logits, 0 probabilities/cotangents), zero pad "
                   "rows; pad sliced away on return"}
    return [
        _report("_softmax_fwd_kernel", "MXNET_PALLAS_SOFTMAX",
                pk.softmax_plan(b, rp, c, 1, br),
                ("x",), ("p",), tail=tail),
        _report("_softmax_bias_fwd_kernel", "MXNET_PALLAS_SOFTMAX",
                pk.softmax_plan(b, rp, c, 1, br, has_bias=True),
                ("x", "bias"), ("p",), tail=tail),
        _report("_softmax_bwd_kernel", "MXNET_PALLAS_SOFTMAX",
                pk.softmax_plan(b, rp, c, 2, br),
                ("p", "do"), ("dx",), tail=tail),
    ]


def kernel_reports():
    """Every in-tree kernel family's reports — the catalog
    ``tools/lint.py --kern`` / ``--all`` judge."""
    return (sweep_reports() + flash_reports() + flash_cell_reports()
            + flash_group_reports()
            + grouped_matmul_reports() + moe_mover_reports()
            + head_ce_reports() + scale_bias_relu_reports()
            + layernorm_reports() + softmax_reports())
