"""graftkern — static verification of the in-tree Pallas kernels.

The fifth analysis leg (source -> plan -> IR -> runtime -> KERNEL):
where graftir proves properties of the traced step program, graftkern
proves properties of the kernels inside it, by abstract interpretation
of each kernel's declarative plan (grid, BlockSpecs, index maps,
scalar-prefetch operands — ``ops/pallas_kernels.py`` builds the plans
for its own dispatch, the catalog here re-reads them as pure data).
Nothing traces or compiles: index maps are evaluated over the grid
with plain Python ints.

Rules (checkers/kern_rules.py): ``kern-grid-coverage``,
``kern-vmem-budget``, ``kern-retrace-hazard`` and the headline
``kern-shard-safety`` — whose verdict
:func:`~mxnet_tpu.ops.pallas_kernels.mesh_sweep_safe` consumes to
decide whether the multi-chip ZeRO trainer may run the fused
optimizer sweep under ``shard_map`` instead of falling back to the
per-array ``tree_map`` path.  Run it with ``tools/lint.py --kern``
(or ``--all``); docs: ``docs/faq/static_analysis.md``.
"""
from __future__ import annotations

from .catalog import (flash_cell_reports, flash_group_reports,
                      flash_reports, grouped_matmul_reports, head_ce_reports,
                      kernel_reports, layernorm_reports, moe_mover_reports,
                      scale_bias_relu_reports,
                      softmax_reports, sweep_reports)

__all__ = ["kernel_reports", "sweep_reports", "flash_reports",
           "flash_cell_reports", "flash_group_reports",
           "grouped_matmul_reports", "moe_mover_reports", "head_ce_reports",
           "scale_bias_relu_reports", "layernorm_reports", "softmax_reports",
           "sweep_shard_verdict"]


def sweep_shard_verdict():
    """The ``kern-shard-safety`` verdict over the optimizer-sweep
    family, as consumed by ``ops/pallas_kernels.py mesh_sweep_safe``:
    ``{"safe": bool, "kernels": {name: per-kernel verdict}}``.  Safe
    only when EVERY sweep kernel's index maps are block-local along
    the sharded rows axis, over the flat AND the native bucket plan (a
    kernel's entry is the first of its plans that is not safe, else
    its last) — one unprovable kernel keeps the whole family on the
    tree_map path."""
    from ..checkers.kern_rules import shard_safety
    per = {}
    for r in sweep_reports():
        if per.get(r["name"], {"safe": True})["safe"]:
            per[r["name"]] = shard_safety(r)
    return {"safe": bool(per) and all(v["safe"] for v in per.values()),
            "kernels": per}
