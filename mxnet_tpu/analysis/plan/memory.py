"""Per-chip memory model: params + optimizer slots + activation
liveness + collective staging.

The optimizer-state component is EXACT by construction: it models the
same layout rules ``ParallelTrainer._init_opt_state`` places buffers
with (slots follow param shardings at zero=0; 1/mesh flat bucket
shards plus per-param leftovers at zero>=1; one scalar slot per
optimizer-state subtree; codec residuals in the slots' layout), and
``tests/test_plan.py`` asserts byte-for-byte equality with the
measured ``trainer.optimizer_state_bytes()`` for zero ∈ {0, 1, 2} on
the 8-device mesh.  The reference analogue is MXNet's plan-memory pass
(PAPER.md §graph-IR): allocation decided by graph walk, not by running.

The activation component is the classic liveness walk the reference
memory planner performs: outputs of each node are allocated at the
node and freed after their last consumer, peak = max live bytes along
the topo order (symbol JSON is already topo-sorted).  Assumptions
documented in docs/faq/static_analysis.md: gradients/workspace are not
modeled (the forward peak is the comparable quantity), batch-sharded
activations divide by the batch shard factor, and XLA fusion can only
shrink the real number — the model is an upper bound on activations
while being exact on state.
"""
from __future__ import annotations

import math

from .shapes import infer_symbol_shapes

__all__ = ["predict_opt_state", "activation_liveness", "predict_memory"]


def _prod(shape):
    return int(math.prod(shape)) if shape else 1


def _param_bytes(p):
    return _prod(p["shape"]) * int(p.get("dtype_size", 4))


def _shard_factor(mesh, pspec):
    f = 1
    for entry in pspec or ():
        f *= mesh.factor(entry)
    return f


def predict_opt_state(spec):
    """``{"total", "per_device"}`` bytes over every optimizer-state
    leaf + compression residuals — the static twin of
    ``ParallelTrainer.optimizer_state_bytes()`` (must match exactly)."""
    mesh = spec.mesh
    n = mesh.size if mesh is not None else 1
    slots = list(spec.optimizer.get("slots", ()))
    scalars = list(spec.optimizer.get("scalar_slots", ()))
    total = per_dev = 0
    trainable = [p for p in spec.params if p.get("trainable", True)]
    fused_names = {nm for b in spec.buckets for nm in b["names"]}
    if spec.zero == 0:
        for p in trainable:
            nb = _param_bytes(p)
            f = _shard_factor(mesh, p.get("spec"))
            for _s in slots:
                total += nb
                per_dev += nb // f
        for _name, nbytes in scalars:
            total += int(nbytes)
            per_dev += int(nbytes)
    else:
        # fused subtree: one fp32 leaf of padded_n elements per bucket
        # per slot — 1-D for a flat bucket, the leaf's own (rows, C)
        # for a native one, the same bytes — sharded 1/mesh over every
        # axis along dimension 0
        for b in spec.buckets:
            nb = 4 * int(b["padded_n"])
            for _s in slots:
                total += nb
                per_dev += nb // n
        # per-param subtree: trainable params outside the buckets keep
        # slots in their own sharding
        for p in trainable:
            if p["name"] in fused_names:
                continue
            nb = _param_bytes(p)
            f = _shard_factor(mesh, p.get("spec"))
            for _s in slots:
                total += nb
                per_dev += nb // f
        # scalar slots (Adam's t) exist once per state SUBTREE — the
        # fused and perparam inits each return one
        for _name, nbytes in scalars:
            total += 2 * int(nbytes)
            per_dev += 2 * int(nbytes)
    # error-feedback residuals ride the slots' layout (1/mesh under
    # ZeRO, replicated otherwise)
    if spec.codec is not None and spec.buckets:
        for b in spec.buckets:
            nb = 4 * int(b["padded_n"])
            total += nb
            per_dev += nb // (n if spec.zero else 1)
    return {"total": int(total), "per_device": int(per_dev)}


def activation_liveness(graph, inputs, batch_shard=1,
                        default_itemsize=4):
    """Peak live activation bytes over the graph's topo order.

    Variables are excluded (params/inputs are accounted separately);
    op outputs allocate at their node and free after their last
    consumer; head outputs stay live to the end.  ``batch_shard``
    divides the result (batch-dim sharding spreads activations across
    the mesh).  Returns ``{"peak", "total", "per_node": [...]}``."""
    inferred = infer_symbol_shapes(graph, inputs,
                                   default_itemsize=default_itemsize)
    nodes = graph["nodes"]
    node_bytes = []
    for i, node in enumerate(nodes):
        if node["op"] == "null" or inferred["node_outputs"][i] is None:
            node_bytes.append(0)
            continue
        node_bytes.append(sum(_prod(s) for s in
                              inferred["node_outputs"][i])
                          * inferred["itemsizes"][i])
    last_use = {}
    for i, node in enumerate(nodes):
        for (src, _oi, *_rest) in node["inputs"]:
            last_use[src] = i
    for (nid, _oi, *_rest) in graph["heads"]:
        last_use[nid] = len(nodes)      # heads survive the program
    live = peak = 0
    for i, node in enumerate(nodes):
        live += node_bytes[i]
        peak = max(peak, live)
        # free every buffer whose last consumer just ran
        for j in range(i + 1):
            if node_bytes[j] and last_use.get(j, j) == i:
                live -= node_bytes[j]
                node_bytes[j] = 0
    shard = max(int(batch_shard), 1)
    total = sum(_prod(s) * inferred["itemsizes"][i]
                for i, outs in enumerate(inferred["node_outputs"])
                if outs is not None and nodes[i]["op"] != "null"
                for s in outs)
    return {"peak": peak // shard, "total": total // shard,
            "shapes": inferred}


def predict_memory(spec):
    """Per-chip peak-memory breakdown of one configuration:
    ``{"params", "opt_state", "staging", "update_temp", "activations",
    "total"}`` bytes — ``activations`` is None when the spec carries no
    graph.

    ``update_temp`` models the optimizer update's transient HBM
    footprint: the per-array path materializes a prepped-gradient
    buffer per update (peak = the largest single update buffer — a
    bucket under ZeRO, the largest trainable param otherwise); the
    one-sweep Pallas path (``optimizer["fused_sweep"]``, the
    ``MXNET_PALLAS_FUSED_OPT`` export) stages its bucket blocks through
    VMEM only — NO per-param HBM temporaries — so the component is 0.
    The VMEM side of that claim is graftkern's to verify: its
    ``kern-vmem-budget`` checker bounds each sweep kernel's
    per-grid-instance residency against ``MXNET_KERN_VMEM_BYTES``, and
    ``tools/lint.py --all`` prints those predictions beside this HBM
    model — one run, the whole byte story."""
    mesh = spec.mesh
    n = mesh.size if mesh is not None else 1
    params = 0
    for p in spec.params:
        params += _param_bytes(p) // _shard_factor(mesh, p.get("spec"))
    opt = predict_opt_state(spec)["per_device"]
    # collective staging: each bucket's fp32 cotangent buffer (a flat
    # bucket's fusion, a native bucket's gradient as it is produced)
    # materializes before (or while) its collective runs, plus the
    # codec's wire payload when compression is on
    staging = 0
    for b in spec.buckets:
        staging += 4 * int(b["padded_n"])
        if spec.codec is not None:
            from .schedule import codec_wire_bytes
            staging += codec_wire_bytes(spec.codec, int(b["padded_n"]))
    update_temp = 0
    # trainer specs only: a program/serving spec carries trainable
    # flags but runs no optimizer update, so charging it an update
    # transient would be a phantom.  Granularity follows the step that
    # actually runs: zero>=1 updates flat bucket SHARDS; zero=0 updates
    # full per-param arrays (buckets exist there too, but only as the
    # gradient-reduction plan)
    if spec.kind == "trainer" and not spec.optimizer.get("fused_sweep"):
        if spec.zero >= 1 and spec.buckets:
            update_temp = max(4 * int(b["padded_n"]) // n
                              for b in spec.buckets)
        else:
            trainable = [p for p in spec.params
                         if p.get("trainable", True)]
            update_temp = max(
                (_param_bytes(p) // _shard_factor(mesh, p.get("spec"))
                 for p in trainable), default=0)
    activations = None
    if spec.graph is not None and spec.graph_inputs:
        batch_shard = 1
        if spec.batch and spec.batch.get("axes") and mesh is not None:
            for a in spec.batch["axes"]:
                batch_shard *= mesh.axis_size(a)
        activations = activation_liveness(
            spec.graph, spec.graph_inputs,
            batch_shard=batch_shard)["peak"]
    total = params + opt + staging + update_temp + (activations or 0)
    return {"params": int(params), "opt_state": int(opt),
            "staging": int(staging), "update_temp": int(update_temp),
            "activations": activations,
            "total": int(total), "mesh_size": n}
