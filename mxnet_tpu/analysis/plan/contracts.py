"""Sharding-contract verdicts: divisibility, collective matching,
serving-ladder economics, reshard-on-restore compatibility.

Each function is a pure predicate over plan data returning a list of
problem dicts (empty = contract holds); the plan checkers
(``analysis/checkers/plan_rules.py``) turn them into ``Finding``
objects, and ``tools/lint.py --plan`` / the tier-1 gate run them over
the in-tree configuration catalog.
"""
from __future__ import annotations

__all__ = ["check_divisibility", "check_schedule", "ladder_report",
           "generative_report", "reshard_compat"]


def check_divisibility(spec):
    """Every sharded dim must divide the product of its mesh axes;
    flat buckets must pad to the mesh size and a native bucket's rows
    must cut into whole (8, 128)-tile shards; the batch must divide its
    sharding axes.  GSPMD rejects (or silently round-trips through
    padded halos) anything else — at compile time; this is the same
    verdict before any compile."""
    mesh = spec.mesh
    problems = []
    if mesh is None:
        return problems
    for p in spec.params:
        shape = tuple(p["shape"])
        for dim, entry in enumerate(p.get("spec") or ()):
            if entry is None:
                continue
            f = mesh.factor(entry)
            if f > 1 and (dim >= len(shape) or shape[dim] % f):
                problems.append({
                    "contract": "divisibility", "param": p["name"],
                    "detail": "dim %d of %s (shape %s) does not divide "
                              "mesh axes %s (=%d)"
                              % (dim, p["name"], shape,
                                 "x".join(entry), f)})
    n = mesh.size
    for b in spec.buckets:
        if int(b["padded_n"]) % n:
            problems.append({
                "contract": "divisibility", "param": "bucket %d"
                % b["index"],
                "detail": "bucket %d padded length %d does not divide "
                          "the %d-way mesh" % (b["index"],
                                               b["padded_n"], n)})
        if b.get("layout") == "native":
            rows, cols = (int(d) for d in b["buffer_shape"])
            if rows % (8 * n) or cols % 128 \
                    or rows * cols != int(b["padded_n"]):
                problems.append({
                    "contract": "divisibility", "param": "bucket %d"
                    % b["index"],
                    "detail": "native bucket %d keeps its leaf as (%d, "
                              "%d): the %d-way mesh needs rows in "
                              "multiples of %d, whole 128-lane columns "
                              "and no padding" % (b["index"], rows, cols,
                                                  n, 8 * n)})
    if spec.batch:
        bshape = tuple(spec.batch.get("shape") or ())
        f = 1
        for a in spec.batch.get("axes", ()):
            f *= mesh.axis_size(a)
        if bshape and f > 1 and bshape[0] % f:
            problems.append({
                "contract": "divisibility", "param": "batch",
                "detail": "batch dim %d does not divide its sharding "
                          "axes %s (=%d)"
                          % (bshape[0],
                             "x".join(spec.batch.get("axes", ())), f)})
    return problems


def check_schedule(schedule):
    """Collective matching over a :func:`~.schedule.build_schedule`
    list: every reduce-scatter of a bucket must be closed by a LATER
    all-gather over the same axes (the sharded update's param
    re-broadcast) — an orphan means every replica but the owner keeps
    stale params after the step."""
    problems = []
    open_rs = {}        # bucket -> entry index
    for i, e in enumerate(schedule):
        if e["kind"] == "reduce_scatter":
            open_rs[(e.get("bucket"), tuple(e.get("axes") or ()))] = i
        elif e["kind"] == "all_gather":
            open_rs.pop((e.get("bucket"),
                         tuple(e.get("axes") or ())), None)
    for (bucket, axes), i in sorted(open_rs.items(),
                                    key=lambda kv: kv[1]):
        problems.append({
            "contract": "collective-matching",
            "detail": "reduce_scatter of bucket %s over axes %s has no "
                      "later all_gather — the sharded update never "
                      "re-broadcasts the parameters" % (bucket,
                                                        list(axes))})
    return problems


def ladder_report(ladder, fill_min=0.6):
    """Predicted economics of a serving bucket ladder under the
    uniform-arrival model: bucket ``b`` (previous rung ``p``) serves
    request sizes ``p+1 .. b``, so its expected fill is
    ``(p + 1 + b) / 2b``.  Rungs at or below their predecessor are
    *shadowed* — ``pick_bucket`` can never select them.  Returns
    ``{"rungs": [...], "problems": [...]}``."""
    rungs, problems = [], []
    prev = 0
    for i, b in enumerate(int(x) for x in ladder):
        if b <= prev:
            rungs.append({"bucket": b, "prev": prev, "fill": None,
                          "shadowed": True})
            problems.append({
                "contract": "bucket-plan", "bucket": b,
                "detail": "rung %d (size %d) is shadowed by the "
                          "preceding rung %d — pick_bucket can never "
                          "select it; remove it or re-sort the ladder"
                          % (i, b, prev)})
            continue
        fill = (prev + 1 + b) / (2.0 * b)
        rungs.append({"bucket": b, "prev": prev,
                      "fill": round(fill, 4), "shadowed": False})
        if fill < fill_min:
            problems.append({
                "contract": "bucket-plan", "bucket": b,
                "detail": "rung %d (size %d, previous %d) has predicted "
                          "fill %.2f < %.2f — padding waste; add an "
                          "intermediate rung" % (i, b, prev, fill,
                                                 fill_min)})
        prev = b
    return {"rungs": rungs, "problems": problems}


def generative_report(gen, fill_min=0.6):
    """Predicted economics of one generative deployment (an entry of
    ``ModelServer.plan_spec()["generative"]``).

    Both prefill axes are ladders and both are judged by the SAME
    uniform-arrival model as the one-shot batch ladder: a shadowed
    prefill LENGTH rung (one ``pick_grid_bucket`` can never select) is
    a finding, and a low-fill rung is padding waste multiplied across
    every batch rung it grids with.  The KV-cache is priced into the
    per-chip memory story: ``kv_bytes_total = kv_bytes_per_slot x
    slots`` is resident for the server's whole lifetime — the
    interpreter folds it into ``report["memory"]["activations"]`` so
    the ``oom-risk`` budget sees decode state, not just weights."""
    batch = ladder_report(gen.get("batch_ladder") or [],
                          fill_min=fill_min)
    length = ladder_report(gen.get("len_ladder") or [],
                           fill_min=fill_min)
    problems = []
    for axis, rep in (("batch", batch), ("length", length)):
        for p in rep["problems"]:
            q = dict(p)
            q["contract"] = "generative-plan"
            q["detail"] = "prefill %s ladder: %s" % (axis, p["detail"])
            problems.append(q)
    slots = int(gen.get("slots") or 0)
    kv_slot = int(gen.get("kv_bytes_per_slot") or 0)
    n_cells = (len(batch["rungs"]) * len(length["rungs"]))
    max_len = int(gen.get("max_len") or 0)
    max_new = int(gen.get("max_new_tokens") or 0)
    if max_len and max_new > max_len:
        problems.append({
            "contract": "generative-plan",
            "detail": "default generation budget %d exceeds the "
                      "%d-token KV window: most of a default-length "
                      "generation attends through ring wrap-around "
                      "(sliding window) — raise max_len or lower "
                      "MXNET_SERVING_GEN_MAX_NEW_TOKENS"
                      % (max_new, max_len)})
    return {"batch_ladder": batch, "len_ladder": length,
            "slots": slots, "prefill_programs": n_cells,
            "kv_bytes_per_slot": kv_slot,
            "kv_bytes_total": kv_slot * slots,
            "param_bytes": int(gen.get("param_bytes") or 0),
            "problems": problems}


def _slot_names(spec):
    return sorted(spec.optimizer.get("slots", ()))


def reshard_compat(saved, target):
    """Checkpoint reshard-on-restore compatibility between two
    mesh/zero configurations.

    ``saved`` / ``target`` are :class:`~.spec.PlanSpec`\\ s (or their
    dicts).  The ``ParallelTrainerState`` payload is mesh-independent
    by design — params full-logical, slots per-param — so mesh width,
    fsdp split, ZeRO stage, and bucket plan may all differ; what MUST
    match is the logical state itself: param names and shapes, and the
    optimizer slot vocabulary.  Codec residuals saved into a
    codec-less target are dropped state (a note, not an error: the
    restore is well-defined, the error feedback restarts at zero).
    Mirrors ``ParallelTrainer.load_state_dict``'s rejection rules,
    statically."""
    from .spec import PlanSpec
    if isinstance(saved, dict):
        saved = PlanSpec.from_dict(saved)
    if isinstance(target, dict):
        target = PlanSpec.from_dict(target)
    problems, notes = [], []
    saved_p = {p["name"]: tuple(p["shape"]) for p in saved.params}
    target_p = {p["name"]: tuple(p["shape"]) for p in target.params}
    for name, shape in sorted(target_p.items()):
        if name not in saved_p:
            problems.append({
                "contract": "reshard-restore",
                "detail": "checkpoint is missing param %r" % name})
        elif saved_p[name] != shape:
            problems.append({
                "contract": "reshard-restore",
                "detail": "param %r has shape %s in the checkpoint, "
                          "%s in the target trainer"
                          % (name, saved_p[name], shape)})
    if _slot_names(saved) != _slot_names(target):
        problems.append({
            "contract": "reshard-restore",
            "detail": "optimizer slots %s do not match the target's %s "
                      "(different optimizer family)"
                      % (_slot_names(saved), _slot_names(target))})
    if saved.codec and not target.codec:
        notes.append("saved error-feedback residuals are dropped: the "
                     "target runs uncompressed")
    if saved.mesh and target.mesh and \
            saved.mesh.size != target.mesh.size:
        notes.append("mesh width %d -> %d: params and slots reshard on "
                     "restore" % (saved.mesh.size, target.mesh.size))
    if saved.zero != target.zero:
        notes.append("zero stage %d -> %d: slots re-flatten into the "
                     "target layout" % (saved.zero, target.zero))
    # target divisibility must hold AFTER the reshard (the saved side
    # already ran; the target is the one about to bind)
    problems.extend(check_divisibility(target))
    return {"compatible": not problems, "problems": problems,
            "notes": notes}
