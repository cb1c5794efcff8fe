"""The comparison that decides ``correct`` for a training cell.

Set-up drives the ONE object the window will drive — the compiled step
with its state — through its first ``CHECK_STEPS`` steps, through the
window's own call and feed, and :func:`probe` reads from it:

- each step's loss;
- the norm of every leaf's first gradient as the optimizer got it,
  worked out from the state after one step (SGD with momentum from zero:
  ``w1 - w0 = -lr (g + wd w0)``; Adam: ``mean1 = (1 - beta1) g``);
- the norm of every leaf's change after the steps.

After the window, with the program freed, the plain reference follows
the same steps from the same weights and batches, and :func:`judge`
holds the program to it.  A leaf's gap is the gap between the program's
norm and the reference's — not the norm of their difference — over the
reference's norm of that leaf or of the median leaf, whichever is larger
(some gradients are all but zero).  Leaves whose reference gradient is
under a thousandth of the median leaf's move by round-off alone (under
Adam by a full step) and are left out of the change.  The gaps are read
three ways: the worst leaf, the median leaf, and the median over the
quarter of the leaves whose reference gradient is largest
(:func:`top_leaves`).  Every number compared has its own limit, from
``limits/<cell>.json``; a number with no limit there is read and printed,
not compared (PERF.md says why, with its readings).
"""
import math

import numpy as np

CHECK_STEPS = 3
DEAD_LEAF = 1e-3     # x the median leaf's gradient norm
TOP_SHARE = 0.25     # the leaves whose reference gradient is largest


def _norm(a):
    """Euclidean norm of a float32 array: float32 dot products over
    blocks of a million elements, the blocks summed in float64 (half a
    billion elements must not be copied to float64 on the host)."""
    flat = np.ascontiguousarray(a, np.float32).reshape(-1)
    total = 0.0
    for i in range(0, flat.size, 1 << 20):
        block = flat[i:i + (1 << 20)]
        total += float(np.dot(block, block))
    return total ** 0.5


def first_gradient_norms(driver, weights, config, wd_mult):
    opt = config["optimizer"]
    lr, wd = float(opt["learning_rate"]), float(opt["wd"])
    if opt["name"] == "sgd":
        now = driver.leaves()
        return {k: _norm((w0 - now[k]) / np.float32(lr)
                         - np.float32(wd * wd_mult(k, config)) * w0)
                for k, w0 in weights.items()}
    if opt["name"] == "adam":
        mean = driver.slots("mean")
        b1 = float(opt["beta1"])
        out = {}
        for k, w0 in weights.items():
            g = mean[k] / np.float32(1.0 - b1)
            decay = wd * wd_mult(k, config)
            out[k] = _norm(g - np.float32(decay) * w0 if decay else g)
        return out
    raise ValueError("no rule for optimizer %r" % opt["name"])


def probe(driver, feed, weights, config, wd_mult):
    """The first steps, on the object the window gets.  Returns the
    program's readings and the batches it was fed (host copies)."""
    got, batches = {"loss": []}, []
    for i in range(CHECK_STEPS):
        batch = feed.next()
        batches.append(batch.host)
        driver.step(batch)
        got["loss"].append(driver.loss())
        if i == 0:
            got["grad1"] = first_gradient_norms(driver, weights, config,
                                                wd_mult)
    now = driver.leaves()
    got["dparam"] = {k: _norm(now[k] - w0) for k, w0 in weights.items()}
    return got, batches


def leaf_gaps(got, want, leaves=None):
    """{leaf: gap} — the gap between the program's norm and the
    reference's over max(reference norm, median reference norm)."""
    names = list(want) if leaves is None else list(leaves)
    med = float(np.median([want[k] for k in names]))
    out = {}
    for k in names:
        gap = abs(got[k] - want[k]) / max(want[k], med, 1e-300)
        out[k] = gap if math.isfinite(gap) else float("inf")
    return out


def worst_leaf_gap(got, want, leaves=None):
    gaps = leaf_gaps(got, want, leaves)
    at = max(gaps, key=gaps.get)
    return gaps[at], at


def median_leaf_gap(got, want, leaves=None):
    return float(np.median(list(leaf_gaps(got, want, leaves).values())))


def top_leaves(want):
    """The quarter of the leaves whose reference gradient norm is
    largest.  A rule on the reference's gradient, not on names: a large
    norm is a coherent sum, where a lower precision shows at its own
    size; a BatchNorm gamma or beta of an early layer is the nearly
    cancelling sum of a million terms, and reads tenths in ANY 8-bit
    mantissa (PERF.md, Findings PR 23)."""
    g = want["grad1"]
    names = sorted(g, key=g.get, reverse=True)
    return names[:max(1, int(len(names) * TOP_SHARE))]


def live_leaves(want):
    g = want["grad1"]
    med = float(np.median(list(g.values())))
    return [k for k, v in g.items() if v >= DEAD_LEAF * med]


def numbers(got, want):
    """{name: (value, leaf or None)} of every number compared."""
    out = {}
    for i, (a, b) in enumerate(zip(got["loss"], want["loss"])):
        gap = abs(a - b) / max(abs(b), 1e-300)
        out["loss%d" % (i + 1)] = (gap if math.isfinite(gap)
                                   else float("inf"), None)
    live, top = live_leaves(want), top_leaves(want)
    out["grad1_top"] = (median_leaf_gap(got["grad1"], want["grad1"], top),
                        None)
    out["dparam_top"] = (median_leaf_gap(got["dparam"], want["dparam"], top),
                         None)
    out["grad1"] = worst_leaf_gap(got["grad1"], want["grad1"])
    out["dparam"] = worst_leaf_gap(got["dparam"], want["dparam"], live)
    out["grad1_med"] = (median_leaf_gap(got["grad1"], want["grad1"]), None)
    out["dparam_med"] = (median_leaf_gap(got["dparam"], want["dparam"],
                                         live), None)
    return out


def judge(got, want, limits):
    """``{"correct": bool, "compared": {name: {value, limit, ok}},
    "read": {name: value}}``.  A number the limits file gives no limit is
    read and not compared (PERF.md names each with its readings)."""
    compared, read, correct = {}, {}, True
    for name, (value, leaf) in numbers(got, want).items():
        limit = limits["limits"].get(name)
        if limit is None:
            read[name] = value
            continue
        ok = bool(value <= limit)
        compared[name] = {"value": value, "limit": limit, "ok": ok}
        if leaf is not None:
            compared[name]["leaf"] = leaf
        correct = correct and ok
    if not compared:
        correct = False
    return {"correct": correct, "compared": compared, "read": read}
