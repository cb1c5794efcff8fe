"""The step from inside: device time by phase of the step program, and
the program's own host spans.

The device events of the xplane (``XLA Ops``) carry no scope, only the
name of their HLO instruction; the optimized module's text carries, on
that instruction, the ``jax.named_scope``s the program wrapped its parts
in (``mxnet_tpu/telemetry/phases.py``: ``mx_fwd``, ``mx_update`` ...).
:func:`phases` joins the two once per run — event -> the program it ran
in (the ``XLA Modules`` event around it) -> leading ``%instruction`` ->
``instruction_phases(program_hlo(...))`` — and the metric files
``fwd_ms``, ``bwd_ms``, ``update_ms`` and ``phase_unattributed_share``
read the result.  ``step_host_ms`` reads the ring of the program's spans
(``tracing.snapshot()``), whose ``t0_ns`` is on ``time.perf_counter``,
the clock of ``ctx["spans"]``: no offset is needed to lay them on the
window.

A test or a recorded trace hands the module texts in as
``ctx["program_hlo"]`` (a list) and the spans as ``ctx["program_spans"]``;
a run asks the program.  A program without the scopes or the registry
(the parent of the PR that added them) gives every reader ``None``.
"""
import bisect
import sys
import time
import traceback

STEP_SPANS = ("module.forward_backward", "module.update",
              "module.update_metric", "trainer.step")
UNATTRIBUTED = "other"      # phases.OTHER, and what no map knows


def _program_hlo(ctx, program):
    """The optimized module texts of the programs registered with
    telemetry — compiled here, after the window, a persistent-cache hit."""
    if "program_hlo" in ctx:
        return ctx["program_hlo"]
    texts = []
    for name in program.program_names():
        t0 = time.perf_counter()
        try:
            texts.append(program.program_hlo(name))
        except Exception:       # a reader reports, the run goes on
            traceback.print_exc()
            continue
        print("[perfbench] program_hlo(%r): %.2fs, %d bytes"
              % (name, time.perf_counter() - t0, len(texts[-1])),
              file=sys.stderr, flush=True)
    return texts


def _module_name(hlo_text):
    head = hlo_text.split("\n", 1)[0]       # "HloModule jit_fbu, is_..."
    return head.split()[1].rstrip(",") if head.startswith("HloModule") \
        else None


def phases(ctx):
    """``{"seconds": {phase: s}, "busy_s": s}`` per chip (mean over the
    chips) over the traced window, memoised on ``ctx``; None where the
    program names no phase."""
    if "_phases" in ctx:
        return ctx["_phases"]
    ctx["_phases"] = None
    try:
        from mxnet_tpu.telemetry import phases as program
    except ImportError:         # a program from before the scopes
        return None
    trace = ctx.get("trace") or {}
    ops = trace.get("ops_by_device")
    texts = _program_hlo(ctx, program) if ops else None
    if not texts:
        return None
    maps = {_module_name(t): program.instruction_phases(t) for t in texts}
    seconds = {}
    for dev, events in ops.items():
        mods = sorted((s, e, name.split("(", 1)[0]) for name, s, e in
                      trace.get("modules_by_device", {}).get(dev, ()))
        starts = [m[0] for m in mods]
        for name, s, e in events:
            mid = (s + e) / 2.0
            i = bisect.bisect_right(starts, mid) - 1
            inside = mods[i][2] if i >= 0 and mid <= mods[i][1] else None
            instruction = name.split(" = ", 1)[0].strip().lstrip("%")
            phase = maps.get(inside, {}).get(instruction, UNATTRIBUTED)
            seconds[phase] = seconds.get(phase, 0.0) + (e - s) * 1e-9
    if not any(t for p, t in seconds.items() if p != UNATTRIBUTED):
        return None
    ctx["_phases"] = {
        "seconds": {p: t / len(ops) for p, t in seconds.items()},
        "busy_s": trace["busy_s"]}
    return ctx["_phases"]


def phase_ms(ctx, phase):
    """Device time per step (ms) of one phase, or None."""
    joined = phases(ctx)
    if joined is None or not ctx.get("steps"):
        return None
    return 1e3 * joined["seconds"].get(phase, 0.0) / ctx["steps"]


def unattributed_share(ctx):
    """Device time no phase claims, over busy time (%), or None."""
    joined = phases(ctx)
    if joined is None or not joined["busy_s"]:
        return None
    return 100.0 * joined["seconds"].get(UNATTRIBUTED, 0.0) \
        / joined["busy_s"]


def step_host_ms(ctx):
    """Host time per step (ms) inside the program's own top-level step
    spans that began inside the window, or None."""
    window = ctx.get("spans") or []
    if not window or not ctx.get("steps"):
        return None
    if "program_spans" in ctx:
        recorded = ctx["program_spans"]
    else:
        from mxnet_tpu.telemetry import tracing
        recorded = tracing.snapshot()
    lo, hi = window[0][0] * 1e9, window[-1][1] * 1e9
    inside = [r["dur_ms"] for r in recorded
              if r["name"] in STEP_SPANS and lo <= r.get("t0_ns", -1) <= hi]
    if not inside:
        return None
    return sum(inside) / ctx["steps"]
