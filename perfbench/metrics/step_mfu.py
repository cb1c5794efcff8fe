"""step_mfu (%): the whole step's share of the chips' bf16 peak.

FLOPs the forward and backward passes need per step (counts/<family>.py:
2 per multiply-add, backward twice the forward, optimizer and
recomputation not counted) over the window's time per step, the chips
used and the peak of peaks.json.  The time is the host clock's over the
whole traced window: all steps, all time.
"""


def read(ctx):
    if ctx["peaks"] is None or not ctx["steps"]:
        return None
    flops = ctx["counts"].step_flops(ctx["config"])
    per_step = ctx["window_s"] / ctx["steps"]
    return 100.0 * flops / (per_step * ctx["chips"]
                            * ctx["peaks"]["bf16_flops"])
