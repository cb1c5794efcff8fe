"""hbm_peak_share (%): the fullest device's peak bytes over its capacity
(peaks.json).  The bytes are what the result line reports as
``memory_peak_bytes``."""


def read(ctx):
    if ctx["peaks"] is None or not ctx["memory_peak_bytes"]:
        return None
    return 100.0 * ctx["memory_peak_bytes"] / ctx["peaks"]["hbm_bytes"]
