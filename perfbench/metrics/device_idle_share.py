"""device_idle_share (%): 1 - (union of the device-op intervals) /
(the traced window), mean over the chips used, from the xplane."""


def read(ctx):
    t = ctx["trace"]
    if not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
