"""compiles_in_window (count): how far the program's own
``mxnet_xla_compiles_total`` advanced across the traced window
(counted exactly at dispatch; telemetry is on in the traced run).
Expected 0: everything the window runs was warmed in set-up."""


def read(ctx):
    return float(ctx["compiles_in_window"])
