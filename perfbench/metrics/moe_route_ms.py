"""moe_route_ms (ms): the part of ``moe_ms`` OUTSIDE the scope
``mx_moe_experts`` — router, softmax, top-k, the sort of the
assignments, the gather of rows and the weighted combine, with their
backward — mean over the chips (``moe_reduce.py``).  No such scope in
the program: nothing returned."""
import moe_reduce


def read(ctx):
    return moe_reduce.part_ms(ctx, "route")
