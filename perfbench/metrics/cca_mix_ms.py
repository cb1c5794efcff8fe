"""cca_mix_ms (ms): device time per step under the scope ``mx_cca`` — what
compressed convolutional attention does between its projections and the
flash call (the two causal convolutions, the q-k mean, the values'
shift, the QK norm with its temperature, the partial rotary), forward,
recomputed forward and backward — mean over the chips
(``cca_reduce.py``).  No such scope in the program: nothing returned."""
import cca_reduce


def read(ctx):
    seconds = cca_reduce.cca_seconds(ctx)
    if seconds is None or not ctx.get("steps"):
        return None
    return 1e3 * seconds / ctx["steps"]
