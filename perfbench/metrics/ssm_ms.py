"""ssm_ms (ms): device time per step under the scope ``mx_ssm`` — the
Mamba mixers whole, input projection to output projection (the causal
convolution, the step projection, the selective scan's kernels, the
gate) — forward, recomputed forward and backward, mean over the chips
(``hybrid_reduce.py``).  No such scope in the program: nothing
returned."""
import hybrid_reduce


def read(ctx):
    return hybrid_reduce.part_ms(ctx, "ssm")
