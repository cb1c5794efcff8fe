"""noise_ms (ms): device time per step under the scope ``mx_noise`` — a
block-diffusion step's noising (one mask rate a block, the masked copy,
the weights ``m / p``), the stack of the noised and the clean copy and
the embedding's gather of the 2 L rows, with its scatter-add in the
backward — mean over the chips (``diffusion_reduce.py``).  No such scope
in the program: nothing returned."""
import diffusion_reduce


def read(ctx):
    return diffusion_reduce.part_ms(ctx, "noise")
