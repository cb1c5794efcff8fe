"""attn_blockdiff_ms (ms): device time per step under the scope
``mx_attn_blockdiff`` — attention of a block-diffusion layer over the
rows ``[noised ; clean]`` under the three-part block mask: QK-norm,
rotary at the rows' positions, the K/V repeat of grouped-query heads and
the flash kernels; forward, recomputed forward and backward — mean over
the chips (``diffusion_reduce.py``).  No such scope in the program:
nothing returned."""
import diffusion_reduce


def read(ctx):
    return diffusion_reduce.part_ms(ctx, "attn_blockdiff")
