"""shared_expert_ms (ms): device time per step under the scope
``mx_shared_expert`` — the SwiGLU every token takes beside its routed
experts; forward, recomputed forward and backward — mean over the chips
(``latent_reduce.py``).  No such scope in the program: nothing
returned."""
import latent_reduce


def read(ctx):
    return latent_reduce.part_ms(ctx, "shared_expert")
