"""attn_latent_ms (ms): device time per step under the scope
``mx_attn_latent`` — latent attention around its flash call: the query
and key/value down-projections, the latents' norms, the up-projections,
the split and the shared rotary key's broadcast over the heads; forward,
recomputed forward and backward — mean over the chips
(``latent_reduce.py``).  No such scope in the program: nothing
returned."""
import latent_reduce


def read(ctx):
    return latent_reduce.part_ms(ctx, "attn_latent")
