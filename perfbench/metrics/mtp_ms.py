"""mtp_ms (ms): device time per step under the scope ``mx_mtp`` — the
multi-token-prediction modules whole: entry projection, their own layer
(attention and experts included, which ``attn_full_ms``, ``moe_ms`` and
the others count too), final norm and term through the shared head;
forward, recomputed forward and backward — mean over the chips
(``latent_reduce.py``).  A share of the step that crosses the parts, not
a part beside them.  No such scope in the program: nothing returned."""
import latent_reduce


def read(ctx):
    return latent_reduce.part_ms(ctx, "mtp")
