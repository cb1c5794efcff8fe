"""What the plain references share: the seed's key, the configuration's
weight-decay rule, and the float8 rounding of the CONTROL (fp8 training's
two formats: e4m3 for operands on the way forward, e5m2 for the gradient
on the way back, one scale per tensor)."""
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

WEIGHT_STREAM = 1


def seed_key(seed, stream):
    """A threefry key from a seed of any size (``--seed`` may pass 2**31):
    its high and low 32 bits are the key's two words, the stream number
    folded in."""
    seed = int(seed)
    data = np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32)
    key = jax.random.wrap_key_data(jnp.asarray(data), impl="threefry2x32")
    return jax.random.fold_in(key, stream)


def wd_mult(name, config):
    """1 where the optimizer decays the leaf, 0 where it does not: the
    configuration lists the exempt name endings (``Module``'s optimizer
    follows MXNet's ``set_wd_mult`` and spares ``*_bias`` and ``*_beta``;
    ``ParallelTrainer``'s pure optimizers decay every leaf)."""
    exempt = tuple(config["optimizer"].get("wd_exempt_suffixes", ()))
    return 0.0 if exempt and name.endswith(exempt) else 1.0


def _to_fp8(x, dtype, top):
    s = top / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * s).astype(dtype).astype(jnp.float32) / s


def fp8(x):
    """Round to float8 e4m3 with one scale per tensor (448 = its largest
    finite value); the gradient passes straight through."""
    return x + lax.stop_gradient(_to_fp8(x, jnp.float8_e4m3fn, 448.0) - x)


@jax.custom_vjp
def fp8_grad(y):
    """Identity forward; the gradient coming back is rounded to float8
    e5m2 (57344 = its largest finite value), one scale per tensor."""
    return y


fp8_grad.defvjp(lambda y: (y, None),
                lambda _res, g: (_to_fp8(g, jnp.float8_e5m2, 57344.0),))
