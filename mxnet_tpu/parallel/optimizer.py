"""Pure-functional optimizer kernels for compiled train steps.

The in-place ``Optimizer.update`` API (optimizer.py) cannot live inside
a jitted step; these adapters re-express the same fused update kernels
(ops/optimizer_ops.py, reference src/operator/optimizer_op-inl.h) as
pure pytree transforms: ``init(params) -> state``,
``apply(params, grads, state, lr) -> (params, state)``.  The whole
update fuses into the train-step XLA program — the reference's
update-on-kvstore collapses into the compiled step.

One-sweep fused path (the MPK mega-kernel leg, ROADMAP item 3): when
the trainer hands ``apply`` bucket BUFFERS (``flat=True`` — fp32
buffers, a flat bucket's 1-D fusion or a native bucket's one leaf as
``(rows, C)``, with slots allocated bucket-major in the same layout,
still ZeRO-sharded 1/mesh over dimension 0) and
``MXNET_PALLAS_FUSED_OPT`` is on, each bucket updates in
ONE Pallas kernel (``ops/pallas_kernels.py`` ``fused_sgd_momentum`` /
``fused_adam``): params, grads and slots stream through VMEM once
instead of XLA's per-stage elementwise kernels, and lr/betas/wd ride a
scalar-prefetch operand so schedule changes never retrace.  On a
multi-chip mesh the trainer additionally passes ``mesh=`` and the
sweep runs ``shard_map``-wrapped over the sharded bucket rows — the
path ``mesh_sweep_safe`` only opens after graftkern's
``kern-shard-safety`` verdict statically proved every sweep kernel's
index maps block-local along the sharded axis
(``analysis/kern/``).  The ``tree_map`` path below stays byte-for-byte
as the fallback AND the bit-parity oracle (tests/test_pallas.py /
test_parallel_zero.py assert exact equality, padded tails and
checkpoint cycles included).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..base import MXNetError
from ..telemetry import phases as _phases

__all__ = ["PureSGD", "PureAdam", "make_optimizer", "sharded_zeros_like"]


def _fused_sweep_on(flat):
    from ..ops.pallas_kernels import family_enabled
    return flat and family_enabled("MXNET_PALLAS_FUSED_OPT")


def sharded_zeros_like(params, shardings):
    """ZeRO-aware slot allocation: each slot is created and immediately
    placed by its entry in the ``shardings`` tree (``None`` entries and
    a ``None`` tree fall back to the param's own layout).  Optimizer
    ``init`` paths route through here so a slot for a mesh-sharded (or
    ZeRO-flattened) parameter never materializes replicated — the
    regression class graftlint's ``replicated-state`` checker flags."""
    if shardings is None:
        return jax.tree_util.tree_map(jnp.zeros_like, params)

    def _zeros(p, s):
        z = jnp.zeros(p.shape, p.dtype)
        return z if s is None else jax.device_put(z, s)

    return jax.tree_util.tree_map(_zeros, params, shardings)


class PureSGD:
    """SGD(+momentum, +wd) as a pure transform."""

    def __init__(self, learning_rate=0.01, momentum=0.0, wd=0.0,
                 rescale_grad=1.0, clip_gradient=None):
        self.lr = learning_rate
        self.momentum = momentum
        self.wd = wd
        self.rescale_grad = rescale_grad
        self.clip_gradient = clip_gradient

    def init(self, params, shardings=None):
        """Slot state for ``params``; with ``shardings`` (a matching
        tree of ``NamedSharding``) each slot is allocated pre-sharded —
        the ZeRO-1/2 memory contract (1/mesh per chip), not a
        replicated tree that GSPMD later reshards."""
        if self.momentum == 0.0:
            return {}
        return {"mom": sharded_zeros_like(params, shardings)}

    def slot_spec(self):
        """Declarative slot layout for graftplan (analysis/plan/): the
        per-param slot names :meth:`init` allocates plus the scalar
        slots with their byte sizes.  The static optimizer-state
        predictor is a pure function of this spec — keep it in
        lockstep with :meth:`init` (tests/test_plan.py asserts the two
        agree byte-for-byte against real shardings)."""
        return {"slots": [] if self.momentum == 0.0 else ["mom"],
                "scalar_slots": [],
                "fused_sweep": _fused_sweep_on(True)}

    def apply(self, params, grads, state, lr=None, flat=False,
              mesh=None):
        """``flat=True`` marks the leaves as bucket buffers (fp32, flat
        1-D or native ``(rows, C)``; slots bucket-major in the same
        layout) — the contract under which
        the one-sweep Pallas path may take over; the per-array
        ``tree_map`` below is its bit-parity oracle.  ``mesh`` (a
        multi-chip trainer mesh) makes the sweep run ``shard_map``-ped
        over the bucket's sharded rows — only reachable when
        graftkern's ``kern-shard-safety`` verdict proved the kernels
        block-local (``mesh_sweep_safe``)."""
        lr = self.lr if lr is None else lr
        clip = self.clip_gradient

        if _fused_sweep_on(flat):
            # bucket contract: params is a plain {bucket_key: fp32
            # buffer} dict and slots share its keys and layouts — sweep
            # each bucket in one kernel
            from ..ops import pallas_kernels as pk
            new_params, new_mom = {}, {}
            with jax.named_scope(_phases.SWEEP_SCOPE):
                for k in params:
                    nw, nm = pk.fused_sgd_momentum(
                        params[k], grads[k],
                        None if self.momentum == 0.0 else state["mom"][k],
                        lr=lr, momentum=self.momentum, wd=self.wd,
                        rescale=self.rescale_grad, clip=clip, mesh=mesh)
                    new_params[k] = nw
                    if nm is not None:
                        new_mom[k] = nm
            if self.momentum == 0.0:
                return new_params, state
            return new_params, {"mom": new_mom}

        def prep(g, w):
            g = g * self.rescale_grad
            if clip is not None:
                g = jnp.clip(g, -clip, clip)
            return g + self.wd * w

        if self.momentum == 0.0:
            new_params = jax.tree_util.tree_map(
                lambda w, g: w - lr * prep(g, w), params, grads)
            return new_params, state
        mom = state["mom"]
        new_mom = jax.tree_util.tree_map(
            lambda m, g, w: self.momentum * m - lr * prep(g, w),
            mom, grads, params)
        new_params = jax.tree_util.tree_map(lambda w, m: w + m, params,
                                            new_mom)
        return new_params, {"mom": new_mom}


class PureAdam:
    """Adam as a pure transform."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, wd=0.0, rescale_grad=1.0, clip_gradient=None):
        self.lr = learning_rate
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon
        self.wd = wd
        self.rescale_grad = rescale_grad
        self.clip_gradient = clip_gradient

    def init(self, params, shardings=None):
        """See :meth:`PureSGD.init` — slots pre-sharded when a
        ``shardings`` tree is given (ZeRO state placement)."""
        return {"mean": sharded_zeros_like(params, shardings),
                "var": sharded_zeros_like(params, shardings),
                "t": jnp.zeros((), jnp.int32)}

    def slot_spec(self):
        """See :meth:`PureSGD.slot_spec`.  ``t`` is a scalar slot:
        :meth:`init` returns it unconditionally, so under ZeRO it
        exists once per state subtree (fused AND perparam) — the
        predictor models exactly that."""
        return {"slots": ["mean", "var"], "scalar_slots": [["t", 4]],
                "fused_sweep": _fused_sweep_on(True)}

    def apply(self, params, grads, state, lr=None, flat=False,
              mesh=None):
        """See :meth:`PureSGD.apply` for the ``flat``/``mesh``
        contract."""
        lr = self.lr if lr is None else lr
        t = state["t"] + 1
        b1, b2 = self.beta1, self.beta2
        coef = jnp.sqrt(1 - b2 ** t.astype(jnp.float32)) / \
            (1 - b1 ** t.astype(jnp.float32))
        clip = self.clip_gradient

        if _fused_sweep_on(flat):
            from ..ops import pallas_kernels as pk
            # lr * coef FIRST — the same grouping the tree_map update
            # evaluates (w - ((lr*coef)*m)/(sqrt(v)+eps)), so the fused
            # sweep is bit-identical; t bookkeeping stays out here
            lr_eff = lr * coef
            new_params, new_mean, new_var = {}, {}, {}
            with jax.named_scope(_phases.SWEEP_SCOPE):
                for k in params:
                    nw, nm, nv = pk.fused_adam(
                        params[k], grads[k], state["mean"][k],
                        state["var"][k], lr_eff=lr_eff, beta1=b1,
                        beta2=b2, epsilon=self.epsilon, wd=self.wd,
                        rescale=self.rescale_grad, clip=clip, mesh=mesh)
                    new_params[k] = nw
                    new_mean[k] = nm
                    new_var[k] = nv
            return new_params, {"mean": new_mean, "var": new_var, "t": t}

        def prep(g, w):
            g = g * self.rescale_grad
            if clip is not None:
                g = jnp.clip(g, -clip, clip)
            return g + self.wd * w

        new_mean = jax.tree_util.tree_map(
            lambda m, g, w: b1 * m + (1 - b1) * prep(g, w),
            state["mean"], grads, params)
        new_var = jax.tree_util.tree_map(
            lambda v, g, w: b2 * v + (1 - b2) * jnp.square(prep(g, w)),
            state["var"], grads, params)
        new_params = jax.tree_util.tree_map(
            lambda w, m, v: w - lr * coef * m / (jnp.sqrt(v) + self.epsilon),
            params, new_mean, new_var)
        return new_params, {"mean": new_mean, "var": new_var, "t": t}


def make_optimizer(name, **kwargs):
    name = name.lower()
    if name == "sgd":
        return PureSGD(**kwargs)
    if name == "adam":
        return PureAdam(**kwargs)
    raise MXNetError("unknown pure optimizer %r (sgd/adam supported in the "
                     "compiled step; others via the eager Trainer)" % name)
