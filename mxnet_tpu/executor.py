"""Executor — compiled forward/backward for a bound Symbol.

Reference: ``src/executor/graph_executor.cc`` (GraphExecutor::Init:512,
Forward:81, Backward:94, the Gradient pass at :298, PlanMemory at :903,
op bulking at :1336) + ``python/mxnet/executor.py``.

TPU-native redesign (SURVEY.md §2.6 TPU mapping): the entire executor
pipeline — gradient graph construction, shape/type inference, memory
planning, op fusion/bulking, cached segment ops — collapses into
``jax.jit`` over ONE pure function lowered from the Symbol DAG:

- ``Forward``  = jitted graph function (one XLA program, fully fused).
- ``Backward`` = the same function under ``jax.vjp``; for training binds
  the forward AND backward run as a single fused XLA program per step
  (grad computed alongside forward — the idiomatic `value_and_grad`
  form), so Forward+Backward costs one device dispatch, matching the
  reference's bulked segments but compiler-scheduled.
- PlanMemory/inplace (`MXNET_EXEC_ENABLE_INPLACE`) = XLA buffer
  assignment + donation.  Aux states (BN moving stats) thread through
  functionally and are written back after each step.
- RNG: the executor owns a key chain; each forward folds a fresh key
  into the graph (dropout etc.), reproducible under mx.random.seed.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from .analysis.sanitizers import hooks as _san_hooks
from .base import MXNetError, dtype_np
from .context import Context, current_context
from .ndarray.ndarray import (NDArray, zeros as nd_zeros, _wrap,
                              _copy_buffers)
from .symbol.symbol import build_graph_fn, _infer_graph
from . import telemetry as _telemetry
from .telemetry import phases as _phases, tracing as _trace

__all__ = ["Executor"]


# ops whose backward defines its own head gradient (label-based), so
# backward() with no out_grads is meaningful — the reference's loss-output
# contract (SoftmaxOutput ignores head grads, graph_executor Gradient pass)
_LOSS_OPS = frozenset({
    "SoftmaxOutput", "Softmax", "MakeLoss", "make_loss",
    "LinearRegressionOutput", "LogisticRegressionOutput",
    "MAERegressionOutput", "SVMOutput",
})


class Executor:
    """A bound, compiled computation (reference: python/mxnet/executor.py:45)."""

    def __init__(self, symbol, ctx, arg_dict, grad_dict, aux_dict, grad_req,
                 compute_dtype=None, cast_exclude=()):
        # first bind in the process places the persistent XLA compile
        # cache (compile_cache.placement) so every jit after it —
        # executor fwd/train/fused-step, kvstore reduce, serving binds —
        # reads/writes the shared on-disk cache; one dict read after
        from . import compile_cache as _compile_cache
        _compile_cache.ensure_initialized()
        self._symbol = symbol
        self._compute_dtype = (jnp.dtype(compute_dtype)
                               if compute_dtype is not None else None)
        self._cast_exclude = frozenset(cast_exclude)
        self._ctx = Context(ctx) if ctx is not None else current_context()
        self.arg_names = symbol.list_arguments()
        self.aux_names = symbol.list_auxiliary_states()
        self.output_names = symbol.list_outputs()
        self.arg_dict = arg_dict
        self.grad_dict = grad_dict
        self.aux_dict = aux_dict
        if isinstance(grad_req, str):
            grad_req = {n: grad_req for n in self.arg_names}
        elif isinstance(grad_req, (list, tuple)):
            grad_req = dict(zip(self.arg_names, grad_req))
        self._grad_req = {n: grad_req.get(n, "null") for n in self.arg_names}
        # only args that have a grad buffer get gradients
        self._diff_idx = [i for i, n in enumerate(self.arg_names)
                          if self._grad_req[n] != "null" and grad_dict.get(n) is not None]
        self._outputs = None
        self._cached_grads = None
        self._monitor_callback = None
        # telemetry: a dispatch whose (program, shape-signature) pair is
        # new compiles an XLA program; track pairs so compile count and
        # compile-time histograms come from the bind/dispatch path itself
        # (the serving cache's miss==recompile insight, generalized)
        self._compile_seen = set()
        if _telemetry.enabled():
            _telemetry.counter(
                "mxnet_executor_binds_total",
                "executor binds (each bind's first dispatch per shape "
                "compiles)").inc()
        self._is_loss_graph = bool(symbol._flat_outputs()) and all(
            (not n.is_variable) and n.op.name in _LOSS_OPS
            for (n, _i) in symbol._flat_outputs())
        # keys come from the global host-side counter chain so runs
        # reproduce under mx.random.seed(n) (see random.py docstring)
        from . import random as _mxrandom
        self._last_key = _mxrandom.next_key()

        fn_train = build_graph_fn(symbol, self.arg_names, self.aux_names, True)
        fn_eval = build_graph_fn(symbol, self.arg_names, self.aux_names, False)
        diff_idx = tuple(self._diff_idx)

        from . import config as _config
        if _config.get("MXNET_BACKWARD_DO_MIRROR"):
            # gradient checkpointing: recompute activations in backward
            # instead of keeping them live — the reference's mirror pass
            # (graph_executor.cc:277-291) as jax.checkpoint over the
            # traced forward
            fn_train = jax.checkpoint(fn_train, static_argnums=())

        # mixed-precision policy (compute_dtype='bfloat16'): fp32 master
        # args cast to bf16 at graph entry (labels / excluded names kept);
        # vjp through the cast hands fp32 grads to the optimizer.  The
        # reference's fp16 path (optimizer.py:434 multi-precision) done
        # the compiled-step way.
        cdt = self._compute_dtype
        cast_idx = frozenset(
            i for i, n in enumerate(self.arg_names)
            if cdt is not None and n not in self._cast_exclude)

        def _cast(args):
            if cdt is None:
                return args
            return [a.astype(cdt)
                    if (i in cast_idx and a.dtype == jnp.float32) else a
                    for i, a in enumerate(args)]

        def fwd_eval(args, aux, key):
            return fn_eval(_cast(args), aux, key)

        def fwd_train(args, aux, key):
            return fn_train(_cast(args), aux, key)

        def fb(args, aux, key, seeds):
            diff = [args[i] for i in diff_idx]

            def f(diff_args):
                full = list(args)
                for j, i in enumerate(diff_idx):
                    full[i] = diff_args[j]
                with jax.named_scope(_phases.FWD_SCOPE):
                    outs, new_aux = fn_train(_cast(full), aux, key)
                return tuple(outs), new_aux

            outs, vjp_fn, new_aux = jax.vjp(f, diff, has_aux=True)
            (grads,) = vjp_fn(tuple(seeds))
            return list(outs), list(grads), new_aux

        self._jit_fwd_eval = jax.jit(fwd_eval)
        self._jit_fwd_train = jax.jit(fwd_train)
        self._jit_fb = jax.jit(fb)
        self._fn_train = fn_train
        self._cast_fn = _cast
        # fused optimizer step (install_fused_update): fwd+bwd+update as
        # ONE donated XLA program — the reference's bulked train segment
        # (graph_executor.cc:1336) plus server-side update, compiled
        self._fused_update = None   # (one_fn, scalars_fn)
        self._fused_state = None    # list of state tuples per diff arg
        self._fused_codec = None    # shared gradient-compression codec
        self._fused_resids = None   # error-feedback residuals (codec on)
        self._jit_fbu = None
        self._registered_fbu = None     # the jit telemetry last saw
        self._updates_applied = False

    # -- fused optimizer step ------------------------------------------------
    def install_fused_update(self, optimizer, param_names=None,
                             compression_params=None):
        """Fold the optimizer into the compiled train step (kvstore=tpu).

        After installation, ``forward(is_train=True)`` on a loss graph
        runs fwd+bwd+update as ONE donated XLA program.  Gradients are
        consumed inside the program (XLA frees them without an HBM
        round-trip): ``backward()`` becomes a commit-nothing no-op and
        grad_dict is NOT populated — use the unfused path (kvstore local/
        device) when per-step gradient inspection is needed.
        ``updates_applied`` tells Module.update to skip the push/pull.
        Returns False (and installs nothing) for optimizers without a
        fused kernel, or when ``param_names`` is given and some
        differentiable arg is not a parameter (e.g. inputs_need_grad:
        the optimizer must never be applied to data inputs).

        ``compression_params`` (the ``Module(compression_params=...)`` /
        ``kvstore.set_gradient_compression`` dict) runs the SAME
        gradient-compression codec the kvstore push path and
        ParallelTrainer use inside the compiled step — each gradient is
        encoded/decoded with an error-feedback residual carried in the
        fused state, so the reference C-API contract (compression
        follows the module wherever its update runs) holds on the
        compiled path too instead of being silently dropped."""
        from . import optimizer as opt_mod
        from .gradient_compression import make_codec

        kernel = opt_mod.fused_update_kernel(optimizer)
        if kernel is None or not self._diff_idx or not self._is_loss_graph:
            return False
        if param_names is not None:
            allowed = set(param_names)
            if any(self.arg_names[i] not in allowed for i in self._diff_idx):
                return False
        self._fused_update = (optimizer, kernel[0], kernel[1])
        self._own_donated_buffers()
        self._fused_codec = make_codec(**dict(compression_params)) \
            if compression_params else None
        self._fused_state = None
        self._fused_resids = None
        self._jit_fbu = None
        self._updates_applied = False
        # one-sweep Pallas path (MXNET_PALLAS_FUSED_OPT): the 1-D
        # leaves (biases, norm scales/shifts) are concatenated into
        # contiguous fp32 buckets and each bucket updates in ONE kernel
        # instead of a tail of tiny per-array kernels (ROADMAP item 3);
        # every N-D weight is updated in its own layout.  None is the
        # all-per-array program, which stays the bit-parity oracle.
        self._sweep = self._plan_sweep(optimizer)
        self._export_update_gauges()
        return True

    @property
    def donates_weights(self):
        """True once the fused step is installed: every training
        forward then DELETES the weight buffers it was handed and
        rebinds the arg NDArrays to the step's outputs.  Whoever reads
        weights out of this executor to keep them (Module.get_params
        -> checkpoints, export_serving) must copy, not alias."""
        return self._fused_update is not None

    def _own_donated_buffers(self):
        """Decouple the weight buffers from any master/kvstore/caller
        alias: the fused step donates them, which would invalidate a
        shared buffer under its other holder."""
        nds = [self.arg_dict[self.arg_names[i]] for i in self._diff_idx]
        for nd, c in zip(nds, _copy_buffers(tuple(nd._data for nd in nds))):
            nd._data = c

    def _plan_sweep(self, optimizer):
        """Bucket plan for the one-sweep fused optimizer, or None.

        A leaf rides a flat bucket only if flattening it IS a
        concatenation: ``ndim <= 1`` (biases, BatchNorm/LayerNorm
        gammas and betas).  A row-major 1-D view of an N-D weight is not
        free on the TPU — the compiler keeps e.g. a ``[O, I, 3, 3]``
        convolution weight tiled with the 3x3 dims major, so
        ``reshape(-1)`` is an element-wise re-layout, paid for weights
        in, gradients in and weights out on every step (ResNet-50: 46 ms
        around a 0.6 ms kernel; PERF.md, PR 26).  Those leaves
        (``info["rest"]``) are updated per array in their own layout
        inside the same program.

        The bucketed leaves are grouped by their static (lr_mult,
        wd_mult) pair — each group's members share one effective
        (lr, wd) at every step, so each bucket's hyperparameters stay
        two scalars riding the kernel's scalar-prefetch operand
        (per-element lr/wd vectors would double the sweep's HBM
        traffic).  The reference convention of wd_mult=0 on
        biases/betas makes two groups the common case.  Eligibility:
        SGD/Adam (the kernels we have) over all-fp32 weights, and at
        least one 1-D leaf."""
        from . import config as _config
        from .ops.pallas_kernels import family_enabled
        if not family_enabled("MXNET_PALLAS_FUSED_OPT"):
            return None
        kind = type(optimizer).__name__
        if kind not in ("SGD", "Adam"):
            return None
        names = [self.arg_names[i] for i in self._diff_idx]
        if any(self.arg_dict[n].dtype != np.float32 for n in names):
            return None
        from .parallel.collectives import build_bucket_plan
        groups, rest = {}, []
        for j, n in enumerate(names):
            if len(self.arg_dict[n].shape) > 1:
                rest.append(j)
                continue
            key = (float(optimizer._param_mult(n, optimizer.lr_mult,
                                               "lr_mult")),
                   float(optimizer._param_mult(n, optimizer.wd_mult,
                                               "wd_mult")))
            groups.setdefault(key, []).append(j)
        if not groups:
            return None
        cap = _config.get("MXNET_PALLAS_OPT_BUCKET_BYTES")
        plan = []
        for key in sorted(groups):
            idxs = groups[key]
            buckets = build_bucket_plan(
                [names[j] for j in idxs],
                [self.arg_dict[names[j]].shape for j in idxs],
                cap, pad_multiple=1)
            pos = {names[j]: j for j in idxs}
            for b in buckets:
                plan.append((b, [pos[n] for n in b.names]))
        # the per-array kernels (optimizer_ops._prep_grad) treat any
        # NEGATIVE clip as "disabled" — normalize the sentinel to None
        # so the sweep kernels' is-not-None gate agrees with the oracle
        clip = optimizer.clip_gradient
        if clip is not None and clip < 0:
            clip = None
        info = {"kind": kind.lower(), "plan": plan, "rest": rest,
                "rescale": float(optimizer.rescale_grad), "clip": clip}
        if kind == "SGD":
            info["momentum"] = float(optimizer.momentum)
        else:
            info.update(beta1=float(optimizer.beta1),
                        beta2=float(optimizer.beta2),
                        epsilon=float(optimizer.epsilon))
        return info

    def _export_update_gauges(self):
        """How the installed update splits the leaves, set when the plan
        is made or dropped (never per step)."""
        if not _telemetry.enabled():
            return
        swept = {j for _b, idxs in self._sweep["plan"] for j in idxs} \
            if self._sweep is not None else set()
        split = {"sweep": [], "per_array": []}
        for j, i in enumerate(self._diff_idx):
            split["sweep" if j in swept else "per_array"].append(
                self.arg_dict[self.arg_names[i]])
        g_leaves = _telemetry.gauge(
            "mxnet_fused_update_leaves",
            "leaves of the newest fused executor step by update path "
            "(sweep = 1-D leaves in flat Pallas buckets, per_array = "
            "updated in their own layout)")
        g_bytes = _telemetry.gauge(
            "mxnet_fused_update_bytes",
            "weight bytes of the newest fused executor step by update "
            "path (sweep / per_array)")
        for path, arrs in split.items():
            g_leaves.labels(path=path).set(len(arrs))
            g_bytes.labels(path=path).set(sum(
                int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
                for a in arrs))

    @property
    def updates_applied(self):
        return self._updates_applied

    def _sweep_update(self, diff, grads, states, lrs, wds):
        """The mixed update of a sweep plan.  Each bucket's 1-D weights
        and gradients are concatenated into contiguous fp32 buffers and
        updated by ONE Pallas kernel (ops/pallas_kernels.py); every
        remaining (N-D) leaf is updated in its own layout by the
        per-array kernel, so no weight is ever re-laid.  states / lrs /
        wds are packed per bucket, then per remaining leaf
        (:meth:`_state_like`).  Returns (new_diff, new_states)."""
        from .ops import pallas_kernels as pk
        from .parallel.collectives import flatten_bucket, unflatten_bucket
        sw = self._sweep
        one = self._fused_update[2]
        new_diff = list(diff)
        new_states = []
        for bi, (b, idxs) in enumerate(sw["plan"]):
            wf = flatten_bucket([diff[j] for j in idxs], b)
            gf = flatten_bucket([grads[j] for j in idxs], b)
            with jax.named_scope(_phases.SWEEP_SCOPE):
                if sw["kind"] == "sgd":
                    # tuple arity is static at trace time (len, not value)
                    mom = states[bi][0] if len(states[bi]) else None
                    nw, nm = pk.fused_sgd_momentum(
                        wf, gf, mom, lr=lrs[bi], momentum=sw["momentum"],
                        wd=wds[bi], rescale=sw["rescale"], clip=sw["clip"])
                    new_states.append((nm,) if nm is not None else ())
                else:
                    nw, nm, nv = pk.fused_adam(
                        wf, gf, states[bi][0], states[bi][1],
                        lr_eff=lrs[bi], beta1=sw["beta1"],
                        beta2=sw["beta2"], epsilon=sw["epsilon"],
                        wd=wds[bi], rescale=sw["rescale"], clip=sw["clip"])
                    new_states.append((nm, nv))
            views = unflatten_bucket(nw, b)
            for j, name in zip(idxs, b.names):
                new_diff[j] = views[name].astype(diff[j].dtype)
        for k, j in enumerate(sw["rest"], len(sw["plan"])):
            new_diff[j], nst = one(diff[j], grads[j], states[k],
                                   lrs[k], wds[k])
            new_states.append(nst)
        return new_diff, new_states

    def _state_like(self, diff):
        """One shape/dtype carrier per entry of the fused state (and of
        the packed lrs / wds), in order: per weight on the per-array
        path; on the sweep per BUCKET (its flat fp32 buffer), then per
        remaining leaf."""
        sw = self._sweep
        if sw is None:
            return list(diff)
        return [jax.ShapeDtypeStruct((b.n,), jnp.float32)
                for b, _idxs in sw["plan"]] + [diff[j] for j in sw["rest"]]

    def _demote_sweep(self):
        """Permanently fall back from the sweep to the per-array path
        (a runtime multiplier change invalidated the bucket grouping):
        bucket-major slots are sliced back into per-weight arrays, the
        remaining leaves' slots move to their weight's index — values
        bit-identical — and the fused program rebuilds on the next
        dispatch."""
        from .parallel.collectives import unflatten_bucket
        sw = self._sweep
        if self._fused_state is not None:
            per = [()] * len(self._diff_idx)
            for bi, (b, idxs) in enumerate(sw["plan"]):
                views = [unflatten_bucket(s, b)
                         for s in self._fused_state[bi]]
                for j, name in zip(idxs, b.names):
                    per[j] = tuple(v[name] for v in views)
            for k, j in enumerate(sw["rest"], len(sw["plan"])):
                per[j] = self._fused_state[k]
            self._fused_state = per
        self._sweep = None
        self._jit_fbu = None
        self._export_update_gauges()

    def _build_fbu(self):
        import jax as _jax

        diff_idx = tuple(self._diff_idx)
        fn_train, _cast = self._fn_train, self._cast_fn
        one = self._fused_update[2]
        codec = getattr(self, "_fused_codec", None)
        sweep = getattr(self, "_sweep", None)

        def fbu(diff, rest, aux, key_data, seeds, states, resids, lrs, wds):
            # the key chain crosses the program boundary as RAW uint32
            # data (random.next_key_data), wrapped into a typed key here
            key = _jax.random.wrap_key_data(key_data, impl="threefry2x32")

            def f(diff_args):
                full = list(rest)
                for j, i in enumerate(diff_idx):
                    full[i] = diff_args[j]
                # the phase scopes (telemetry/phases.py) are metadata
                # only: forward instructions come out of the compiler as
                # jvp(mx_fwd), backward ones as transpose(jvp(mx_fwd))
                with _jax.named_scope(_phases.FWD_SCOPE):
                    outs, new_aux = fn_train(_cast(full), aux, key)
                return tuple(outs), new_aux

            outs, vjp_fn, new_aux = _jax.vjp(f, list(diff), has_aux=True)
            (grads,) = vjp_fn(tuple(seeds))
            # gradient compression INSIDE the compiled step: the same
            # codec roundtrip the kvstore push path applies, with the
            # error-feedback residual carried across steps in the fused
            # state — Module(compression_params=...) numerics are
            # identical whether the update runs eagerly or compiled
            new_resids = resids
            if codec is not None:
                decoded, new_resids = [], []
                with _jax.named_scope(_phases.CODEC_SCOPE):
                    for g, r in zip(grads, resids):
                        d, nr = codec.roundtrip(g.astype(jnp.float32), r)
                        decoded.append(d.astype(g.dtype))
                        new_resids.append(nr)
                grads = decoded
            # lrs/wds are ONE packed array each (per weight on the
            # per-array path; on the sweep per BUCKET, then per
            # remaining leaf) — one host transfer when the schedule
            # moves, not one per scalar
            with _jax.named_scope(_phases.UPDATE_SCOPE):
                if sweep is not None:
                    new_diff, new_states = self._sweep_update(
                        diff, grads, states, lrs, wds)
                else:
                    new_diff, new_states = [], []
                    for j, (w, g, st) in enumerate(zip(diff, grads,
                                                       states)):
                        nw, nst = one(w, g, st, lrs[j], wds[j])
                        new_diff.append(nw)
                        new_states.append(nst)
            # grads are consumed in-program (XLA frees them); they are not
            # outputs — saves an HBM round-trip per step.  backward() is a
            # no-op in fused mode (grad_dict intentionally not populated).
            # The RNG key advances INSIDE the program so back-to-back
            # steps need no host work at all: step i+1 consumes the key
            # step i emitted (device-closed chain, no per-step
            # host-to-device transfer to wait on).
            new_key = _jax.random.fold_in(key, 1)
            return (list(outs), new_diff, new_states, new_resids, new_aux,
                    _jax.random.key_data(new_key))

        # donate weights + optimizer state + compression residuals
        # (exclusively owned: the arg NDArrays are rebound to the
        # outputs right after the call)
        return _jax.jit(fbu, donate_argnums=(0, 5, 6))

    def _fused_lr_wd(self, optimizer):
        """This step's (lrs, wds) as device arrays, packed in
        :meth:`_state_like`'s order, advancing the optimizer's schedule
        bookkeeping for every weight."""
        from . import optimizer as opt_mod
        sweep = getattr(self, "_sweep", None)
        lrs, wds = [], []
        for i in self._diff_idx:
            lr, wd = opt_mod.fused_lr_wd(optimizer, self.arg_names[i])
            lrs.append(lr)
            wds.append(wd)
        if sweep is not None and any(
                lrs[j] != lrs[idxs[0]] or wds[j] != wds[idxs[0]]
                for _b, idxs in sweep["plan"] for j in idxs):
            # a set_lr_mult/set_wd_mult AFTER install broke the
            # uniform-bucket contract the plan was grouped under —
            # permanently demote to the per-array path (slot values
            # carried over bit-for-bit) rather than stepping bucket
            # members with a stale group lr/wd
            self._demote_sweep()
            sweep = None
        if sweep is not None:
            # per-BUCKET scalars, then the remaining leaves' own: every
            # member of a bucket shares its static (lr_mult, wd_mult),
            # so the first member's effective values are the bucket's
            # (the per-index loop above still ran — num_update
            # bookkeeping advances for every weight)
            order = [idxs[0] for _b, idxs in sweep["plan"]] + sweep["rest"]
            lrs = [lrs[j] for j in order]
            wds = [wds[j] for j in order]
        lrs = np.asarray(lrs, np.float32)
        wds = np.asarray(wds, np.float32)
        # device-resident lr/wd cache, refreshed only when the schedule
        # moves — no host transfer on a steady-state step
        cached = getattr(self, "_lr_wd_cache", None)
        if cached is None or not (np.array_equal(cached[0], lrs)
                                  and np.array_equal(cached[1], wds)):
            self._lr_wd_cache = (lrs, wds, jnp.asarray(lrs), jnp.asarray(wds))
        return self._lr_wd_cache[2], self._lr_wd_cache[3]

    def _forward_fused(self, args, aux, key):
        optimizer = self._fused_update[0]
        init_state = self._fused_update[1]
        diff_set = set(self._diff_idx)
        diff = [args[i] for i in self._diff_idx]
        # None placeholders where diff args go (overwritten inside the
        # program) — the donated weight buffers must not appear twice
        rest = [None if i in diff_set else a for i, a in enumerate(args)]
        if self._fused_state is None:
            # host-built zeros (init_state's _host_zeros_like): no XLA
            # broadcast compile per bucket or weight shape
            self._fused_state = [init_state(d)
                                 for d in self._state_like(diff)]
        if self._fused_resids is None:
            # error-feedback residuals, one per weight when a codec is
            # installed (empty pytree otherwise: ONE program shape)
            self._fused_resids = [
                jnp.zeros(d.shape, jnp.float32) for d in diff] \
                if getattr(self, "_fused_codec", None) is not None else []
        with _trace.span("executor.hyper"):
            lrs_dev, wds_dev = self._fused_lr_wd(optimizer)
        # key chain: consume the device key-DATA the previous step
        # emitted; first call seeds from the host counter chain
        key_dev = getattr(self, "_fused_key", None)
        if key_dev is None:
            from . import random as _mxrandom
            key_dev = _mxrandom.next_key_data()
        seeds = self._default_seeds(args, aux, key)
        if self._jit_fbu is None:
            self._jit_fbu = self._build_fbu()
        self._replay_key_data = key_dev  # for backward(out_grads) replay
        # graftsan donation sanitizer: the dispatch below consumes
        # (donate_argnums=(0, 5, 6)) these exact arrays — snapshot the
        # references first so post-donation use can be attributed
        donated = None
        if _san_hooks.DONATION[0]:
            import jax.tree_util as _tree
            donated = (list(diff)
                       + _tree.tree_leaves(self._fused_state)
                       + _tree.tree_leaves(self._fused_resids))
        call = (diff, rest, aux, key_dev, seeds, self._fused_state,
                self._fused_resids, lrs_dev, wds_dev)
        if _telemetry.enabled() and \
                self._registered_fbu is not self._jit_fbu:
            # so that telemetry.program_hlo("fbu") can name the phase of
            # every instruction after the fact; nothing compiles here
            _telemetry.register_program("fbu", self._jit_fbu, call)
            self._registered_fbu = self._jit_fbu
        with _trace.span("executor.dispatch"):
            outs, new_diff, new_states, new_resids, new_aux, new_key = \
                self._dispatch_compiled("fbu", self._jit_fbu, diff, *call)
        with _trace.span("executor.rebind"):
            self._fused_key = new_key
            self._fused_state = new_states
            self._fused_resids = new_resids
            for j, i in enumerate(self._diff_idx):
                self.arg_dict[self.arg_names[i]]._data = new_diff[j]
            self._cached_grads = None
            self._updates_applied = True
        if donated is not None:
            # after the rebinds: any executor slot (or later NDArray
            # read) still referencing a donated buffer is a defect
            _san_hooks.on_donated_dispatch(self, donated, "fbu")
        return outs, new_aux

    # -- binding constructors ----------------------------------------------
    @staticmethod
    def _simple_bind(symbol, ctx, grad_req, type_dict, shape_kwargs,
                     shared_exec=None, compute_dtype=None, cast_exclude=()):
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        known = {k: tuple(v) for k, v in shape_kwargs.items()
                 if not isinstance(v, str)}
        shapes, _, aux_shapes = _infer_graph(symbol, known, {})
        type_dict = type_dict or {}
        arg_dict, grad_dict, aux_dict = {}, {}, {}
        for n in arg_names:
            shp = shapes.get(n)
            if shp is None:
                raise MXNetError("simple_bind could not infer shape of %r" % n)
            dt = dtype_np(type_dict.get(n, np.float32))
            if (shared_exec is not None and n in shared_exec.arg_dict
                    and shared_exec.arg_dict[n].shape == tuple(shp)):
                arg_dict[n] = shared_exec.arg_dict[n]
            else:
                arg_dict[n] = nd_zeros(shp, ctx=ctx, dtype=dt)
        if isinstance(grad_req, dict):
            req = grad_req
        elif isinstance(grad_req, (list, tuple)):
            req = dict(zip(arg_names, grad_req))
        else:
            req = {n: grad_req for n in arg_names}
        for n in arg_names:
            if req.get(n, "null") != "null":
                grad_dict[n] = nd_zeros(arg_dict[n].shape, ctx=ctx,
                                        dtype=arg_dict[n].dtype)
        for n in aux_names:
            shp = aux_shapes.get(n) or shapes.get(n)
            if shp is None:
                raise MXNetError("simple_bind could not infer aux shape of %r" % n)
            if (shared_exec is not None and n in shared_exec.aux_dict
                    and shared_exec.aux_dict[n].shape == tuple(shp)):
                aux_dict[n] = shared_exec.aux_dict[n]
            else:
                aux_dict[n] = nd_zeros(shp, ctx=ctx)
        return Executor(symbol, ctx, arg_dict, grad_dict, aux_dict, grad_req,
                        compute_dtype=compute_dtype, cast_exclude=cast_exclude)

    @staticmethod
    def _bind(symbol, ctx, args, args_grad, grad_req, aux_states,
              shared_exec=None, compute_dtype=None, cast_exclude=()):
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        if isinstance(args, dict):
            arg_dict = dict(args)
        else:
            arg_dict = dict(zip(arg_names, args))
        missing = [n for n in arg_names if n not in arg_dict]
        if missing:
            raise MXNetError("bind: missing arguments %s" % missing)
        if args_grad is None:
            grad_dict = {}
        elif isinstance(args_grad, dict):
            grad_dict = dict(args_grad)
        else:
            grad_dict = dict(zip(arg_names, args_grad))
        if aux_states is None:
            aux_dict = {}
        elif isinstance(aux_states, dict):
            aux_dict = dict(aux_states)
        else:
            aux_dict = dict(zip(aux_names, aux_states))
        for n in aux_names:
            if n not in aux_dict:
                known = {m: arg_dict[m].shape for m in arg_names}
                _, _, aux_shapes = _infer_graph(symbol, known, {})
                aux_dict = {**{a: nd_zeros(aux_shapes[a], ctx=ctx)
                               for a in aux_names if a in aux_shapes}, **aux_dict}
                break
        return Executor(symbol, ctx, arg_dict, grad_dict, aux_dict, grad_req,
                        compute_dtype=compute_dtype, cast_exclude=cast_exclude)

    # -- execution ----------------------------------------------------------
    @property
    def outputs(self):
        if self._outputs is None:
            raise MXNetError("run forward() first")
        return self._outputs

    def _next_key(self):
        # host-side counter chain, like random.next_key(): a device-side
        # split would dispatch a tiny kernel per step, serializing
        # against the in-flight train step
        from . import random as _mxrandom
        sub = _mxrandom.next_key()
        self._last_key = sub
        return sub

    def _dispatch_compiled(self, tag, fn, sig_arrays, *call_args):
        """Dispatch a jitted program, accounting XLA compiles.

        A compile is detected EXACTLY: jax's jit cache growing across
        the call (``_cache_size``), so a program compiled before
        telemetry was enabled is never miscounted as a recompile when a
        measurement window opens mid-run.  Disabled telemetry pays one
        boolean check and an extra frame.  Fallback for jit objects
        without a cache-size probe: a per-executor (tag, shapes)
        signature set.  What the compile cost, by stage and for every
        program of the process, is ``compile_cache``'s to observe (the
        ``xla.trace`` / ``xla.lower`` / ``xla.compile`` spans).

        The graftsan recompile sanitizer shares this exact detection:
        when armed, every observed compile is forwarded with its shape
        signature and the count of signatures this program had already
        compiled — inside a steady-state region that event is a
        san-recompile finding (docs/faq/static_analysis.md)."""
        from . import telemetry
        san_on = _san_hooks.RECOMPILE[0]
        if not telemetry.enabled() and not san_on:
            return fn(*call_args)
        sig = None
        size_fn = getattr(fn, "_cache_size", None)
        if size_fn is not None:
            before = size_fn()
            out = fn(*call_args)
            compiled = size_fn() > before
        else:
            sig = (tag, tuple(tuple(a.shape) for a in sig_arrays))
            compiled = sig not in self._compile_seen
            out = fn(*call_args)
        if compiled:
            # the signature tuple is O(arg count) to build — only pay
            # for it on the rare compiling dispatch (or the fallback
            # branch above, which needs it for detection itself)
            if sig is None:
                sig = (tag, tuple(tuple(a.shape) for a in sig_arrays))
            prior = sum(1 for s in self._compile_seen if s[0] == tag)
            self._compile_seen.add(sig)
            if telemetry.enabled():
                telemetry.counter(
                    "mxnet_xla_compiles_total",
                    "XLA program compilations observed at dispatch "
                    "(jit-cache growth; cache-miss == recompile)").inc()
            if san_on:
                _san_hooks.on_compile(tag, sig[1], prior)
        return out

    def _args(self):
        return [self.arg_dict[n]._data for n in self.arg_names]

    def _aux(self):
        return [self.aux_dict[n]._data for n in self.aux_names]

    def forward(self, is_train=False, **kwargs):
        """Reference: executor.py:113 -> GraphExecutor::Forward.

        For loss-headed graphs (the Module.fit hot path) a training
        forward runs ONE fused fwd+bwd XLA program and caches gradients
        for the no-args backward() — the reference's bulked segments,
        compiler-scheduled.  For feature graphs (head grads unknown until
        backward(out_grads)) it runs forward only; backward dispatches
        the fused program once with the real seeds."""
        for k, v in kwargs.items():
            if k not in self.arg_dict:
                raise MXNetError("unknown forward argument %r" % k)
            tgt = self.arg_dict[k]
            if isinstance(v, NDArray):
                tgt._data = v._data.astype(tgt.dtype) if v.dtype != tgt.dtype else v._data
            else:
                # h2d staging of a host-provided feed (numpy/list), not
                # a device round-trip — np.asarray on host data is free
                tgt._data = jnp.asarray(np.asarray(v), dtype=tgt.dtype)  # graftlint: disable=host-sync
        args, aux = self._args(), self._aux()
        if is_train and self._fused_update is not None:
            # steady-state fused steps consume the device-resident key
            # the previous step emitted — don't mint (device_put) a new
            # one per call
            key = (self._last_key if getattr(self, "_fused_key", None)
                   is not None else self._next_key())
            outs, new_aux = self._forward_fused(args, aux, key)
        elif is_train and self._diff_idx and self._is_loss_graph:
            key = self._next_key()
            seeds = self._default_seeds(args, aux, key)
            outs, grads, new_aux = self._dispatch_compiled(
                "fb", self._jit_fb, args, args, aux, key, seeds)
            self._cached_grads = grads
            self._updates_applied = False
        else:
            key = self._next_key()
            outs, new_aux = (
                self._dispatch_compiled("fwd_train", self._jit_fwd_train,
                                        args, args, aux, key)
                if is_train else
                self._dispatch_compiled("fwd_eval", self._jit_fwd_eval,
                                        args, args, aux, key))
            self._cached_grads = None
        self._commit(outs, new_aux)
        if self._monitor_callback is not None and \
                getattr(self, "_monitor_all", False):
            self._run_monitor_taps(args, aux, key, is_train)
        return self._outputs

    def _commit(self, outs, new_aux):
        for n, a in zip(self.aux_names, new_aux):
            self.aux_dict[n]._data = a
        self._outputs = [_wrap(o) for o in outs]
        if self._monitor_callback is not None and \
                not getattr(self, "_monitor_all", False):
            # with monitor_all the heads are reported by the internals
            # program (_run_monitor_taps) — reporting here too would
            # duplicate them in the monitor's queue
            for name, o in zip(self.output_names, self._outputs):
                self._monitor_callback(name, o)

    def _default_seeds(self, args, aux, key):
        sig = tuple(a.shape for a in args)
        cache = getattr(self, "_seed_cache", None)
        if cache is None or cache[0] != sig:
            outs_shape = jax.eval_shape(self._jit_fwd_train, args, aux, key)[0]
            self._seed_cache = (sig, [jnp.asarray(np.ones(o.shape, o.dtype))
                                      for o in outs_shape])
        return self._seed_cache[1]

    def backward(self, out_grads=None, is_train=True):
        """Reference: executor.py:154 -> GraphExecutor::Backward.

        With no out_grads, gradients were already computed fused with
        forward(is_train=True) — this just commits them to the grad
        arrays (kWriteTo/kAddTo semantics)."""
        if not self._diff_idx:
            return
        if out_grads is None and self._updates_applied:
            # fused step: gradients were consumed by the in-program
            # optimizer update; nothing to commit
            return
        if out_grads is not None:
            if isinstance(out_grads, NDArray):
                out_grads = [out_grads]
            seeds = [g._data if isinstance(g, NDArray) else jnp.asarray(g)
                     for g in out_grads]
            # reuse the key of the preceding forward so stochastic ops
            # (dropout) see the same mask the user observed.  In fused
            # mode the key advances on-device — _replay_key_data tracks
            # the key data the last fused step actually consumed.
            replay = getattr(self, "_replay_key_data", None)
            if replay is not None:
                key = jax.random.wrap_key_data(jnp.asarray(replay),
                                               impl="threefry2x32")
            else:
                key = self._last_key
            args, aux = self._args(), self._aux()
            _, grads, _ = self._dispatch_compiled(
                "fb", self._jit_fb, args, args, aux, key, seeds)
        else:
            if self._cached_grads is None:
                raise MXNetError(
                    "backward() without out_grads requires a loss-output "
                    "graph and a preceding forward(is_train=True)")
            grads = self._cached_grads
        for j, i in enumerate(self._diff_idx):
            n = self.arg_names[i]
            g = self.grad_dict.get(n)
            if g is None:
                continue
            if self._grad_req[n] == "add":
                g._data = g._data + grads[j]
            else:
                g._data = grads[j].astype(g.dtype)

    # -- reference API surface ----------------------------------------------
    @property
    def arg_arrays(self):
        return [self.arg_dict[n] for n in self.arg_names]

    @property
    def grad_arrays(self):
        return [self.grad_dict.get(n) for n in self.arg_names]

    @property
    def aux_arrays(self):
        return [self.aux_dict[n] for n in self.aux_names]

    def copy_params_from(self, arg_params, aux_params=None,
                         allow_extra_params=False):
        """Reference: executor.py copy_params_from."""
        for k, v in arg_params.items():
            if k in self.arg_dict:
                if tuple(v.shape) != self.arg_dict[k].shape:
                    raise MXNetError(
                        "shape mismatch for parameter %r: %s vs executor %s"
                        % (k, v.shape, self.arg_dict[k].shape))
                self.arg_dict[k]._data = v._data.astype(self.arg_dict[k].dtype)
            elif not allow_extra_params:
                raise MXNetError("unknown parameter %r" % k)
        if aux_params:
            for k, v in aux_params.items():
                if k in self.aux_dict:
                    if tuple(v.shape) != self.aux_dict[k].shape:
                        raise MXNetError(
                            "shape mismatch for aux state %r: %s vs executor %s"
                            % (k, v.shape, self.aux_dict[k].shape))
                    self.aux_dict[k]._data = v._data.astype(self.aux_dict[k].dtype)
                elif not allow_extra_params:
                    raise MXNetError("unknown aux state %r" % k)
        if self.donates_weights:
            # same-dtype astype above ALIASES the caller's buffers
            self._own_donated_buffers()

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Rebind with new data shapes, sharing parameter arrays
        (reference: MXExecutorReshape — bucketing/variable batch).  On TPU
        this is a new jit cache entry; XLA recompiles per shape.

        Flag contract (reference src/c_api/c_api_executor.cc Reshape):
        growing a PROVIDED argument needs ``allow_up_sizing=True``; a
        shape change inferred onto an UNSPECIFIED argument (typically a
        parameter, whose trained values would be replaced) needs
        ``partial_shaping=True`` — silently zeroing weights is exactly
        the failure this guards."""
        import numpy as _np

        new_shapes = {k: tuple(v) for k, v in kwargs.items()}
        shapes, _, aux_shapes = _infer_graph(self._symbol, dict(new_shapes), {})
        for n in self.arg_names:
            cur = self.arg_dict[n].shape
            new = shapes.get(n)
            if n in new_shapes:
                if new is not None and \
                        _np.prod(new, dtype=_np.int64) > \
                        _np.prod(cur, dtype=_np.int64) and \
                        not allow_up_sizing:
                    raise MXNetError(
                        "reshape: arg %r grows %s -> %s; set "
                        "allow_up_sizing=True to permit reallocation"
                        % (n, cur, new))
            elif new is not None and new != cur and not partial_shaping:
                raise MXNetError(
                    "reshape: unspecified arg %r would change shape "
                    "%s -> %s (its contents would be re-initialized); "
                    "set partial_shaping=True to permit this"
                    % (n, cur, new))
        arg_dict, grad_dict = {}, {}
        for n in self.arg_names:
            if n in new_shapes or shapes.get(n) != self.arg_dict[n].shape:
                arg_dict[n] = nd_zeros(shapes[n], ctx=self._ctx,
                                       dtype=self.arg_dict[n].dtype)
            else:
                arg_dict[n] = self.arg_dict[n]
            if self._grad_req[n] != "null":
                grad_dict[n] = nd_zeros(arg_dict[n].shape, ctx=self._ctx,
                                        dtype=arg_dict[n].dtype)
        return Executor(self._symbol, self._ctx, arg_dict, grad_dict,
                        dict(self.aux_dict), self._grad_req,
                        compute_dtype=self._compute_dtype,
                        cast_exclude=self._cast_exclude)

    def set_monitor_callback(self, callback, monitor_all=False):
        """Reference: graph_executor.cc:121,1444 monitor tap.

        With ``monitor_all=True`` every internal node output is fed to
        the callback after each forward (the reference taps each engine
        op as it completes).  The compiled step never materializes
        intermediates, so monitoring runs a SEPARATE jitted program
        built from ``symbol.get_internals()`` — slower, like the
        reference's monitored runs, and only while installed."""
        self._monitor_callback = callback
        self._monitor_all = bool(monitor_all)
        self._monitor_fn = None

    def _run_monitor_taps(self, args, aux, key, is_train):
        """Compute + report every internal activation (monitor_all).

        The internals program is built in the SAME mode as the step it
        mirrors (dropout active, BatchNorm on batch stats when
        is_train) and replays the step's RNG key, so reported
        activations match what the monitored step computed — the
        reference taps the actually-executed op outputs
        (graph_executor.cc:1444)."""
        internals = self._symbol.get_internals()
        if self._monitor_fn is None:
            self._monitor_fn = {}
        if is_train not in self._monitor_fn:
            fn = build_graph_fn(internals, self.arg_names, self.aux_names,
                                is_train)
            self._monitor_fn[is_train] = (
                jax.jit(lambda a, x, k: fn(a, x, k)[0]),
                internals.list_outputs())
        jit_fn, names = self._monitor_fn[is_train]
        outs = jit_fn(self._cast_fn(args), aux, key)
        arg_names = set(self.arg_names) | set(self.aux_names)
        for name, o in zip(names, outs):
            # report op outputs only — variables (args/aux) are covered
            # by Monitor.toc's own argument snapshot, as in the
            # reference's engine tap (op completions, not variables)
            if name not in arg_names:
                self._monitor_callback(name, _wrap(o))

    def debug_str(self):
        return self._symbol.debug_str()

    def step_callable(self, mode="train"):
        """Export a compiled-step program for ABSTRACT analysis
        (graftir, ``analysis/ir/``): ``(jitted_fn, args)`` where the
        args mirror one real dispatch as ``ShapeDtypeStruct``s (plus a
        concrete RNG key — key minting is host work, not a compile).
        Tracing/lowering the pair never compiles or dispatches.

        Modes: ``eval`` (inference forward), ``train`` (the fused
        fwd+bwd program for loss graphs, plain train forward
        otherwise), ``fused`` (the donated fwd+bwd+optimizer step —
        requires :meth:`install_fused_update`; state/residual/lr
        operands are staged exactly as ``_forward_fused`` stages them,
        without advancing the optimizer's schedule bookkeeping)."""
        import jax as _jax

        from . import random as _mxrandom

        def _sds(arr):
            return _jax.ShapeDtypeStruct(tuple(arr.shape),
                                         np.dtype(arr.dtype))

        args = [_sds(self.arg_dict[n]) for n in self.arg_names]
        aux = [_sds(self.aux_dict[n]) for n in self.aux_names]
        # analysis must be RNG-neutral: minting trace keys off the
        # global chain would shift every later draw and break the
        # checkpoint-resume bit-identical contract (random.set_state)
        rng_snapshot = _mxrandom.get_state()
        try:
            key = _mxrandom.next_key()
            key_data = _mxrandom.next_key_data()
        finally:
            _mxrandom.set_state(rng_snapshot)
        if mode == "eval":
            return self._jit_fwd_eval, (args, aux, key)
        if mode == "train":
            if self._diff_idx and self._is_loss_graph:
                outs = _jax.eval_shape(self._jit_fwd_train, args, aux,
                                       key)[0]
                seeds = [_jax.ShapeDtypeStruct(o.shape, o.dtype)
                         for o in outs]
                return self._jit_fb, (args, aux, key, seeds)
            return self._jit_fwd_train, (args, aux, key)
        if mode != "fused":
            raise MXNetError("step_callable mode must be eval/train/"
                             "fused; got %r" % (mode,))
        if self._fused_update is None:
            raise MXNetError("step_callable('fused') requires "
                             "install_fused_update() first")
        diff_set = set(self._diff_idx)
        diff = [args[i] for i in self._diff_idx]
        rest = [None if i in diff_set else a for i, a in enumerate(args)]
        init_state = self._fused_update[1]
        like = self._state_like(diff)
        if self._fused_state is not None:
            states = _jax.tree_util.tree_map(_sds, self._fused_state)
        else:
            # slots are zeros_like(carrier) (fused_update_kernel's
            # init_state contract) — build ONE prototype to learn the
            # slot count/dtypes, then mirror abstractly per entry
            # instead of allocating the full state
            proto = init_state(like[0]) if like else ()
            states = [tuple(_jax.ShapeDtypeStruct(d.shape, s.dtype)
                            for s in proto) for d in like]
        resids = ([_jax.ShapeDtypeStruct(d.shape, jnp.float32)
                   for d in diff]
                  if getattr(self, "_fused_codec", None) is not None
                  else [])
        lrs = _jax.ShapeDtypeStruct((len(like),), jnp.float32)
        wds = _jax.ShapeDtypeStruct((len(like),), jnp.float32)
        outs = _jax.eval_shape(self._jit_fwd_train, args, aux, key)[0]
        seeds = [_jax.ShapeDtypeStruct(o.shape, o.dtype) for o in outs]
        if self._jit_fbu is None:
            self._jit_fbu = self._build_fbu()
        return self._jit_fbu, (diff, rest, aux, key_data, seeds, states,
                               resids, lrs, wds)

    def program_plan(self):
        """This bound program, declaratively, for graftplan
        (``analysis/plan/``): the symbol-JSON graph plus the bound
        array shapes/dtypes.  graftplan's stdlib shape interpreter and
        activation-liveness walk (the reference's ``infer_shape`` +
        plan-memory passes, done pre-bind) run over exactly this —
        no trace, no XLA compile."""
        import json as _json
        params = []
        inputs = {}
        for name in self.arg_names + self.aux_names:
            arr = self.arg_dict.get(name)
            if arr is None:
                arr = self.aux_dict.get(name)
            if arr is None:
                continue
            shape = [int(s) for s in arr.shape]
            inputs[name] = tuple(shape)
            params.append({
                "name": name, "shape": shape,
                "dtype_size": int(np.dtype(arr.dtype).itemsize),
                "trainable": self._grad_req.get(name, "null") != "null",
                "spec": None})
        return {"graph": _json.loads(self._symbol.tojson()),
                "inputs": inputs, "params": params}
