"""Module — symbolic training on one or more devices.

Reference: ``python/mxnet/module/module.py:40`` — bind (:364),
init_params (:259), init_optimizer (:473, decides update-on-kvstore vs
local updater), forward/backward, update (:631), save/load_checkpoint.
"""
from __future__ import annotations

import logging
import warnings

from .. import context as ctx_mod
from .. import optimizer as opt
from .. import ndarray
from ..base import MXNetError
from ..context import cpu, current_context
from ..initializer import Uniform, InitDesc
from ..model import (_create_kvstore, _initialize_kvstore, _update_params,
                     _update_params_on_kvstore, load_checkpoint,
                     save_checkpoint)
from ..ndarray import zeros
from ..telemetry import tracing as _tracing
from .base_module import BaseModule, _check_input_names
from .executor_group import DataParallelExecutorGroup

__all__ = ["Module"]


class Module(BaseModule):
    """Module over a Symbol (reference: module.py:40)."""

    def __init__(self, symbol, data_names=("data",),
                 label_names=("softmax_label",), logger=logging,
                 context=None, work_load_list=None, fixed_param_names=None,
                 state_names=None, group2ctxs=None,
                 compression_params=None, compute_dtype=None):
        super().__init__(logger=logger)
        # compute_dtype='bfloat16': executor-level mixed precision — fp32
        # master params, bf16 compute; labels stay fp32 (the reference's
        # --dtype float16 training mode, TPU-native)
        self._compute_dtype = compute_dtype
        if context is None:
            context = current_context()
        if isinstance(context, ctx_mod.Context):
            context = [context]
        self._context = context
        if work_load_list is None:
            work_load_list = [1] * len(self._context)
        assert len(work_load_list) == len(self._context)
        self._work_load_list = work_load_list

        self._symbol = symbol

        # validate + normalize every declared input-name group in one
        # sweep (label names only warn: scripts routinely bind label-free
        # symbols for inference)
        groups = {}
        for typename, names in (("data", data_names), ("label", label_names),
                                ("state", state_names),
                                ("fixed_param", fixed_param_names)):
            names = list(names) if names is not None else []
            _check_input_names(symbol, names, typename,
                               throw=typename != "label")
            groups[typename] = names
        self._data_names = groups["data"]
        self._label_names = groups["label"]
        self._state_names = groups["state"]
        self._fixed_param_names = groups["fixed_param"]
        non_params = set(self._data_names + self._label_names
                         + self._state_names)
        self._param_names = [a for a in symbol.list_arguments()
                             if a not in non_params]
        self._aux_names = symbol.list_auxiliary_states()
        self._output_names = symbol.list_outputs()

        self._arg_params = None
        self._aux_params = None
        self._params_dirty = False
        self._compression_params = compression_params
        # optimizer/kvstore wiring happens in init_optimizer; executor
        # state in bind
        for attr in ("_optimizer", "_kvstore", "_update_on_kvstore",
                     "_updater", "_preload_opt_states", "_grad_req",
                     "_exec_group", "_data_shapes", "_label_shapes"):
            setattr(self, attr, None)

    @staticmethod
    def load(prefix, epoch, load_optimizer_states=False, **kwargs):
        """Load from checkpoint (reference: module.py:126)."""
        sym, args, auxs = load_checkpoint(prefix, epoch)
        mod = Module(symbol=sym, **kwargs)
        mod._arg_params, mod._aux_params = args, auxs
        mod.params_initialized = True
        if load_optimizer_states:
            mod._preload_opt_states = "%s-%04d.states" % (prefix, epoch)
        return mod

    def save_checkpoint(self, prefix, epoch, save_optimizer_states=False,
                        manager=None):
        """Save checkpoint (reference: module.py:161).

        The legacy prefix files are always written (now crash-safe:
        every file commits via write-to-temp + ``os.replace``).  When a
        ``checkpoint.CheckpointManager`` is passed — or
        ``MXNET_CKPT_DIR`` selects the process-default one — the save
        is ALSO routed through the manager: one atomic, sharded,
        integrity-checked checkpoint carrying full resume state, which
        the serving watcher can hot-swap.  Pass ``manager=False`` to
        suppress the routing (a caller that already saved through its
        own manager)."""
        self._symbol.save("%s-symbol.json" % prefix)
        param_name = "%s-%04d.params" % (prefix, epoch)
        self.save_params(param_name)
        logging.info("Saved checkpoint to \"%s\"", param_name)
        if save_optimizer_states:
            state_name = "%s-%04d.states" % (prefix, epoch)
            self.save_optimizer_states(state_name)
            logging.info("Saved optimizer state to \"%s\"", state_name)
        if manager is None:
            from .. import config as _config
            if _config.get("MXNET_CKPT_DIR"):
                from .. import checkpoint as _checkpoint
                manager = _checkpoint.default_manager()
        if manager:   # False suppresses, None means "not configured"
            manager.save_module(self, epoch=epoch)

    def _reset_bind(self):
        self.binded = False
        self._exec_group = None
        self._data_shapes = None
        self._label_shapes = None

    @property
    def data_names(self):
        return self._data_names

    @property
    def label_names(self):
        return self._label_names

    @property
    def output_names(self):
        return self._output_names

    @property
    def data_shapes(self):
        assert self.binded
        return self._data_shapes

    @property
    def label_shapes(self):
        assert self.binded
        return self._label_shapes

    @property
    def output_shapes(self):
        assert self.binded
        known = {d.name: d.shape for d in self._data_shapes}
        if self._label_shapes:
            known.update({l.name: l.shape for l in self._label_shapes})
        _, out_shapes, _ = self._symbol.infer_shape(**known)
        return list(zip(self._output_names, out_shapes))

    def get_params(self):
        """Reference: module.py get_params."""
        assert self.binded and self.params_initialized
        if self._params_dirty:
            self._sync_params_from_devices()
        return (self._arg_params, self._aux_params)

    def init_params(self, initializer=Uniform(0.01), arg_params=None,
                    aux_params=None, allow_missing=False, force_init=False,
                    allow_extra=False):
        """Reference: module.py:259."""
        if self.params_initialized and not force_init:
            warnings.warn("Parameters already initialized and force_init=False. "
                          "init_params call ignored.", stacklevel=2)
            return
        assert self.binded, "call bind before initializing the parameters"
        with _tracing.span("module.init_params"):
            self._init_params(initializer, arg_params, aux_params,
                              allow_missing, allow_extra)

    def _init_params(self, initializer, arg_params, aux_params,
                     allow_missing, allow_extra):
        attrs = self._symbol.attr_dict()
        for own, given in ((self._arg_params, arg_params),
                           (self._aux_params, aux_params)):
            for name, arr in sorted(own.items()):
                desc = InitDesc(name, attrs.get(name, None))
                src = None if given is None else given.get(name)
                if src is not None:
                    if src is not arr:
                        src.copyto(arr)
                    continue
                if given is not None:
                    if not allow_missing:
                        raise RuntimeError("%s is not presented" % name)
                    if initializer is not None:
                        initializer(desc, arr)
                    continue
                if initializer is None:
                    # no source dict and nothing to initialize with —
                    # failing loudly beats silently keeping bind-time
                    # garbage in a module marked initialized
                    raise RuntimeError(
                        "no initializer given and %s has no source value"
                        % name)
                initializer(desc, arr)

        self.params_initialized = True
        self._params_dirty = False
        self._exec_group.set_params(self._arg_params, self._aux_params,
                                    allow_extra=allow_extra)

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True, allow_extra=False):
        """Directly assign params (reference: module.py set_params)."""
        with _tracing.span("module.set_params"):
            self._set_params(arg_params, aux_params, allow_missing,
                             force_init, allow_extra)

    def _set_params(self, arg_params, aux_params, allow_missing, force_init,
                    allow_extra):
        if not allow_missing:
            self.init_params(initializer=None, arg_params=arg_params,
                             aux_params=aux_params, allow_missing=allow_missing,
                             force_init=force_init, allow_extra=allow_extra)
            return
        if self.params_initialized and not force_init:
            warnings.warn("Parameters already initialized and force_init=False. "
                          "set_params call ignored.", stacklevel=3)
            return
        self._exec_group.set_params(arg_params, aux_params,
                                    allow_extra=allow_extra)
        self._params_dirty = True
        self.params_initialized = True

    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        """Bind executors (reference: module.py:364)."""
        if force_rebind:
            self._reset_bind()
        if self.binded:
            self.logger.warning("Already binded, ignoring bind()")
            return

        with _tracing.span("module.bind"):
            self._bind(data_shapes, label_shapes, for_training,
                       inputs_need_grad, shared_module, grad_req)

    def _bind(self, data_shapes, label_shapes, for_training,
              inputs_need_grad, shared_module, grad_req):
        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self._grad_req = grad_req

        if not for_training:
            assert not inputs_need_grad

        self._data_shapes, self._label_shapes = _parse_data_desc(
            self.data_names, self.label_names, data_shapes, label_shapes)

        shared_group = None
        if shared_module is not None:
            if not (isinstance(shared_module, Module) and shared_module.binded
                    and shared_module.params_initialized):
                raise AssertionError(
                    "shared_module must be a bound, initialized Module")
            shared_group = shared_module._exec_group
            if len(shared_group.execs) < len(self._context):
                raise AssertionError(
                    "shared_module was bound on fewer devices than this "
                    "module needs")

        self._exec_group = DataParallelExecutorGroup(
            self._symbol, self._context, self._work_load_list,
            self._data_shapes, self._label_shapes, self._param_names,
            for_training, inputs_need_grad, shared_group, logger=self.logger,
            fixed_param_names=self._fixed_param_names, grad_req=grad_req,
            state_names=self._state_names,
            compute_dtype=self._compute_dtype,
            cast_exclude=tuple(self._label_names))
        self.binded = True

        if shared_module is not None and shared_module.params_initialized:
            self._arg_params = shared_module._arg_params
            self._aux_params = shared_module._aux_params
            self.params_initialized = True
        elif self._arg_params is None:
            # fresh master param buffers (reference keeps per-device
            # arrays; we keep one master + per-exec copies), all copied
            # by ONE jitted program
            from ..ndarray.ndarray import _copy_buffers, _wrap as _nd_wrap

            def _copy_all(names, arrays_per_name):
                copies = _copy_buffers(
                    tuple(arrs[0]._data for arrs in arrays_per_name))
                return {n: _nd_wrap(c) for n, c in zip(names, copies)}

            self._arg_params = _copy_all(self._param_names,
                                         self._exec_group.param_arrays)
            self._aux_params = _copy_all(self._aux_names,
                                         self._exec_group.aux_arrays)
        if self.params_initialized:
            self._exec_group.set_params(self._arg_params, self._aux_params)

    def reshape(self, data_shapes, label_shapes=None):
        """Reshape for new batch shapes (reference: module.py reshape)."""
        assert self.binded
        self._data_shapes, self._label_shapes = _parse_data_desc(
            self.data_names, self.label_names, data_shapes, label_shapes)
        self._exec_group.reshape(self._data_shapes, self._label_shapes)

    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        """Reference: module.py:473."""
        assert self.binded and self.params_initialized
        if self.optimizer_initialized and not force_init:
            self.logger.warning("optimizer already initialized, ignoring...")
            return
        with _tracing.span("module.init_optimizer"):
            self._init_optimizer(kvstore, optimizer, optimizer_params)

    def _init_optimizer(self, kvstore, optimizer, optimizer_params):
        if self._params_dirty:
            self._sync_params_from_devices()

        (kvstore, update_on_kvstore) = _create_kvstore(
            kvstore, len(self._context), self._arg_params)
        batch_size = self._exec_group.batch_size
        if kvstore and "dist" in kvstore.type and "_async" in kvstore.type:
            batch_size *= kvstore.num_workers
        rescale_grad = 1.0 / batch_size

        # optimizer index -> param name; update-on-worker keeps one slot
        # per (param, device) pair, matching the updater call pattern
        names = self._exec_group.param_names
        ndev = 1 if update_on_kvstore else len(self._context)
        idx2name = {i * ndev + k: n
                    for i, n in enumerate(names) for k in range(ndev)}
        if isinstance(optimizer, str):
            optimizer_params = dict(optimizer_params)
            if "rescale_grad" not in optimizer_params:
                optimizer_params["rescale_grad"] = rescale_grad
            optimizer = opt.create(optimizer, sym=self.symbol,
                                   param_idx2name=idx2name, **optimizer_params)
        else:
            assert isinstance(optimizer, opt.Optimizer)
            if optimizer.rescale_grad != rescale_grad:
                warnings.warn(
                    "Optimizer created manually outside Module but rescale_grad "
                    "is not normalized to 1.0/batch_size/num_workers (%s vs. %s). "
                    "Is this intended?" % (optimizer.rescale_grad, rescale_grad),
                    stacklevel=3)
            if not optimizer.idx2name:
                # faithful reference quirk (module.py:528): the map is
                # assigned without refreshing lr/wd mults, so a manually
                # constructed optimizer keeps full weight decay on
                # biases/gammas unless the caller invokes set_wd_mult
                # after init_optimizer
                optimizer.idx2name = idx2name.copy()

        self._optimizer, self._kvstore = optimizer, kvstore
        self._update_on_kvstore, self._updater = update_on_kvstore, None

        if kvstore:
            if self._compression_params:
                kvstore.set_gradient_compression(self._compression_params)
            _initialize_kvstore(kvstore=kvstore,
                                param_arrays=self._exec_group.param_arrays,
                                arg_params=self._arg_params,
                                param_names=self._param_names,
                                update_on_kvstore=update_on_kvstore)
        if update_on_kvstore:
            kvstore.set_optimizer(self._optimizer)
        else:
            self._updater = opt.get_updater(optimizer)

        # kvstore=tpu on a single context: fold the optimizer into the
        # executor's compiled step (fwd+bwd+update = one donated XLA
        # program — the TPU-native form of update-on-kvstore; the
        # reference's server-side update, kvstore_dist_server.h:282,
        # becomes part of the step program)
        # executor fusion donates the weight buffers, so it requires this
        # executor to be their EXCLUSIVE owner — BucketingModule shares
        # weights across per-bucket executors and borrowed optimizers go
        # through the kvstore, which would then read donated (deleted)
        # buffers; bucketing therefore forces the kvstore fused store
        # (one optimizer state for all buckets) instead
        self._fused_exec_update = False
        if (kvstore is not None and kvstore.type == "tpu"
                and update_on_kvstore and len(self._exec_group.execs) == 1
                and getattr(self, "_allow_exec_fusion", True)):
            # compression follows the module wherever its update runs
            # (reference C-API contract): the kvstore's
            # set_gradient_compression params ride into the compiled
            # step so the codec is applied there too, not only on the
            # eager push path
            self._fused_exec_update = \
                self._exec_group.execs[0].install_fused_update(
                    self._optimizer,
                    param_names=self._exec_group.param_names,
                    compression_params=kvstore._compression_params)

        self.optimizer_initialized = True

        if self._preload_opt_states is not None:
            self.load_optimizer_states(self._preload_opt_states)
            self._preload_opt_states = None

    def borrow_optimizer(self, shared_module):
        """Reference: module.py borrow_optimizer (BucketingModule)."""
        assert shared_module.optimizer_initialized
        for attr in ("_optimizer", "_kvstore", "_update_on_kvstore",
                     "_updater"):
            setattr(self, attr, getattr(shared_module, attr))
        self.optimizer_initialized = True

    def forward(self, data_batch, is_train=None):
        """Reference: module.py forward."""
        assert self.binded and self.params_initialized
        curr_data_shapes = tuple(i.shape for i in self._data_shapes)
        if isinstance(data_batch, list):
            assert data_batch, "Encountered empty data batch"
            new_data_shapes = tuple(i.shape for i in data_batch[0].data)
        else:
            new_data_shapes = tuple(i.shape for i in data_batch.data)
        if curr_data_shapes != new_data_shapes:
            # batch shape changed (bucketing / last partial batch):
            # re-derive descs, preferring the batch's own provide_* info
            new_dshape = getattr(data_batch, "provide_data", None) or \
                [(d.name, shape) for d, shape in
                 zip(self._data_shapes, new_data_shapes)]
            new_lshape = getattr(data_batch, "provide_label", None)
            if not new_lshape and getattr(data_batch, "label", None):
                new_lshape = [(d.name, lab.shape) for d, lab in
                              zip(self._label_shapes, data_batch.label)]
            self.reshape(new_dshape, new_lshape or None)
        self._exec_group.forward(data_batch, is_train)

    def backward(self, out_grads=None):
        """Reference: module.py backward."""
        assert self.binded and self.params_initialized
        self._exec_group.backward(out_grads=out_grads)

    def update(self):
        """Apply optimizer to gradients (reference: module.py:631)."""
        assert self.binded and self.params_initialized \
            and self.optimizer_initialized
        self._params_dirty = True
        if getattr(self, "_fused_exec_update", False) and \
                self._exec_group.execs[0].updates_applied:
            # weights already advanced inside the compiled train step
            return
        with _tracing.span("module.update"):
            if self._update_on_kvstore:
                _update_params_on_kvstore(self._exec_group.param_arrays,
                                          self._exec_group.grad_arrays,
                                          self._kvstore,
                                          self._exec_group.param_names)
            else:
                _update_params(self._exec_group.param_arrays,
                               self._exec_group.grad_arrays,
                               updater=self._updater,
                               num_device=len(self._context),
                               kvstore=self._kvstore,
                               param_names=self._exec_group.param_names)

    def get_outputs(self, merge_multi_context=True):
        assert self.binded and self.params_initialized
        return self._exec_group.get_outputs(
            merge_multi_context=merge_multi_context)

    def get_input_grads(self, merge_multi_context=True):
        assert self.binded and self.params_initialized \
            and self.inputs_need_grad
        return self._exec_group.get_input_grads(
            merge_multi_context=merge_multi_context)

    def update_metric(self, eval_metric, labels, pre_sliced=False):
        with _tracing.span("module.update_metric"):
            self._exec_group.update_metric(eval_metric, labels, pre_sliced)

    def _sync_params_from_devices(self):
        """Reference: module.py _sync_params_from_devices."""
        self._exec_group.get_params(self._arg_params, self._aux_params)
        if self._updater is not None:
            pass  # updater states live on host already
        self._params_dirty = False

    def save_optimizer_states(self, fname):
        """Reference: module.py save_optimizer_states (write is atomic:
        temp + ``os.replace``, so a crash cannot truncate an existing
        state file in place)."""
        assert self.optimizer_initialized
        if self._update_on_kvstore:
            self._kvstore.save_optimizer_states(fname)
        else:
            from .._atomic_io import atomic_write
            atomic_write(fname, self._updater.get_states())

    def load_optimizer_states(self, fname):
        """Reference: module.py load_optimizer_states."""
        assert self.optimizer_initialized
        if self._update_on_kvstore:
            self._kvstore.load_optimizer_states(fname)
        else:
            self._updater.set_states(open(fname, "rb").read())

    def install_monitor(self, mon):
        assert self.binded
        self._exec_group.install_monitor(mon)


def _parse_data_desc(data_names, label_names, data_shapes, label_shapes):
    """Normalize shapes to DataDesc lists (reference: module base)."""
    from ..io import DataDesc

    def _desc(x):
        if isinstance(x, DataDesc):
            return x
        return DataDesc(x[0], tuple(x[1]), *(x[2:] if len(x) > 2 else ()))

    data_shapes = [_desc(x) for x in data_shapes]
    if label_shapes is not None and len(label_shapes):
        label_shapes = [_desc(x) for x in label_shapes]
    else:
        label_shapes = None
    return data_shapes, label_shapes
