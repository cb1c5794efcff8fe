"""BaseModule — the canonical training loop.

Reference: ``python/mxnet/module/base_module.py:81`` — the intermediate
and high-level Module APIs: forward_backward (:191), score (:208),
iter_predict (:266), predict (:310), **fit (:395)** (the canonical loop:
forward_backward / update / update_metric / checkpoint / epoch
callbacks), plus the abstract param/optimizer/bind interface.
"""
from __future__ import annotations

import logging
import time
import warnings

import numpy as np

from .. import metric
from .. import ndarray
from ..context import cpu
from ..model import BatchEndParam
from ..initializer import Uniform
from ..io import DataDesc
from ..base import MXNetError
from ..telemetry import tracing as _tracing

__all__ = ["BaseModule"]


_PARAM_SUFFIXES = ("_weight", "_bias", "_gamma", "_beta")


def _check_input_names(symbol, names, typename, throw):
    """Validate that declared data/label names exist in the graph
    (reference contract: base_module.py:34)."""
    args = set(symbol.list_arguments())
    missing = [n for n in names if n not in args]
    if not missing:
        return
    # suggest only non-parameter arguments — inputs are what the caller
    # plausibly meant
    inputs = [a for a in symbol.list_arguments()
              if not a.endswith(_PARAM_SUFFIXES)]
    msg = ("%s_names=%r includes %r, which is not an argument of the "
           "symbol. Graph inputs are: %s"
           % (typename, list(names), missing[0], ", ".join(inputs)))
    if throw:
        raise ValueError(msg)
    warnings.warn(msg)


def _as_list(obj):
    if isinstance(obj, (list, tuple)):
        return obj
    return [obj]


class BaseModule:
    """Base class for modules (reference: base_module.py:81)."""

    def __init__(self, logger=logging):
        self.logger = logger
        self.binded = False
        self.for_training = False
        self.inputs_need_grad = False
        self.params_initialized = False
        self.optimizer_initialized = False
        self._symbol = None
        self._total_exec_bytes = 0

    # -- high level ----------------------------------------------------------
    def forward_backward(self, data_batch):
        """Reference: base_module.py:191."""
        with _tracing.span("module.forward_backward"):
            self.forward(data_batch, is_train=True)
            self.backward()

    def score(self, eval_data, eval_metric, num_batch=None,
              batch_end_callback=None, score_end_callback=None, reset=True,
              epoch=0, sparse_row_id_fn=None):
        """Evaluate on eval_data (reference: base_module.py:208)."""
        assert self.binded and self.params_initialized
        eval_metric = (eval_metric
                       if isinstance(eval_metric, metric.EvalMetric)
                       else metric.create(eval_metric))
        eval_metric.reset()
        if reset:
            eval_data.reset()
        actual_num_batch = 0
        for nbatch, eval_batch in enumerate(eval_data):
            if nbatch == num_batch:  # None never equals an int: no limit
                break
            self.forward(eval_batch, is_train=False)
            if isinstance(eval_batch, list):
                self.update_metric(eval_metric,
                                   [eb.label for eb in eval_batch],
                                   pre_sliced=True)
            else:
                self.update_metric(eval_metric, eval_batch.label)
            if batch_end_callback is not None:
                batch_end_params = BatchEndParam(epoch=epoch, nbatch=nbatch,
                                                 eval_metric=eval_metric,
                                                 locals=locals())
                for callback in _as_list(batch_end_callback):
                    callback(batch_end_params)
            actual_num_batch += 1
        if score_end_callback:
            params = BatchEndParam(epoch=epoch, nbatch=actual_num_batch,
                                   eval_metric=eval_metric, locals=locals())
            for callback in _as_list(score_end_callback):
                callback(params)
        return eval_metric.get_name_value()

    def iter_predict(self, eval_data, num_batch=None, reset=True):
        """Reference: base_module.py:266."""
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            pad = eval_batch.pad
            outputs = [out[0:out.shape[0] - pad] for out in self.get_outputs()]
            yield (outputs, nbatch, eval_batch)

    def predict(self, eval_data, num_batch=None, merge_batches=True,
                reset=True, always_output_list=False, sparse_row_id_fn=None):
        """Reference: base_module.py:310."""
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        output_list = []
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            pad = eval_batch.pad
            outputs = [out[0:out.shape[0] - pad].copy()
                       for out in self.get_outputs()]
            output_list.append(outputs)
        if len(output_list) == 0:
            return output_list
        if merge_batches:
            num_outputs = len(output_list[0])
            for out in output_list:
                assert len(out) == num_outputs, \
                    "Cannot merge batches, as num of outputs is not the same " \
                    "in mini-batches. Maybe bucketing is used?"
            output_list2 = [ndarray.concatenate([out[i] for out in output_list])
                            for i in range(num_outputs)]
            if num_outputs == 1 and not always_output_list:
                return output_list2[0]
            return output_list2
        return output_list

    def fit(self, train_data, eval_data=None, eval_metric="acc",
            epoch_end_callback=None, batch_end_callback=None, kvstore="local",
            optimizer="sgd", optimizer_params=(("learning_rate", 0.01),),
            eval_end_callback=None, eval_batch_end_callback=None,
            initializer=Uniform(0.01), arg_params=None, aux_params=None,
            allow_missing=False, force_rebind=False, force_init=False,
            begin_epoch=0, num_epoch=None, validation_metric=None,
            monitor=None, sparse_row_id_fn=None, checkpoint_manager=None,
            elastic=False):
        """The canonical train loop (reference: base_module.py:395).

        ``checkpoint_manager``: a ``checkpoint.CheckpointManager`` for
        preemption-safe periodic saves — every ``period_steps`` batches
        and/or every ``period_epochs`` epochs, plus one final
        synchronous save on SIGTERM.  When None and ``MXNET_CKPT_DIR``
        is set, the process-default manager is used (the pure-env-knob
        path: no code change to checkpoint a job).

        ``elastic=True`` runs the loop under the graftfault
        :class:`~mxnet_tpu.fault.ElasticSupervisor`: recoverable
        failures (infrastructure errors, injected faults, the SIGTERM
        exit-143 preemption path) restore the newest checkpoint —
        params, optimizer, RNG, iterator cursor — and re-enter with
        exponential backoff, up to ``MXNET_FAULT_RETRIES`` times; a
        checkpoint manager is then required
        (docs/faq/fault_tolerance.md)."""
        assert num_epoch is not None, "please specify number of epochs"
        if elastic:
            from ..fault.elastic import elastic_fit
            return elastic_fit(
                self, train_data, checkpoint_manager=checkpoint_manager,
                eval_data=eval_data, eval_metric=eval_metric,
                epoch_end_callback=epoch_end_callback,
                batch_end_callback=batch_end_callback, kvstore=kvstore,
                optimizer=optimizer, optimizer_params=optimizer_params,
                eval_end_callback=eval_end_callback,
                eval_batch_end_callback=eval_batch_end_callback,
                initializer=initializer, arg_params=arg_params,
                aux_params=aux_params, allow_missing=allow_missing,
                force_rebind=force_rebind, force_init=force_init,
                begin_epoch=begin_epoch, num_epoch=num_epoch,
                validation_metric=validation_metric, monitor=monitor,
                sparse_row_id_fn=sparse_row_id_fn)

        self.bind(data_shapes=train_data.provide_data,
                  label_shapes=train_data.provide_label,
                  for_training=True, force_rebind=force_rebind)
        if monitor is not None:
            self.install_monitor(monitor)
        self.init_params(initializer=initializer, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init)
        self.init_optimizer(kvstore=kvstore, optimizer=optimizer,
                            optimizer_params=optimizer_params)

        if validation_metric is None:
            validation_metric = eval_metric
        if not isinstance(eval_metric, metric.EvalMetric):
            eval_metric = metric.create(eval_metric)

        # telemetry: MXNET_TELEMETRY_STEP_LOG installs a per-step JSONL
        # emitter as an extra batch-end callback (samples/sec + counter
        # deltas; see telemetry.step_logger)
        from .. import config as _config
        batch_end_cbs = (list(_as_list(batch_end_callback))
                         if batch_end_callback is not None else [])
        step_logger = None
        step_log_path = _config.get("MXNET_TELEMETRY_STEP_LOG")
        if step_log_path:
            from .. import telemetry as _telemetry
            step_logger = _telemetry.StepLogger(
                step_log_path,
                batch_size=getattr(train_data, "batch_size", None),
                interval=_config.get("MXNET_TELEMETRY_STEP_INTERVAL"))
            batch_end_cbs.append(step_logger)

        # checkpointing: explicit manager wins; otherwise MXNET_CKPT_DIR
        # selects the process-default manager (checkpoint subsystem)
        ckpt_mgr = checkpoint_manager
        if ckpt_mgr is None and _config.get("MXNET_CKPT_DIR"):
            from .. import checkpoint as _checkpoint
            ckpt_mgr = _checkpoint.default_manager()

        # training loop.  The upcoming batch is fetched and prepare()d
        # only AFTER the current step has been dispatched — a
        # buffer-reusing iterator may invalidate the current batch on
        # its next() call, and a row-sparse prepare must see the updated
        # rows; under XLA's async dispatch this staging still overlaps
        # the in-flight device step.
        try:
            self._fit_epochs(train_data, eval_data, eval_metric,
                             validation_metric, batch_end_cbs,
                             epoch_end_callback, eval_end_callback,
                             eval_batch_end_callback, monitor,
                             sparse_row_id_fn, begin_epoch, num_epoch,
                             ckpt_mgr)
        finally:
            if getattr(self, "_san_fit_region", None) is not None:
                # an exception aborted the batch loop mid-epoch — the
                # graftsan region must not outlive the loop it proves
                self._san_fit_region.close()
                self._san_fit_region = None
            if step_logger is not None:
                step_logger.close()
            if ckpt_mgr is not None:
                # drain the last async save so a job that exits right
                # after fit() never loses its newest snapshot
                ckpt_mgr.wait()

    def _fit_epochs(self, train_data, eval_data, eval_metric,
                    validation_metric, batch_end_cbs, epoch_end_callback,
                    eval_end_callback, eval_batch_end_callback, monitor,
                    sparse_row_id_fn, begin_epoch, num_epoch, ckpt_mgr=None):
        import contextlib
        from .. import config as _config
        # preemption hook: SIGTERM only sets a flag (running the save
        # inside the handler could re-acquire locks the interrupted
        # thread holds); the batch loop polls the flag at safe points
        # and calls _preemption_save there
        progress = {"epoch": begin_epoch, "nbatch": 0}
        scope = contextlib.nullcontext(None)
        if ckpt_mgr is not None and _config.get("MXNET_CKPT_ON_SIGTERM"):
            from .. import checkpoint as _checkpoint
            scope = _checkpoint.sigterm_flag_scope()
        with scope as sigterm:
            self._fit_epochs_inner(
                train_data, eval_data, eval_metric, validation_metric,
                batch_end_cbs, epoch_end_callback, eval_end_callback,
                eval_batch_end_callback, monitor, sparse_row_id_fn,
                begin_epoch, num_epoch, ckpt_mgr, progress, sigterm)
            # a signal that landed after the last in-loop poll (e.g.
            # during final evaluation) still gets its grace-window save
            if sigterm is not None and sigterm["signaled"]:
                self._preemption_save(ckpt_mgr, progress, train_data)

    def _preemption_save(self, ckpt_mgr, progress, train_data):
        """One guaranteed synchronous save of the current loop position,
        then exit 143 (the preemption convention).  Runs on the training
        thread at a safe point — never inside the signal handler."""
        # the loop prefetches one batch ahead; when that batch is
        # fetched but not yet trained ("pending"), the iterator cursor
        # overstates progress by one batch — rewind it for the capture
        # so resume re-trains (never skips) that batch
        rewound = False
        if progress.get("pending") and \
                isinstance(getattr(train_data, "cursor", None), int) \
                and getattr(train_data, "batch_size", 0):
            train_data.cursor -= train_data.batch_size
            rewound = True
        try:
            # the grace-window save is a deliberate terminal sync —
            # save_module's own graftsan suspension covers it
            ckpt_mgr.save_module(self, epoch=progress["epoch"],
                                 nbatch=progress["nbatch"],
                                 train_data=train_data, block=True)
        except Exception:
            self.logger.exception("checkpoint: SIGTERM save failed")
        finally:
            if rewound:
                train_data.cursor += train_data.batch_size
        self.logger.info("SIGTERM: checkpoint saved; exiting 143")
        raise SystemExit(143)

    def _fit_epochs_inner(self, train_data, eval_data, eval_metric,
                          validation_metric, batch_end_cbs,
                          epoch_end_callback, eval_end_callback,
                          eval_batch_end_callback, monitor,
                          sparse_row_id_fn, begin_epoch, num_epoch,
                          ckpt_mgr=None, progress=None, sigterm=None):
        from ..analysis.sanitizers import hooks as _san_hooks
        from ..fault import hooks as _fault
        # graftfault step address: a monotone batch counter across
        # epochs, so plans can say "SIGTERM at global batch 7" and the
        # kill-and-resume drill is exact (published only while armed)
        global_batch = 0
        for epoch in range(begin_epoch, num_epoch):
            epoch_start = time.time()
            eval_metric.reset()
            epoch_metrics = []
            batches = iter(train_data)
            data_batch = next(batches, None)
            nbatch = 0
            if progress is not None:
                progress.update(epoch=epoch, nbatch=0,
                                pending=data_batch is not None)
            # graftsan: after the first step of each epoch's batch loop
            # the step program is compiled and every per-step sync must
            # be claimed — open a steady-state region over the rest of
            # the loop (closed before epoch-end work: params sync,
            # callbacks and eval legitimately sync once per epoch; the
            # handle lives on self so fit()'s finally also closes it
            # when an exception aborts the loop mid-epoch)
            while data_batch is not None:
                # fit.step is the parent of the three module.* spans
                with _tracing.span("fit.step", epoch=epoch,
                                   batch=global_batch):
                    if _fault.ACTIVE[0]:
                        _fault.set_step(global_batch)
                        _fault.fire("fit.step", epoch=epoch)
                    global_batch += 1
                    if monitor is not None:
                        monitor.tic()
                    self.forward_backward(data_batch)
                    self.update()
                    if getattr(self, "_san_fit_region", None) is None and \
                            _san_hooks.region_sanitizers_active():
                        from ..analysis import sanitizers as _sanitizers
                        self._san_fit_region = \
                            _sanitizers.steady_state("fit")
                    labels = ([db.label for db in data_batch]
                              if isinstance(data_batch, list) else
                              data_batch.label)
                    self.update_metric(
                        eval_metric, labels,
                        pre_sliced=isinstance(data_batch, list))
                if progress is not None:
                    # batch (epoch, nbatch) is fully applied and the
                    # iterator has advanced past exactly nbatch+1 batches
                    progress.update(epoch=epoch, nbatch=nbatch + 1,
                                    pending=False)
                if ckpt_mgr is not None and ckpt_mgr.period_steps > 0 \
                        and (nbatch + 1) % ckpt_mgr.period_steps == 0:
                    # save BEFORE the prefetch advances the iterator, so
                    # the captured cursor points at the just-trained
                    # batch and resume continues with the next one
                    # (capturing after next() would skip a batch).
                    # Capture stages to host; serialization overlaps the
                    # next steps on the async writer.  A refusal (one
                    # already in flight) is fine: next period retries.
                    # (graftsan suspension lives in save_module itself —
                    # every caller inherits it.)
                    ckpt_mgr.save_module(self, epoch=epoch,
                                         nbatch=nbatch + 1,
                                         train_data=train_data)
                with _tracing.span("fit.data_wait"):
                    upcoming = next(batches, None)
                    if upcoming is not None:
                        self.prepare(upcoming,
                                     sparse_row_id_fn=sparse_row_id_fn)
                if upcoming is not None and progress is not None:
                    # fetched but untrained: the SIGTERM save must
                    # rewind the cursor over this batch
                    progress["pending"] = True
                if monitor is not None:
                    monitor.toc_print()
                if upcoming is None:
                    # read the epoch totals BEFORE callbacks can reset
                    # the metric (Speedometer with auto_reset)
                    epoch_metrics = eval_metric.get_name_value()
                for callback in batch_end_cbs:
                    callback(BatchEndParam(epoch=epoch, nbatch=nbatch,
                                           eval_metric=eval_metric,
                                           locals=locals()))
                if sigterm is not None and sigterm["signaled"]:
                    # preemption: save at this safe point (outside every
                    # lock) and exit — _preemption_save raises SystemExit
                    self._preemption_save(ckpt_mgr, progress, train_data)
                nbatch += 1
                data_batch = upcoming

            if getattr(self, "_san_fit_region", None) is not None:
                self._san_fit_region.close()
                self._san_fit_region = None

            for name, val in epoch_metrics:
                self.logger.info("Epoch[%d] Train-%s=%f", epoch, name, val)
            self.logger.info("Epoch[%d] Time cost=%.3f", epoch,
                             time.time() - epoch_start)

            # sync aux params across devices
            arg_params, aux_params = self.get_params()
            self.set_params(arg_params, aux_params)

            if epoch_end_callback is not None:
                for callback in _as_list(epoch_end_callback):
                    callback(epoch, self.symbol, arg_params, aux_params)

            if ckpt_mgr is not None and ckpt_mgr.period_epochs > 0 \
                    and (epoch + 1) % ckpt_mgr.period_epochs == 0:
                # an epoch-boundary snapshot means "start of epoch+1":
                # no iterator position is captured (the iterator is
                # exhausted here and resets below), so resume begins the
                # next epoch cleanly.  The final epoch's save blocks —
                # the end-of-training state must not lose a skip race
                # against an in-flight periodic save.
                ckpt_mgr.save_module(self, epoch=epoch + 1, nbatch=0,
                                     block=(epoch + 1 == num_epoch))

            # ----------------------------------------
            # evaluation on validation set
            if eval_data is not None:
                # graftsan: evaluation's first forward binds (compiles)
                # a fresh eval program and scoring syncs per batch —
                # deliberate cold work, exempt like warmup plans
                with _san_hooks.suspended():
                    res = self.score(
                        eval_data, validation_metric,
                        score_end_callback=eval_end_callback,
                        batch_end_callback=eval_batch_end_callback,
                        epoch=epoch)
                for name, val in res:
                    self.logger.info("Epoch[%d] Validation-%s=%f", epoch,
                                     name, val)

            # end of 1 epoch, reset the data-iter for another epoch
            train_data.reset()
            if progress is not None:
                # epoch boundary: position is "start of epoch+1", no
                # prefetched batch outstanding
                progress.update(epoch=epoch + 1, nbatch=0, pending=False)
            if sigterm is not None and sigterm["signaled"]:
                # a SIGTERM that landed during epoch-end work (sync,
                # callbacks, eval) — save before starting another epoch
                self._preemption_save(ckpt_mgr, progress, train_data)

    # -- symbol/params interface (abstract) ----------------------------------
    @property
    def symbol(self):
        return self._symbol

    @property
    def data_names(self):  # pragma: no cover - abstract
        raise NotImplementedError()

    @property
    def output_names(self):  # pragma: no cover - abstract
        raise NotImplementedError()

    @property
    def data_shapes(self):  # pragma: no cover - abstract
        raise NotImplementedError()

    @property
    def label_shapes(self):  # pragma: no cover - abstract
        raise NotImplementedError()

    @property
    def output_shapes(self):  # pragma: no cover - abstract
        raise NotImplementedError()

    def get_params(self):  # pragma: no cover - abstract
        raise NotImplementedError()

    def init_params(self, initializer=Uniform(0.01), arg_params=None,
                    aux_params=None, allow_missing=False, force_init=False,
                    allow_extra=False):  # pragma: no cover - abstract
        raise NotImplementedError()

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True, allow_extra=False):
        """Reference: base_module.py set_params."""
        self.init_params(initializer=None, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init, allow_extra=allow_extra)

    def save_params(self, fname):
        """Reference: base_module.py save_params."""
        arg_params, aux_params = self.get_params()
        save_dict = {("arg:%s" % k): v for k, v in arg_params.items()}
        save_dict.update({("aux:%s" % k): v for k, v in aux_params.items()})
        ndarray.save(fname, save_dict)

    def export_serving(self, name, registry, version=None,
                       input_shapes=None):
        """Register this module's symbol + CURRENT params into a
        serving registry (``mxnet_tpu.serving``) without a checkpoint
        round-trip — the hot-swap path for continuously-trained models:
        ``fit()`` -> ``export_serving()`` -> ``set_default()``.

        ``registry`` accepts a ``ModelRegistry`` or a ``ModelServer``
        (its registry is used).  ``input_shapes`` defaults to the bound
        ``data_shapes``; returns the registered version number."""
        if hasattr(registry, "registry"):    # a ModelServer
            registry = registry.registry
        arg_params, aux_params = self.get_params()
        if input_shapes is None:
            input_shapes = {d[0]: tuple(d[1]) for d in self.data_shapes}
        return registry.add(name, self.symbol, arg_params, aux_params,
                            input_shapes, version=version)

    def load_params(self, fname):
        """Reference: base_module.py load_params."""
        save_dict = ndarray.load(fname)
        arg_params = {}
        aux_params = {}
        for k, value in save_dict.items():
            arg_type, name = k.split(":", 1)
            if arg_type == "arg":
                arg_params[name] = value
            elif arg_type == "aux":
                aux_params[name] = value
            else:
                raise ValueError("Invalid param file " + fname)
        self.set_params(arg_params, aux_params)

    def get_states(self, merge_multi_context=True):
        assert self.binded and self.params_initialized
        assert not merge_multi_context
        return []

    def set_states(self, states=None, value=None):
        assert self.binded and self.params_initialized
        assert not states and not value

    def install_monitor(self, mon):  # pragma: no cover - abstract
        raise NotImplementedError()

    def prepare(self, data_batch, sparse_row_id_fn=None):
        """Prepare for next batch (row-sparse pull hook; reference
        base_module.py prepare)."""

    # -- computation interface (abstract) ------------------------------------
    def forward(self, data_batch, is_train=None):  # pragma: no cover
        raise NotImplementedError()

    def backward(self, out_grads=None):  # pragma: no cover - abstract
        raise NotImplementedError()

    def get_outputs(self, merge_multi_context=True):  # pragma: no cover
        raise NotImplementedError()

    def get_input_grads(self, merge_multi_context=True):  # pragma: no cover
        raise NotImplementedError()

    def update(self):  # pragma: no cover - abstract
        raise NotImplementedError()

    def update_metric(self, eval_metric, labels,
                      pre_sliced=False):  # pragma: no cover - abstract
        raise NotImplementedError()

    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):  # pragma: no cover - abstract
        raise NotImplementedError()

    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):  # pragma: no cover - abstract
        raise NotImplementedError()
