"""DataParallelExecutorGroup — the data-parallel engine of the Module API.

Reference: ``python/mxnet/module/executor_group.py:129`` — splits each
batch across contexts (``_split_input_slice``, executor_manager.py:31),
binds one executor per device (bind_exec :330), scatters data
(_load_data :65), runs forward (:422) / backward (:554), exposes
per-device param/grad arrays, update_metric (:583).

TPU-native: per-context executors are per-device jit programs; the
idiomatic TPU data parallelism (one pjit program over a mesh) lives in
``mxnet_tpu.parallel`` — this class keeps the reference's multi-executor
architecture so Module/examples behave identically.
"""
from __future__ import annotations

import logging

import numpy as np

from ..base import MXNetError
from ..context import Context
from ..ndarray import NDArray, zeros as nd_zeros, array as nd_array
from ..io import DataDesc

__all__ = ["DataParallelExecutorGroup", "_split_input_slice"]


def _split_input_slice(batch_size, work_load_list):
    """Workload-weighted batch split (reference: executor_manager.py:31)."""
    total_work_load = sum(work_load_list)
    batch_num_list = [round(work_load * batch_size / total_work_load)
                      for work_load in work_load_list]
    batch_num_sum = sum(batch_num_list)
    if batch_num_sum < batch_size:
        batch_num_list[-1] += batch_size - batch_num_sum
    slices = []
    end = 0
    for batch_num in batch_num_list:
        begin = int(min(end, batch_size))
        end = int(min(begin + batch_num, batch_size))
        if begin >= end:
            raise ValueError("Too many slices. Some splits are empty.")
        slices.append(slice(begin, end))
    return slices


def _load_general(data, targets):
    """Scatter batch slices into per-device arrays (reference:
    executor_group.py _load_general/executor_manager.py:65)."""
    for d_src, d_targets in zip(data, targets):
        if isinstance(d_targets, NDArray):
            d_src.copyto(d_targets)
        else:
            for slice_idx, d_dst in d_targets:
                part = d_src[slice_idx]
                if part.shape != d_dst.shape:
                    raise MXNetError("shape mismatch when scattering batch")
                d_dst._data = part._data.astype(d_dst.dtype)


class DataParallelExecutorGroup:
    """Per-device executor group (reference: executor_group.py:129)."""

    def __init__(self, symbol, contexts, workload, data_shapes, label_shapes,
                 param_names, for_training, inputs_need_grad,
                 shared_group=None, logger=logging, fixed_param_names=None,
                 grad_req="write", state_names=None, compute_dtype=None,
                 cast_exclude=()):
        self.compute_dtype = compute_dtype
        self.cast_exclude = tuple(cast_exclude)
        self.param_names = param_names
        self.arg_names = symbol.list_arguments()
        self.aux_names = symbol.list_auxiliary_states()
        self.symbol = symbol
        self.contexts = [Context(c) for c in contexts]
        self.workload = workload if workload else [1] * len(self.contexts)
        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self.fixed_param_names = fixed_param_names or []
        self.state_names = state_names or []
        self.logger = logger
        self._total_exec_bytes = 0

        data_names = [x[0] for x in data_shapes]
        if isinstance(grad_req, str):
            self.grad_req = {}
            for k in self.arg_names:
                if k in self.param_names:
                    self.grad_req[k] = ("null" if k in self.fixed_param_names
                                        else grad_req)
                elif k in data_names:
                    self.grad_req[k] = grad_req if inputs_need_grad else "null"
                else:
                    self.grad_req[k] = "null"
        elif isinstance(grad_req, (list, tuple)):
            self.grad_req = dict(zip(self.arg_names, grad_req))
        elif isinstance(grad_req, dict):
            self.grad_req = {k: "null" for k in self.arg_names}
            self.grad_req.update(grad_req)
        else:
            raise ValueError("invalid grad_req")
        if not for_training:
            self.grad_req = {k: "null" for k in self.arg_names}

        self.execs = []
        self.shared_group = shared_group
        self.data_shapes = None
        self.label_shapes = None
        self.data_layouts = None
        self.label_layouts = None
        self.output_names = symbol.list_outputs()
        self.output_layouts = [0] * len(self.output_names)
        self.num_outputs = len(self.output_names)
        self.bind_exec(data_shapes, label_shapes, shared_group)

    def decide_slices(self, data_shapes):
        """Per-context batch slices (reference: executor_group.py:289)."""
        assert len(data_shapes) > 0
        major_axis = [DataDesc.get_batch_axis(getattr(x, "layout", "NCHW"))
                      for x in data_shapes]
        for (name, shape), axis in zip(data_shapes, major_axis):
            if axis == -1:
                continue
            batch_size = shape[axis]
            if self.batch_size is not None:
                assert batch_size == self.batch_size, (
                    "all data must have the same batch size: batch_size = %d,"
                    " but %s has shape %s" % (self.batch_size, name, shape))
            else:
                self.batch_size = batch_size
                self.slices = _split_input_slice(self.batch_size, self.workload)
        return major_axis

    def bind_exec(self, data_shapes, label_shapes, shared_group=None,
                  reshape=False):
        """Bind one executor per context (reference: executor_group.py:330)."""
        self.batch_size = None
        self.data_layouts = self.decide_slices(data_shapes)
        if label_shapes is not None:
            self.label_layouts = self.decide_slices(label_shapes)

        # build into a local list: during reshape shared_group is self and
        # the old executors must stay visible for param sharing
        new_execs = [self._bind_ith_exec(i, data_shapes, label_shapes,
                                         shared_group)
                     for i in range(len(self.contexts))]
        self.execs = new_execs
        self.data_shapes = data_shapes
        self.label_shapes = label_shapes
        self.data_names = [i.name if isinstance(i, DataDesc) else i[0]
                           for i in self.data_shapes]
        if label_shapes is not None:
            self.label_names = [i.name if isinstance(i, DataDesc) else i[0]
                                for i in self.label_shapes]
        self._collect_arrays()

    def reshape(self, data_shapes, label_shapes):
        """Rebind for new shapes, sharing params (reference: :398)."""
        if data_shapes == self.data_shapes and label_shapes == self.label_shapes:
            return
        self.bind_exec(data_shapes, label_shapes, reshape=True,
                       shared_group=self)

    def _sliced_shape(self, shapes, i, major_axis):
        sliced = []
        for (desc, axis) in zip(shapes, major_axis):
            name, shape = (desc.name, desc.shape) if isinstance(desc, DataDesc) \
                else (desc[0], desc[1])
            shape = list(shape)
            if axis >= 0:
                shape[axis] = self.slices[i].stop - self.slices[i].start
            sliced.append(DataDesc(name, tuple(shape),
                                   getattr(desc, "dtype", np.float32)))
        return sliced

    def _bind_ith_exec(self, i, data_shapes, label_shapes, shared_group):
        shared_exec = None if shared_group is None else shared_group.execs[i]
        context = self.contexts[i]
        data_shapes_i = self._sliced_shape(data_shapes, i, self.data_layouts)
        if label_shapes is not None:
            label_shapes_i = self._sliced_shape(label_shapes, i,
                                                self.label_layouts)
        else:
            label_shapes_i = []
        input_shapes = {d.name: d.shape for d in data_shapes_i}
        input_shapes.update({l.name: l.shape for l in label_shapes_i})
        type_dict = {d.name: d.dtype for d in data_shapes_i}
        type_dict.update({l.name: l.dtype for l in label_shapes_i})
        return self.symbol.simple_bind(
            ctx=context, grad_req=self.grad_req, type_dict=type_dict,
            shared_exec=shared_exec, compute_dtype=self.compute_dtype,
            cast_exclude=self.cast_exclude, **input_shapes)

    def _collect_arrays(self):
        """Expose param/grad/data arrays per device (reference: :310)."""
        self.data_arrays = [
            [(self.slices[i], e.arg_dict[name]) for i, e in enumerate(self.execs)]
            for name in self.data_names]
        if self.label_shapes is not None:
            self.label_arrays = [
                [(self.slices[i], e.arg_dict[name])
                 for i, e in enumerate(self.execs)]
                for name in self.label_names]
        else:
            self.label_arrays = None
        self.param_arrays = [
            [e.arg_dict[name] for e in self.execs]
            for name in self.param_names]
        if self.for_training:
            self.grad_arrays = [
                [e.grad_dict.get(name) for e in self.execs]
                for name in self.param_names]
        else:
            self.grad_arrays = None
        data_names = [x[0] for x in self.data_shapes]
        if self.inputs_need_grad:
            self.input_grad_arrays = [
                [e.grad_dict.get(name) for e in self.execs]
                for name in data_names]
        else:
            self.input_grad_arrays = None
        self.aux_arrays = [
            [e.aux_dict[name] for e in self.execs]
            for name in self.aux_names]

    def set_params(self, arg_params, aux_params, allow_extra=False):
        """Copy params into every executor (reference: :441)."""
        for exec_ in self.execs:
            exec_.copy_params_from(arg_params, aux_params,
                                   allow_extra_params=allow_extra)

    def get_params(self, arg_params, aux_params):
        """Average params back from devices (reference: :453)."""
        owned = {}
        if len(self.execs) == 1 and self.execs[0].donates_weights:
            # the fused step deletes these buffers on its next
            # dispatch; what leaves the executor must be a copy
            from ..ndarray.ndarray import _copy_buffers, _wrap
            owned = dict(zip(self.param_names, map(_wrap, _copy_buffers(
                tuple(block[0]._data for block in self.param_arrays)))))
        for name, block in zip(self.param_names, self.param_arrays):
            weight = owned.get(name, block[0])
            if len(block) > 1:
                weight = block[0].copy()
                for w in block[1:]:
                    weight += w.as_in_context(weight.context)
                weight /= len(block)
            arg_params[name] = weight.astype(arg_params[name].dtype) \
                if name in arg_params else weight
        for name, block in zip(self.aux_names, self.aux_arrays):
            # a snapshot, not the executor's own NDArray: that one is
            # rebound to the new moving stats by every training step
            weight = block[0].detach()
            if len(block) > 1:
                weight = block[0].copy()
                for w in block[1:]:
                    weight += w.as_in_context(weight.context)
                weight /= len(block)
            aux_params[name] = weight

    def forward(self, data_batch, is_train=None):
        """Scatter + forward all executors (reference: :422)."""
        _load_general(data_batch.data, self.data_arrays)
        if is_train is None:
            is_train = self.for_training
        if self.label_arrays is not None and data_batch.label:
            _load_general(data_batch.label, self.label_arrays)
        for exec_ in self.execs:
            exec_.forward(is_train=is_train)

    def backward(self, out_grads=None):
        """Backward all executors (reference: :554)."""
        assert self.for_training, "re-bind with for_training=True to run backward"
        if out_grads is None:
            for exec_ in self.execs:
                exec_.backward()
        else:
            if not isinstance(out_grads, (list, tuple)):
                out_grads = [out_grads]
            for i, exec_ in enumerate(self.execs):
                out_grads_slice = [grad[self.slices[i]] for grad in out_grads]
                exec_.backward(out_grads_slice)

    def get_outputs(self, merge_multi_context=True):
        """Gather outputs (reference: :475)."""
        outputs = [[exec_.outputs[i] for exec_ in self.execs]
                   for i in range(self.num_outputs)]
        if merge_multi_context:
            return _merge_multi_context(outputs, self.output_layouts)
        return outputs

    def get_input_grads(self, merge_multi_context=True):
        assert self.inputs_need_grad
        if merge_multi_context:
            return _merge_multi_context(self.input_grad_arrays,
                                        self.data_layouts)
        return self.input_grad_arrays

    def get_states(self, merge_multi_context=True):
        assert not merge_multi_context, \
            "merge_multi_context=True is not supported for get_states yet."
        return [[] for _ in self.execs]

    def set_states(self, states=None, value=None):
        raise NotImplementedError("stateful modules not supported by executor group")

    def update_metric(self, eval_metric, labels, pre_sliced=False):
        """Per-device metric update (reference: :583)."""
        for current_exec, (texec, islice) in enumerate(
                zip(self.execs, self.slices)):
            if not pre_sliced:
                labels_slice = [label[islice] for label in labels]
            else:
                labels_slice = labels[current_exec]
            labels_ = dict(zip(self.label_names, labels_slice)) \
                if self.label_shapes is not None else {}
            preds = dict(zip(self.output_names, texec.outputs))
            eval_metric.update_dict(labels_, preds)

    def install_monitor(self, mon):
        for exe in self.execs:
            mon.install(exe)


def _merge_multi_context(outputs, major_axis):
    """Concatenate per-device outputs along the batch axis (reference:
    executor_group.py _merge_multi_context)."""
    from ..ndarray import concat
    rets = []
    for tensors, axis in zip(outputs, major_axis):
        if len(tensors) == 1:
            rets.append(tensors[0])
        elif axis >= 0:
            rets.append(concat(*tensors, dim=axis))
        else:
            rets.append(tensors[0])
    return rets
