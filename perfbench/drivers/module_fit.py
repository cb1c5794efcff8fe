"""Driver: the ``Module.fit`` loop on the module ``train_imagenet.main``
builds with ``--kv-store tpu`` — fwd + bwd + optimizer in one donated
executor program (``executor.py`` ``_build_fbu``).

``train_imagenet.main(argv)`` with ``--num-epochs 0`` binds the module,
initialises it and installs the fused update without running a step;
the benchmark then sets its own weights (``set_params``) and makes the
calls the fit loop makes for each batch: ``forward_backward``,
``update``, ``update_metric``.  Nothing of the program is edited.
"""
import os
import sys

import numpy as np


class Driver:
    def __init__(self, config, devices, rehearse=False):
        self.config, self.devices, self.rehearse = config, devices, rehearse
        self.mod = self.metric = self._last = None

    # -- build ---------------------------------------------------------------
    def build(self, weights):
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        sys.path.insert(0, os.path.join(root, "example",
                                        "image-classification"))
        import mxnet_tpu as mx
        import train_imagenet
        cfg, opt = self.config, self.config["optimizer"]
        argv = ["--benchmark", "1", "--kv-store", "tpu",
                "--network", cfg["network"],
                "--num-layers", str(cfg["num_layers"]),
                "--num-classes", str(cfg["num_classes"]),
                "--image-shape", ",".join(map(str, cfg["image_shape"])),
                "--batch-size", str(cfg["batch_size"]),
                "--dtype", cfg["compute_dtype"],
                "--lr", str(opt["learning_rate"]), "--mom",
                str(opt["momentum"]), "--wd", str(opt["wd"]),
                "--lr-factor", "1",     # a constant rate: no schedule
                "--num-epochs", "0", "--disp-batches", "1000000"]
        self.mx = mx
        self.mod = train_imagenet.main(argv)
        self.ctx = self.mod._context[0]
        # the benchmark's weights, not the program's initialiser's
        arg = {k: mx.nd.array(v) for k, v in weights.items()}
        _arg0, aux0 = self.mod.get_params()
        aux = {}
        for k, v in aux0.items():
            fill = np.ones if k.endswith("_moving_var") else np.zeros
            aux[k] = mx.nd.array(fill(v.shape, np.float32))
        missing = sorted(set(_arg0) ^ set(arg))
        if missing:
            raise RuntimeError("reference and program disagree on the "
                               "leaves: %s" % missing[:6])
        self.mod.set_params(arg, aux, force_init=True)
        self.metric = mx.metric.create("acc")

    def place(self, host):
        x, y = host
        mx = self.mx
        return mx.io.DataBatch(data=[mx.nd.array(x, ctx=self.ctx)],
                               label=[mx.nd.array(y, ctx=self.ctx)])

    # -- the timed calls -----------------------------------------------------
    def step(self, batch):
        b = batch.placed
        self.mod.forward_backward(b)
        self.mod.update()
        self.mod.update_metric(self.metric, b.label)
        self._last = batch
        return self.mod.get_outputs()[0]._data

    def block(self):
        self.mod.get_outputs()[0].wait_to_read()

    # -- read for `correct` (set-up and after the window only) ---------------
    def loss(self):
        """Mean cross-entropy of the last step, from the probabilities
        the step's own output holds."""
        p = self.mod.get_outputs()[0].asnumpy().astype(np.float64)
        y = self._last.host[1].astype(np.int64)
        return float(-np.log(np.maximum(p[np.arange(len(y)), y],
                                        1e-30)).mean())

    def leaves(self):
        arg, _aux = self.mod.get_params()
        return {k: v.asnumpy() for k, v in arg.items()}

    def slots(self, slot):
        raise NotImplementedError("SGD's first gradient is worked out "
                                  "from the weights after one step")

    def assert_fast_path(self):
        from mxnet_tpu import telemetry
        from mxnet_tpu.ops import pallas_kernels as pk
        exe = self.mod._exec_group.execs[0]
        if self.mod._fused_exec_update is not True:
            raise RuntimeError("the fused executor step is not installed")
        if self.rehearse:
            return
        if exe._sweep is None or exe._sweep["kind"] != "sgd":
            raise RuntimeError("the one-sweep optimizer plan is missing")
        if pk._interpret() is not False:
            raise RuntimeError("Pallas runs in interpret mode")
        calls = telemetry.counter("mxnet_pallas_kernel_calls_total")
        for kname in ("fused_sgd_momentum", "fused_softmax_fwd"):
            if calls.labels(kernel=kname).value < 1:
                raise RuntimeError("the step never instantiated Pallas "
                                   "kernel %s" % kname)

    def free(self):
        self.mod = self.metric = self._last = None
