"""Driver: ``ParallelTrainer.step`` over a mesh of the cell's chips —
loss, backward, gradient exchange (ZeRO) and the flat optimizer sweep
in ONE compiled program (``parallel/trainer.py`` ``_build``).

The configuration's ``model`` names a gluon block (``transformer_lm``:
``gluon.contrib.transformer.TransformerLM``; ``model_zoo``: a
``gluon.model_zoo.vision`` network) and ``trainer`` gives the mesh's
``zero`` stage and dtype.  A driver of another block under another
objective derives from :class:`Driver` and brings ``_block`` and
``_loss`` (``drivers/looped_lm.py``); ``rehearse_compile.py`` takes any
such driver.  Parameters materialise on the HOST (no eager
forward on the chip) and are then overwritten, in construction order,
with the benchmark's own weights; the trainer places them.
"""
import numpy as np


class Driver:
    def __init__(self, config, devices, rehearse=False):
        self.config, self.devices, self.rehearse = config, devices, rehearse
        self.trainer = self._out = self._names = None

    # -- build ---------------------------------------------------------------
    def _block(self, mx, weights):
        """The gluon block with every parameter materialised on the HOST
        and no forward on the chip.  The transformer's deferred shapes
        are filled in from the reference's leaf shapes (an eager probe
        forward would run the Pallas LayerNorm on a host array); the
        model-zoo CNN takes one 1-row probe forward on the host, as
        chip_smoke.py does."""
        cfg = self.config
        if cfg["model"] == "transformer_lm":
            from mxnet_tpu.gluon.contrib.transformer import TransformerLM
            net = TransformerLM(
                int(cfg["vocab_size"]), units=int(cfg["hidden_size"]),
                hidden_size=int(cfg["ffn_dim"]),
                num_layers=int(cfg["num_hidden_layers"]),
                num_heads=int(cfg["num_attention_heads"]),
                max_len=int(cfg["max_position_embeddings"]), dropout=0.0)
            for p, w in zip(net.collect_params().values(), weights.values()):
                p.shape = w.shape
            net.initialize(mx.init.Zero(), ctx=mx.cpu())
        elif cfg["model"] == "model_zoo":
            from mxnet_tpu.gluon.model_zoo import vision
            net = vision.get_model(cfg["network"],
                                   classes=int(cfg["num_classes"]))
            with mx.cpu():
                net.initialize(mx.init.Zero(), ctx=mx.cpu())
                net(mx.nd.ones((1,) + tuple(cfg["image_shape"]),
                               ctx=mx.cpu()))
        else:
            raise ValueError("unknown model %r" % cfg["model"])
        return net

    def _loss(self, net):
        """The objective the trainer takes beside the block; a driver of
        another model overrides this and :meth:`_block`, nothing else."""
        from mxnet_tpu import gluon
        return gluon.loss.SoftmaxCrossEntropyLoss()

    def build(self, weights):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        import mxnet_tpu as mx
        from mxnet_tpu.parallel import ParallelTrainer, make_mesh
        self.mx = mx
        cfg, tr = self.config, self.config["trainer"]
        net = self._block(mx, weights)
        params = net.collect_params()
        trainable = [(k, p) for k, p in params.items()
                     if p.grad_req != "null"]
        if len(trainable) != len(weights):
            raise RuntimeError("the block has %d trainable parameters, the "
                               "reference %d" % (len(trainable), len(weights)))
        self._names = {}
        for (pname, p), (rname, w) in zip(trainable, weights.items()):
            if tuple(p.shape) != tuple(w.shape):
                raise RuntimeError("parameter %s %s does not match the "
                                   "reference's %s %s"
                                   % (pname, p.shape, rname, w.shape))
            p.set_data(mx.nd.array(w, ctx=mx.cpu()))
            self._names[pname] = rname
        opt = dict(cfg["optimizer"])
        name = opt.pop("name")
        opt.pop("wd_exempt_suffixes", None)     # the reference's business
        mesh = make_mesh(dp=len(self.devices), devices=list(self.devices))
        self.trainer = ParallelTrainer(
            net, self._loss(net), name, opt, mesh=mesh,
            zero=int(tr["zero"]), dtype=tr["dtype"])
        self._batch_ns = NamedSharding(mesh, P(("dp", "fsdp")))
        self._jax = jax

    def place(self, host):
        x, y = host
        return (self._jax.device_put(x, self._batch_ns),
                self._jax.device_put(y, self._batch_ns))

    # -- the timed call -------------------------------------------------------
    def step(self, batch):
        nd = self.mx.nd
        x, y = batch.placed
        self._out = self.trainer.step(nd.NDArray(x), nd.NDArray(y))._data
        return self._out

    def block(self):
        self._out.block_until_ready()

    # -- read for `correct` (set-up only) ------------------------------------
    def loss(self):
        return float(np.asarray(self._out))

    def leaves(self):
        p = self.trainer.params
        return {r: np.asarray(p[n]) for n, r in self._names.items()}

    def slots(self, slot):
        """One optimizer slot per leaf, sliced out of the ZeRO buckets."""
        fused = self.trainer.opt_state["fused"][slot]
        out = {}
        for b in self.trainer.bucket_plan:
            flat = np.asarray(fused["b%d" % b.index])
            for name, shape, off, size in zip(b.names, b.shapes, b.offsets,
                                              b.sizes):
                out[self._names[name]] = flat[off:off + size].reshape(shape)
        per = self.trainer.opt_state["perparam"].get(slot, {})
        for name, arr in per.items():
            out[self._names[name]] = np.asarray(arr)
        return out

    def assert_fast_path(self):
        from mxnet_tpu import telemetry
        from mxnet_tpu.ops import pallas_kernels as pk
        if self.trainer.zero != int(self.config["trainer"]["zero"]):
            raise RuntimeError("the trainer runs zero=%d" % self.trainer.zero)
        if self.rehearse:
            return
        if pk._interpret() is not False:
            raise RuntimeError("Pallas runs in interpret mode")
        if len(self.devices) > 1 and pk._sweep_shard_verdict() is not True:
            raise RuntimeError("the sweep was not proved shard-safe: the "
                               "step falls back to tree_map")
        calls = telemetry.counter("mxnet_pallas_kernel_calls_total")
        for kname in self.config["trainer"]["kernels"]:
            if calls.labels(kernel=kname).value < 1:
                raise RuntimeError("the step never instantiated Pallas "
                                   "kernel %s" % kname)

    def free(self):
        self.trainer = self._out = None
