"""Driver: ``ParallelTrainer.step`` over a LOOPED language model —
``gluon.contrib.transformer.LoopedLM`` (one stack of layers applied
``total_ut_steps`` times with shared weights, an exit after every pass)
under its own objective, ``LoopedLM.exit_loss()`` (the exit-weighted
cross-entropy less ``exit_entropy_beta`` times the exit distribution's
entropy).

Everything but the block and the loss is ``drivers/parallel_trainer.py``
as it stands — the same trainer construction, placement, ``step``,
``leaves``, ``slots`` and ``assert_fast_path`` — taken from that file's
class by name; only :meth:`build` differs, because that file's builds a
plain cross-entropy and raises on a ``model`` it does not know.
"""
import os

import loader

_BASE = loader.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "parallel_trainer.py"))


class Driver(_BASE.Driver):
    def _block(self, mx, weights):
        """The block with every parameter materialised on the HOST (its
        shapes are all given at construction; no forward on the chip)."""
        from mxnet_tpu.gluon.contrib.transformer import LoopedLM
        cfg = self.config
        if cfg["model"] != "looped_lm":
            raise ValueError("unknown model %r" % cfg["model"])
        units, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
        if units != heads * int(cfg["head_dim"]):
            raise ValueError("hidden_size is not heads x head_dim")
        net = LoopedLM(
            int(cfg["vocab_size"]), units=units,
            hidden_size=int(cfg["intermediate_size"]),
            num_layers=int(cfg["num_hidden_layers"]), num_heads=heads,
            num_passes=int(cfg["total_ut_steps"]),
            epsilon=float(cfg["rms_norm_eps"]),
            rope_base=float(cfg["rope_theta"]))
        net.initialize(mx.init.Zero(), ctx=mx.cpu())
        return net

    def build(self, weights):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        import mxnet_tpu as mx
        from mxnet_tpu.parallel import ParallelTrainer, make_mesh
        self.mx = mx
        cfg, tr = self.config, self.config["trainer"]
        net = self._block(mx, weights)
        trainable = [(k, p) for k, p in net.collect_params().items()
                     if p.grad_req != "null"]
        if len(trainable) != len(weights):
            raise RuntimeError("the block has %d trainable parameters, the "
                               "reference %d" % (len(trainable), len(weights)))
        self._names = {}
        for (pname, p), (rname, w) in zip(trainable, weights.items()):
            if tuple(p.shape) != tuple(w.shape) or not pname.endswith(rname):
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
            net, net.exit_loss(beta=float(cfg["exit_entropy_beta"])), name,
            opt, mesh=mesh, zero=int(tr["zero"]), dtype=tr["dtype"])
        self._batch_ns = NamedSharding(mesh, P(("dp", "fsdp")))
        self._jax = jax
