"""Driver: ``ParallelTrainer.step`` over a LOOPED language model —
``gluon.contrib.transformer.LoopedLM`` (one stack of layers applied
``total_ut_steps`` times with shared weights, an exit after every pass)
under its own objective, ``LoopedLM.exit_loss()`` (the exit-weighted
cross-entropy less ``exit_entropy_beta`` times the exit distribution's
entropy).

Everything but the block and the loss is ``drivers/parallel_trainer.py``
as it stands — ``build``, placement, ``step``, ``leaves``, ``slots`` and
``assert_fast_path`` — taken from that file's class by name.  The block
names its parameters as the reference names its leaves, so ``_block``
also holds the two to pairing BY NAME (the base pairs by position and
shape alone: ``TransformerLM``'s generated names do not end in the
reference's).
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
        trainable = (k for k, p in net.collect_params().items()
                     if p.grad_req != "null")
        for pname, rname in zip(trainable, weights):
            if not pname.endswith(rname):
                raise RuntimeError("parameter %s is not the reference's %s"
                                   % (pname, rname))
        return net

    def _loss(self, net):
        return net.exit_loss(beta=float(self.config["exit_entropy_beta"]))
