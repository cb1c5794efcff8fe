"""Driver: ``ParallelTrainer.step`` over a SPARSE-EXPERT language model —
``gluon.contrib.transformer.MoELM`` (top-k routed SwiGLU experts of which
the block holds one chip's share, under sliding-window and full
attention layers) with its own objective, ``MoELM.lm_loss()`` (the head
fused with the next token's cross-entropy).

Everything but the block and the loss is ``drivers/parallel_trainer.py``
as it stands — ``build``, placement, ``step``, ``leaves``, ``slots`` and
``assert_fast_path`` — taken from that file's class by name.  The block
names its parameters as the reference names its leaves, so ``_block``
also holds the two to pairing BY NAME.
"""
import os

import loader

_BASE = loader.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "parallel_trainer.py"))


class Driver(_BASE.Driver):
    def _block(self, mx, weights):
        """The block with every parameter materialised on the HOST (its
        shapes are all given at construction; no forward on the chip)."""
        from mxnet_tpu.gluon.contrib.transformer import MoELM
        cfg = self.config
        if cfg["model"] != "moe_lm":
            raise ValueError("unknown model %r" % cfg["model"])
        first, end = (int(e) for e in cfg["deployment"]["experts_held"])
        if end - first != int(cfg["num_experts"]):
            raise ValueError("deployment.experts_held is not num_experts")
        layers = int(cfg["num_hidden_layers"])
        net = MoELM(
            int(cfg["vocab_size"]), units=int(cfg["hidden_size"]),
            expert_width=int(cfg["moe_intermediate_size"]),
            layer_types=list(cfg["layer_types"])[:layers],
            num_heads=int(cfg["num_attention_heads"]),
            num_kv_heads=int(cfg["num_key_value_heads"]),
            head_dim=int(cfg["head_dim"]),
            num_routed=int(cfg["published"]["num_experts"]),
            held=(first, end - first),
            top_k=int(cfg["num_experts_per_tok"]),
            window=int(cfg["sliding_window"]),
            rope=cfg["rope_parameters"],
            norm_topk=bool(cfg["norm_topk_prob"]),
            epsilon=float(cfg["rms_norm_eps"]))
        net.initialize(mx.init.Zero(), ctx=mx.cpu())
        trainable = (k for k, p in net.collect_params().items()
                     if p.grad_req != "null")
        for pname, rname in zip(trainable, weights):
            if not pname.endswith(rname):
                raise RuntimeError("parameter %s is not the reference's %s"
                                   % (pname, rname))
        return net

    def _loss(self, net):
        return net.lm_loss()
