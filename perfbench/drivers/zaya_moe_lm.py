"""Driver: ``ParallelTrainer.step`` over a sparse-expert language model of
COMPRESSED CONVOLUTIONAL ATTENTION — ``gluon.contrib.transformer.MoELM``
with ``cca`` (latent queries and keys mixed by two causal convolutions,
shifted values, L2-normed heads under a temperature), partial rotary, a
router that is an MLP over a top-1 expert layer of which the block holds
one chip's share, and the head tied to the embedding — with its own
objective, ``MoELM.lm_loss()`` (the tied table fused with the next
token's cross-entropy).

Everything but the block and the loss is ``drivers/parallel_trainer.py``
as it stands, taken from that file's class by name.  The block names its
parameters as the reference names its leaves, so ``_block`` also holds
the two to pairing BY NAME.
"""
import os

import loader

_BASE = loader.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "parallel_trainer.py"))


class Driver(_BASE.Driver):
    def _block(self, mx, weights):
        """The block with every parameter materialised on the HOST (its
        shapes are all given at construction; no forward on the chip)."""
        from mxnet_tpu.gluon.contrib.transformer import FULL, MoELM
        cfg = self.config
        if cfg["model"] != "cca_moe_lm":
            raise ValueError("unknown model %r" % cfg["model"])
        first, end = (int(e) for e in cfg["deployment"]["experts_held"])
        if end - first != int(cfg["num_experts"]):
            raise ValueError("deployment.experts_held is not num_experts")
        layers = int(cfg["num_hidden_layers"])
        if set(cfg["layer_types"][:layers]) != {"hybrid"} \
                or cfg["sliding_window"] is not None:
            raise ValueError("every layer run is a hybrid one without a "
                             "window")
        head_dim = int(cfg["head_dim"])
        net = MoELM(
            int(cfg["vocab_size"]), units=int(cfg["hidden_size"]),
            expert_width=int(cfg["moe_intermediate_size"]),
            layer_types=[FULL] * layers,
            num_heads=int(cfg["num_attention_heads"]),
            num_kv_heads=int(cfg["num_key_value_heads"]),
            head_dim=head_dim,
            num_routed=int(cfg["published"]["num_experts"]),
            held=(first, end - first),
            top_k=int(cfg["num_experts_per_tok"]),
            rope={FULL: cfg["rope_parameters"]["hybrid"]},
            norm_topk=bool(cfg["assumed"]["norm_topk_prob"]),
            epsilon=float(cfg["rms_norm_eps"]),
            cca=(int(cfg["cca_time0"]), int(cfg["cca_time1"])),
            rotary_dim=int(round(head_dim
                                 * float(cfg["partial_rotary_factor"]))),
            router_hidden=int(cfg["router_hidden_size"]),
            router_layers=int(cfg["assumed"]["router_mlp_layers"]),
            tie_embeddings=bool(cfg["tie_word_embeddings"]))
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
