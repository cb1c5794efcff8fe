"""Driver: ``ParallelTrainer.step`` over a LATENT-ATTENTION sparse-expert
language model — ``gluon.contrib.transformer.LatentMoELM`` (MLA with a
value head size of its own, a leading dense layer, experts routed by
biased sigmoid scores of which the block holds one chip's share, a
shared expert, multi-token-prediction modules) with its own objective,
``LatentMoELM.lm_loss()`` (every term through the one head, fused with
its cross-entropy).

Everything but the block and the loss is ``drivers/parallel_trainer.py``
as it stands, taken from that file's class by name.  The block names its
parameters as the reference names its leaves, so ``_block`` also holds
the two to pairing BY NAME.  One kind of leaf is HELD and not trained —
the routers' selection bias, ``grad_req`` null: ``build`` sets it beside
the trainable ones, ``leaves`` reads it back and ``slots`` answers zero
for it, so the comparison sees that the step left it alone.
"""
import os

import numpy as np

import loader

_BASE = loader.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "parallel_trainer.py"))


class Driver(_BASE.Driver):
    def _block(self, mx, weights):
        """The block with every parameter materialised on the HOST (its
        shapes are all given at construction; no forward on the chip),
        the held leaves set here."""
        from mxnet_tpu.gluon.contrib.transformer import LatentMoELM
        cfg = self.config
        if cfg["model"] != "latent_moe_lm":
            raise ValueError("unknown model %r" % cfg["model"])
        first, end = (int(e) for e in cfg["deployment"]["experts_held"])
        if end - first != int(cfg["n_routed_experts"]):
            raise ValueError("deployment.experts_held is not "
                             "n_routed_experts")
        layers, dense = int(cfg["num_hidden_layers"]), \
            int(cfg["first_k_dense_replace"])
        net = LatentMoELM(
            int(cfg["vocab_size"]), units=int(cfg["hidden_size"]),
            dense_width=int(cfg["intermediate_size"]),
            expert_width=int(cfg["moe_intermediate_size"]),
            mlp_layer_types=["dense"] * dense + ["sparse"] * (layers - dense),
            num_heads=int(cfg["num_attention_heads"]),
            q_rank=int(cfg["q_lora_rank"]), kv_rank=int(cfg["kv_lora_rank"]),
            nope_dim=int(cfg["qk_nope_head_dim"]),
            rope_dim=int(cfg["qk_rope_head_dim"]),
            v_dim=int(cfg["v_head_dim"]),
            num_routed=int(cfg["published"]["n_routed_experts"]),
            held=(first, end - first),
            top_k=int(cfg["num_experts_per_tok"]),
            shared_experts=int(cfg["n_shared_experts"]),
            scoring=cfg["scoring_func"],
            selection_bias=cfg["topk_method"] == "noaux_tc",
            route_scale=float(cfg["routed_scaling_factor"]),
            norm_topk=bool(cfg["norm_topk_prob"]),
            rope_base=float(cfg["rope_theta"]),
            rope_interleaved=bool(cfg["rope_interleave"]),
            mtp_depth=int(cfg["num_nextn_predict_layers"]),
            epsilon=float(cfg["rms_norm_eps"]))
        net.initialize(mx.init.Zero(), ctx=mx.cpu())
        params = net.collect_params()
        for pname, rname in zip(
                (k for k, p in params.items() if p.grad_req != "null"),
                weights):
            if not pname.endswith(rname):
                raise RuntimeError("parameter %s is not the reference's %s"
                                   % (pname, rname))
        self._held_names = {}
        for pname, rname in zip(
                (k for k, p in params.items() if p.grad_req == "null"),
                self._held):
            if not pname.endswith(rname):
                raise RuntimeError("held parameter %s is not the "
                                   "reference's %s" % (pname, rname))
            params[pname].set_data(mx.nd.array(self._held[rname],
                                               ctx=mx.cpu()))
            self._held_names[pname] = rname
        if len(self._held_names) != len(self._held):
            raise RuntimeError("the block holds %d untrained leaves, the "
                               "reference %d" % (len(self._held_names),
                                                 len(self._held)))
        return net

    def _loss(self, net):
        return net.lm_loss(mtp_weight=float(self.config["mtp_loss_weight"]))

    def build(self, weights):
        self._held = {k: v for k, v in weights.items()
                      if k.endswith("router_bias")}
        super().build({k: v for k, v in weights.items()
                       if k not in self._held})

    def leaves(self):
        out = super().leaves()
        p = self.trainer.params
        out.update({r: np.asarray(p[n], np.float32)
                    for n, r in self._held_names.items()})
        return out

    def slots(self, slot):
        out = super().slots(slot)
        out.update({r: np.zeros_like(v) for r, v in self._held.items()})
        return out
