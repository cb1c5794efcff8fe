"""Driver: ``ParallelTrainer.step`` over a sparse-expert language model
TRAINED BY DIFFUSION OVER BLOCKS — ``gluon.contrib.transformer.MoELM``
with ``qk_norm`` and ``block_length`` (a noised and a clean copy of every
sequence through every layer under the three-part block mask) with its
own objective, ``MoELM.diffusion_loss()`` (the head over the noised
half's states fused with the clean token's cross-entropy, weighed ``m /
p``).

Everything but the block, the loss and what a batch is placed as is
``drivers/parallel_trainer.py`` as it stands, taken from that file's
class by name.  Nothing of the model lives here: the noising is the
package's operator inside the compiled step.  Only the DRAWS arrive with
the batch, so that the step is a function of arrays the reference has
too: ``place`` makes them from the batch's clean ids alone — it calls the
reference's own ``draws`` — and hands the block ``[ids ; the positions'
draws ; the blocks' draws]`` as ONE int32 array (``ParallelTrainer``
hands a block one array and rounds a float32 one to bfloat16), the clean
ids as the label; the traffic's next-token labels carry nothing a
diffusion objective uses.
"""
import os

import numpy as np

import loader

_HERE = os.path.dirname(os.path.abspath(__file__))
_BASE = loader.load_module(os.path.join(_HERE, "parallel_trainer.py"))


class Driver(_BASE.Driver):
    def _block(self, mx, weights):
        """The block with every parameter materialised on the HOST (its
        shapes are all given at construction; no forward on the chip)."""
        from mxnet_tpu.gluon.contrib.transformer import FULL, MoELM
        cfg = self.config
        if cfg["model"] != "block_diffusion_moe_lm":
            raise ValueError("unknown model %r" % cfg["model"])
        first, end = (int(e) for e in cfg["deployment"]["experts_held"])
        if end - first != int(cfg["num_experts"]):
            raise ValueError("deployment.experts_held is not num_experts")
        net = MoELM(
            int(cfg["vocab_size"]), units=int(cfg["hidden_size"]),
            expert_width=int(cfg["moe_intermediate_size"]),
            layer_types=[FULL] * int(cfg["num_hidden_layers"]),
            num_heads=int(cfg["num_attention_heads"]),
            num_kv_heads=int(cfg["num_key_value_heads"]),
            head_dim=int(cfg["head_dim"]),
            num_routed=int(cfg["published"]["num_experts"]),
            held=(first, end - first),
            top_k=int(cfg["num_experts_per_tok"]),
            rope={FULL: {"rope_theta": float(cfg["rope_theta"])}},
            norm_topk=bool(cfg["norm_topk_prob"]),
            epsilon=float(cfg["rms_norm_eps"]),
            qk_norm=bool(cfg["qk_norm"]),
            block_length=int(cfg["block_length"]),
            mask_token_id=int(cfg["mask_token_id"]),
            noise_eps=float(cfg["noise_eps"]))
        net.initialize(mx.init.Zero(), ctx=mx.cpu())
        trainable = (k for k, p in net.collect_params().items()
                     if p.grad_req != "null")
        for pname, rname in zip(trainable, weights):
            if not pname.endswith(rname):
                raise RuntimeError("parameter %s is not the reference's %s"
                                   % (pname, rname))
        return net

    def _loss(self, net):
        return net.diffusion_loss()

    def place(self, host):
        """``[ids ; position draws ; block draws]`` ``(B, 3, L)`` int32 —
        a block's draw at its first position — and the clean ids as the
        label."""
        ids = np.asarray(host[0]).astype(np.int32)
        length = int(self.config["block_length"])
        if not hasattr(self, "_draws"):
            self._draws = loader.load_module(os.path.join(
                os.path.dirname(_HERE), "reference",
                self.config["family"] + ".py")).draws
        u, t = self._draws(ids, length)
        packed = np.stack([ids, u, np.repeat(t, length, axis=1)], axis=1)
        return super().place((packed, ids))
