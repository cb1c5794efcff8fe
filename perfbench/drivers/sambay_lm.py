"""Driver: ``ParallelTrainer.step`` over a DECODER-HYBRID-DECODER language
model — ``gluon.contrib.transformer.SambaYLM`` (Mamba mixers on the
selective scan's kernels, differential window / full / cross attention,
gated memory units reading a Mamba layer's scan output, SwiGLU,
LayerNorm, the head tied to the embedding) — with its own objective,
``SambaYLM.lm_loss()`` (the tied table fused with the next token's
cross-entropy).

Everything but the block, the loss and one more check of the fast path
(the scan ran in its kernels) is ``drivers/parallel_trainer.py`` as it
stands, taken from that file's class by name.  The block names its
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
        from mxnet_tpu.gluon.contrib.transformer import SambaYLM
        cfg = self.config
        if cfg["model"] != "sambay_lm":
            raise ValueError("unknown model %r" % cfg["model"])
        a = cfg["assumed"]
        if not cfg["tie_word_embeddings"]:
            raise ValueError("the block's head is its embedding table")
        net = SambaYLM(
            int(cfg["vocab_size"]), units=int(cfg["hidden_size"]),
            hidden_size=int(cfg["intermediate_size"]),
            layer_types=list(cfg["layer_kinds"]),
            num_heads=int(cfg["num_attention_heads"]),
            num_kv_heads=int(cfg["num_key_value_heads"]),
            window=int(cfg["sliding_window"]),
            state_size=int(a["mamba_d_state"]),
            conv_kernel=int(a["mamba_d_conv"]),
            expand=int(a["mamba_expand"]),
            first_layer=int(cfg["first_layer"]),
            epsilon=float(cfg["layer_norm_eps"]))
        net.initialize(mx.init.Zero(), ctx=mx.cpu())
        for pname, rname in zip(net.collect_params().keys(), weights):
            if not pname.endswith(rname):
                raise RuntimeError("parameter %s is not the reference's %s"
                                   % (pname, rname))
        return net

    def _loss(self, net):
        return net.lm_loss()

    def assert_fast_path(self):
        super().assert_fast_path()
        if self.rehearse:
            return
        from mxnet_tpu import telemetry
        calls = telemetry.counter("mxnet_ssm_scan_calls_total")
        if calls.labels(path="kernel").value < 1 \
                or calls.labels(path="plain").value:
            raise RuntimeError("the selective scan did not run in its "
                               "kernels alone")
