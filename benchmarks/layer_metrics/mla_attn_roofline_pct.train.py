"""The latent-attention layer's causal attention as a share of its
roofline: the least time the chip could take for T^2 (192 + 128) heads
operations a sequence forward and three times that for training, and for
reading q, k, v, o and writing the gradients (harness/flops_lm.py), over
the device time under ``mx/mla``. Layer: kernels. Moves
train_img_per_s."""
from harness import flops_lm, scopes


def read(ctx):
    cfg = ctx.get("cfg")
    if not cfg:
        return None
    n = ctx["batch_size"]
    return scopes.roofline_pct(
        ctx, "mx/mla", n * flops_lm.attention_flops_per_sequence(cfg),
        n * flops_lm.attention_bytes_per_sequence(cfg))
