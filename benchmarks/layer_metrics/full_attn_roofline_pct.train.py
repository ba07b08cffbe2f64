"""The full layers' causal grouped-query attention
as a share of its roofline: the least time the chip could take for the
pairs of query and key the mask lets through (counted exactly, forward and
three times that for training) and for reading q, k, v, o and writing the
gradients with the keys' and values' own heads (harness/flops_afmoe.py),
over the device time under ``mx/attn/full``. Layer: kernels. Moves
train_img_per_s."""
from harness import scopes_of


def read(ctx):
    cfg = ctx.get("cfg")
    if not cfg or "mx/attn/full" not in cfg.get("device_scopes", ()):
        return None
    from harness import flops_afmoe
    n = ctx["batch_size"]
    return scopes_of.roofline_pct(
        ctx, "mx/attn/full",
        n * flops_afmoe.attention_flops_per_sequence(cfg, False),
        n * flops_afmoe.attention_bytes_per_sequence(cfg, False))
