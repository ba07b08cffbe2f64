"""The window layers' grouped-query attention (keys within 2,048 of a query)
as a share of its roofline: the least time the chip could take for the
pairs of query and key the mask lets through (counted exactly, forward and
three times that for training) and for reading q, k, v, o and writing the
gradients with the keys' and values' own heads (harness/flops_afmoe.py),
over the device time under ``mx/attn/window``. Layer: kernels. Moves
train_img_per_s."""
from harness import scopes_of


def read(ctx):
    cfg = ctx.get("cfg")
    if not cfg or "mx/attn/window" not in cfg.get("device_scopes", ()):
        return None
    from harness import flops_afmoe
    n = ctx["batch_size"]
    return scopes_of.roofline_pct(
        ctx, "mx/attn/window",
        n * flops_afmoe.attention_flops_per_sequence(cfg, True),
        n * flops_afmoe.attention_bytes_per_sequence(cfg, True))
