"""The KDA core's share of its roofline: the least time the chip could
take for the operations and bytes a training step needs there
(harness/flops_lm.py: the chunkwise form's matrix products, and three
passes over q, k, v, the gate and o; whatever implements the op, the same
count), over the device time under ``mx/kda``. Layer: kernels. Moves
train_img_per_s."""
from harness import flops_lm, scopes


def read(ctx):
    cfg = ctx.get("cfg")
    if not cfg:
        return None
    n = ctx["batch_size"]
    return scopes.roofline_pct(
        ctx, "mx/kda", n * flops_lm.kda_flops_per_sequence(cfg),
        n * flops_lm.kda_bytes_per_sequence(cfg))
