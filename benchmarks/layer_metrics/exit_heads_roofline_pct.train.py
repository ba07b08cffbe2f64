"""A looped model's heads, gates and weighted loss as a share of the
heads' roofline: the least time the chip could take for the heads'
operations (``2 x vocabulary x hidden`` a token and a pass forward, three
times that for training) and for moving the head's matrix and the stream
(harness/flops_ouro.py), over the device time under ``mx/lm_head`` and
``mx/exit`` (``exit_heads_ms.train``). Layer: kernels. Moves
train_img_per_s."""
import os

from harness import manifest

_exit_heads_ms = manifest.layer_reader(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "exit_heads_ms.train")


def read(ctx):
    ms = _exit_heads_ms(ctx)
    if not ms or not ctx.get("peaks"):
        return None
    from harness import flops_ouro as flops
    n, cfg = ctx["batch_size"], ctx["cfg"]
    least_s = max(
        n * flops.head_flops_per_sequence(cfg) / ctx["peaks"]["flops_per_s"],
        n * flops.head_bytes_per_sequence(cfg)
        / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
