"""The Mamba-2 layers' state-space part as a share of its roofline: the
least time the chip could take for the chunked algorithm's operations at
the configuration's chunk (forward and three times that for training) and
for reading x, z, B, C, dt and writing y and the gradients once
(harness/flops_granite_hybrid.py), over the device time under ``mx/ssm``
and the scopes inside it (``ssm_ms.train``). Layer: kernels. Moves
train_img_per_s."""
import os

from harness import manifest

_ssm_ms = manifest.layer_reader(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "ssm_ms.train")


def read(ctx):
    ms = _ssm_ms(ctx)
    if not ms or not ctx.get("peaks"):
        return None
    from harness import flops_granite_hybrid as flops
    n, cfg = ctx["batch_size"], ctx["cfg"]
    least_s = max(
        n * flops.ssm_flops_per_sequence(cfg) / ctx["peaks"]["flops_per_s"],
        n * flops.ssm_bytes_per_sequence(cfg)
        / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
