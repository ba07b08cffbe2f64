"""The dense products as a share of the peak: the least time the chip
could take for their operations (harness/flops_dense.py, from the
configuration's shapes: 2 x tokens x weights of every matrix that
``FullyConnected`` and ``_contrib_SwiGLU`` apply, forward, and twice that
for the backward pass; nothing recomputed is counted, the same count
whatever implements the ops) over the device time under their scopes
(``dense_ms.train``). That time holds the forward products that are
recomputed and the updates fused into the weight-gradient products, which
the count leaves out: the share is the useful operations' and a floor of
the products' own (scope_cover's line on stderr gives the parts). Nothing
where the program names no such scope or the peaks are not known. Layer:
kernels. Moves train_img_per_s."""
import os

from harness import flops_dense, manifest

_dense_ms = manifest.layer_reader(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "dense_ms.train")


def read(ctx):
    ms = _dense_ms(ctx)
    fwd = flops_dense.forward_flops(ctx.get("cfg") or {}, ctx["batch_size"])
    if not ms or not fwd or not ctx.get("peaks"):
        return None
    return 100.0 * (3 * fwd / ctx["peaks"]["flops_per_s"]) / (ms / 1e3)
