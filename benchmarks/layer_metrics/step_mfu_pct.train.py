"""The whole step's share of the chips' peak: the analytic FLOPs of the
images whose steps began inside the steady traced span (harness/flops.py:
forward and backward, nothing recomputed), over the span, over chips x
peak FLOP/s (harness/peaks.py). Layer: fused step. Moves train_img_per_s."""
from harness import xplane


def read(ctx):
    shares = []
    for dev in ctx["trace"]["devices"]:
        span = xplane.steady_span(dev, ctx["step_program"])
        if span:
            lo, hi, steps = span
            flops = steps * ctx["steps_per_program"] * ctx["batch_size"] \
                * ctx["train_flops_per_image"]
            shares.append(flops / ((hi - lo) / 1e9)
                          / (ctx["chips"] * ctx["peaks"]["flops_per_s"]))
    return 100.0 * sum(shares) / len(shares) if shares else None
