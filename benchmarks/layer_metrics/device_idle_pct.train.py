"""Share of the steady traced span in which no op ran on the device:
100 * (1 - union of op intervals / span), the mean over the cell's chips.
Layer: device. Moves train_img_per_s."""
from harness import xplane


def read(ctx):
    shares = []
    for dev in ctx["trace"]["devices"]:
        span = xplane.steady_span(dev, ctx["step_program"])
        if span:
            lo, hi, _ = span
            shares.append(1.0 - xplane.total(xplane.busy(dev, lo, hi))
                          / (hi - lo))
    return 100.0 * sum(shares) / len(shares) if shares else None
