"""Share of the steady traced span in which a collective runs on a device
and no other op does: the gradient exchange that the backward pass does
not hide. The mean over chips; nothing where the trace holds no
collective. Layer: collectives. Moves train_img_per_s."""
from harness import xplane


def read(ctx):
    shares = []
    for dev in ctx["trace"]["devices"]:
        span = xplane.steady_span(dev, ctx["step_program"])
        if not span or not any(xplane.COLLECTIVE.search(e[0])
                               for e in dev["ops"]):
            continue
        lo, hi, _ = span
        shares.append(xplane.total(xplane.exposed_collective(dev, lo, hi))
                      / (hi - lo))
    return 100.0 * sum(shares) / len(shares) if shares else None
