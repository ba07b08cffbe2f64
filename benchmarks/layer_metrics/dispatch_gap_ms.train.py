"""Mean idle time on the device between one execution of the step
program and the next: what the fit loop's dispatch leaves uncovered.
Layer: fit loop. Moves train_img_per_s."""
from harness import xplane


def read(ctx):
    gaps = []
    for dev in ctx["trace"]["devices"]:
        gaps.extend(xplane.step_gaps(dev, ctx["step_program"]))
    return sum(gaps) / len(gaps) / 1e6 if gaps else None
