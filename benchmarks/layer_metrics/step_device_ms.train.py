"""Device-busy milliseconds inside one execution of the step program,
over the steps it holds (K a scan dispatch); the mean over executions
and chips. Layer: fused step. Moves train_img_per_s."""
from harness import xplane


def read(ctx):
    per_step = []
    for dev in ctx["trace"]["devices"]:
        ops = xplane.union(xplane.intervals(dev["ops"]))
        for _name, start, dur in xplane.step_modules(dev, ctx["step_program"]):
            per_step.append(xplane.total(xplane.clip(ops, start, start + dur))
                            / ctx["steps_per_program"])
    return sum(per_step) / len(per_step) / 1e6 if per_step else None
