"""The busiest held expert's tokens a step over the mean of the held
experts': 1 is even routing; the expert layer's grouped products follow
the counts, so this moves their tiles and not their shape. From the
counters the step carries on the device
(``moe/max_expert_tokens`` over ``moe/assignments_held`` / experts held),
a step's mean over the run and the expert layers. Layer: expert layer.
Moves train_img_per_s."""


def read(ctx):
    c, cfg = ctx.get("counters", {}), ctx.get("cfg")
    held = c.get("moe/assignments_held")
    if not cfg or not held:
        return None
    lo, hi = cfg["experts_held"]
    return c["moe/max_expert_tokens"] / (held / (hi - lo))
