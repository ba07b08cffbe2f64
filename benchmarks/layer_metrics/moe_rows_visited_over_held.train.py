"""The sorted assignments the expert layers' blocks passed over a step
over the assignments their held experts received: 1 is a walk with no
waste, 2 a block twice what was held (the counters ``moe/rows_visited``
over ``moe/assignments_held`` that the step carries on the device, a
step's mean over the run and the expert layers). Nothing where the program
carries no such counter. Layer: expert layer. Moves train_img_per_s."""


def read(ctx):
    c = ctx.get("counters", {})
    held, visited = c.get("moe/assignments_held"), c.get("moe/rows_visited")
    if not held or visited is None:
        return None
    return visited / held
