"""Mean host time a step of ``mx/fit/dispatch`` itself: the Python of the
fit step and the enqueue of the step program, less what its children
cover (``mx/feed/h2d``). From the program's spans, over the steady span
of the device metrics. Layer: fit loop. Moves train_img_per_s."""
from harness import spans


def read(ctx):
    return spans.ms_per_step(ctx, "mx/fit/dispatch", own=True)
