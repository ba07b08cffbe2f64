"""Mean host time a step inside ``mx/fit/next``: the fit loop waiting for
the iterator (or the staged feed) to hand it the next batch. From the
program's spans, over the steady span of the device metrics. Layer: feed.
Moves train_img_per_s."""
from harness import spans


def read(ctx):
    return spans.ms_per_step(ctx, "mx/fit/next")
