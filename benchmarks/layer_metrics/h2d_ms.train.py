"""Mean host time a step inside ``mx/feed/h2d``, on the fit thread or the
feeder's: the cast and the ``device_put`` of inputs that were not yet
where the executor computes. 0 where every batch is resident. From the
program's spans, over the steady span of the device metrics. Layer: feed.
Moves train_img_per_s."""
from harness import spans


def read(ctx):
    return spans.ms_per_step(ctx, "mx/feed/h2d")
