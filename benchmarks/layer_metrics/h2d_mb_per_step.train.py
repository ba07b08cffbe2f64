"""Megabytes (1e6 bytes) a step that the program copied to the device
for its inputs: the ``bytes`` the ``mx/feed/h2d`` spans begun inside the
steady span carry, counted where the copy is made. 0 where every batch is
resident, 77.07 for a float32 batch of 128x3x224x224 with its labels.
Layer: feed. Moves train_img_per_s."""
from harness import spans


def read(ctx):
    v = spans.view(ctx)
    if v is None:
        return None
    return spans.count_sum(v, "mx/feed/h2d", "bytes") / 1e6 / v["steps"]
