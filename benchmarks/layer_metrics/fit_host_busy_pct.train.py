"""Share of the steady span in which the fit thread is in neither
``mx/fit/depth_wait`` nor ``mx/fit/quiesce``, its two waits for the
device: near 0 the device sets the pace and the host has slack, near 100
the host does. From the program's spans. Layer: fit loop. Moves
train_img_per_s."""
from harness import spans


def read(ctx):
    v = spans.view(ctx)
    if v is None:
        return None
    tid = spans.fit_tid(v)
    waits = [e for e in spans.named(v, "mx/fit/depth_wait",
                                    "mx/fit/quiesce")
             if e[spans.TID] == tid]
    waiting = sum(e - s for s, e in spans.clipped(v, waits))
    return 100.0 * (1.0 - waiting / (v["hi"] - v["lo"]))
