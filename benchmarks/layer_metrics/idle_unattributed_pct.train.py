"""Share of the device's idle time inside the steady span that the
program's spans do not explain: the gaps whose middle lies in no span
below ``mx/fit/epoch``. 0 where the device is never idle. Layer: fit
loop. Moves train_img_per_s."""
from harness import spans


def read(ctx):
    if spans.view(ctx) is None:
        return None
    idle = spans.idle_by_span(ctx)
    whole = sum(idle.values())
    return 100.0 * idle.get("(none)", 0) / whole if whole else 0.0
