"""Device milliseconds a step under the scopes ``mx/lm_head`` and
``mx/exit`` of a looped model: every pass's head and cross-entropy, the
exit gates, the exit distribution and the weighted loss, forward and
backward (harness/scopes_of.py over the configuration's
``device_scopes``). Nothing where the configuration lists no ``mx/exit``
or the program has no such text. Layer: kernels. Moves train_img_per_s."""
from harness import scopes_of


def read(ctx):
    cfg = ctx.get("cfg")
    if not cfg or "mx/exit" not in cfg.get("device_scopes", ()):
        return None
    ms = scopes_of.scope_ms(ctx)
    if not ms:
        return None
    return ms.get("mx/lm_head", 0.0) + ms["mx/exit"]
