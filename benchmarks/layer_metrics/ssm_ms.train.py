"""Device milliseconds a step under the scope ``mx/ssm`` and the scopes
inside it (``mx/ssm/conv``, ``mx/ssm/intra``, ``mx/ssm/scan``): every
Mamba-2 layer's causal convolution, state-space core (forward, the groups
recomputed in its own backward, backward) and gated norm
(harness/scopes_of.py over the configuration's ``device_scopes``; an op
goes to the longest scope it was traced under, so the parts are summed).
Nothing where the configuration lists no such scope or the program has no
such text. Layer: kernels. Moves train_img_per_s."""
from harness import scopes_of


def read(ctx):
    cfg = ctx.get("cfg")
    if not cfg or "mx/ssm" not in cfg.get("device_scopes", ()):
        return None
    ms = scopes_of.scope_ms(ctx)
    if not ms:
        return None
    return sum(v for s, v in ms.items()
               if s == "mx/ssm" or s.startswith("mx/ssm/"))
