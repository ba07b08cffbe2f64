"""Device milliseconds a step under the scope ``mx/attn/full``: the
full layers' causal grouped-query attention, forward
kernel and blockwise backward, of every such layer
(harness/scopes_of.py over the configuration's ``device_scopes``). Layer:
kernels. Moves train_img_per_s."""
from harness import scopes_of


def read(ctx):
    ms = scopes_of.scope_ms(ctx)
    return ms.get("mx/attn/full") if ms else None
