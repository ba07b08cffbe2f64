"""Device milliseconds a step under the scope ``mx/attn/window``: the
window layers' grouped-query attention (keys within 2,048 of a query), forward
kernel and blockwise backward, of every such layer
(harness/scopes_of.py over the configuration's ``device_scopes``). Layer:
kernels. Moves train_img_per_s."""
from harness import scopes_of


def read(ctx):
    ms = scopes_of.scope_ms(ctx)
    return ms.get("mx/attn/window") if ms else None
