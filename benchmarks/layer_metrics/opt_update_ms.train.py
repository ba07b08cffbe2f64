"""Device milliseconds a step under the scope ``mx/opt``
(``module/fused.py``: every parameter's update with its casts), by the
instructions' own ``op_name`` (harness/scope_cover.py). Where the compiler
fuses a matrix's update into the product that makes its gradient, the
fusion carries the product's name and its time is the product's
(``dense_ms.train``): this is the time of the updates that stayed ops of
their own, and scope_cover's line on stderr says how much rides elsewhere.
Nothing where the program names no such scope. Layer: fused step. Moves
train_img_per_s."""
from harness import scope_cover


def read(ctx):
    return scope_cover.under_ms(ctx, ("mx/opt",))
