"""Device milliseconds a step under the scopes ``mx/op/FullyConnected`` and
``mx/op/_contrib_SwiGLU``: every projection and every dense or shared
feed-forward block, forward, recomputed and backward, with whatever the
compiler fused into a product under the product's name (a weight's update
into the product that makes its gradient): harness/scope_cover.py, whose
line on stderr says how much of it is which. Nothing where the program
names no such scope. Layer: kernels. Moves train_img_per_s."""
from harness import scope_cover


def read(ctx):
    return scope_cover.under_ms(ctx, scope_cover.DENSE_SCOPES)
