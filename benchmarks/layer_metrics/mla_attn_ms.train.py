"""Device milliseconds a step under the scope ``mx/mla``: the causal
attention of the latent-attention layer (192-wide q.k, 128-wide v),
forward kernel, recomputation and blockwise backward (harness/scopes.py).
Layer: kernels. Moves train_img_per_s."""
from harness import scopes


def read(ctx):
    ms = scopes.scope_ms(ctx)
    return ms["mx/mla"] if ms else None
