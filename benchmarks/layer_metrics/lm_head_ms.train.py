"""Device milliseconds a step under the scope ``mx/lm_head``: the head's
logits and every token's loss in blocks of tokens, forward and backward
(harness/scopes.py). Layer: kernels. Moves train_img_per_s."""
from harness import scopes


def read(ctx):
    ms = scopes.scope_ms(ctx)
    return ms["mx/lm_head"] if ms else None
