"""Device milliseconds a step under the scope ``mx/kda``: the chunked
delta-rule recurrence of every KDA layer with its gates, forward,
recomputed and backward, by self time of the ops traced under it
(harness/scopes.py). Layer: kernels. Moves train_img_per_s."""
from harness import scopes


def read(ctx):
    ms = scopes.scope_ms(ctx)
    return ms["mx/kda"] if ms else None
