"""Device milliseconds a step under the scopes ``mx/moe/route`` and
``mx/moe/experts``: the router over all the published experts, the sort,
the gather, the held experts' grouped products and the scatter back, of
every expert layer (harness/scopes.py). Layer: expert layer. Moves
train_img_per_s."""
from harness import scopes


def read(ctx):
    ms = scopes.scope_ms(ctx)
    return ms["mx/moe/route"] + ms["mx/moe/experts"] if ms else None
