"""The share of the step's device-busy time spent in instructions whose
``op_name`` holds no ``mx/`` scope: what the program's names do not reach
(harness/scope_cover.py). Prints one line to stderr: every scope's
milliseconds a step and the five largest classes of op left over. Nothing
where the program's text names no ``mx/`` scope at all. Layer: fused step.
Moves train_img_per_s."""
from harness import scope_cover


def read(ctx):
    left = scope_cover.unattributed(ctx)
    if not left or not left[2] or left[0] == left[2]:
        return None
    scope_cover.report(ctx)
    return 100.0 * left[0] / left[2]
