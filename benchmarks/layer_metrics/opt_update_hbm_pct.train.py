"""The optimizer's updates that are ops of their own as a share of the
memory's pace: the least time the chip could take to read every operand
and write every result of the instructions under ``mx/opt`` once (their
shapes in the compiled step's text: a master parameter and its state read
and written, the gradient read), over those instructions' device time
(``opt_update_ms.train``). Work and time are of the same instructions; an
update that the compiler fused into a product is in neither. Nothing where
the program names no such scope or the peaks are not known. Layer: fused
step. Moves train_img_per_s."""
from harness import scope_cover


def read(ctx):
    got = scope_cover.under(ctx, ("mx/opt",))
    if not got or not ctx.get("peaks"):
        return None
    ms, nbytes = got["mx/opt"]
    if not ms or not nbytes:
        return None
    return 100.0 * (nbytes / ctx["peaks"]["hbm_bytes_per_s"]) / (ms / 1e3)
