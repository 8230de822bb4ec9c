"""images_per_s: images whose logits reached the host inside the window,
over the window's length (host clock)."""
import loop


def read(ctx):
    return loop.rows_per_s(ctx.window)
