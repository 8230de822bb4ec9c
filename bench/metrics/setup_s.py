"""setup_s: seconds from the process's start to the window's start —
imports, finding the chip, weights, compiling them to the served form,
building the front door, the image pool and the warm-up (host clock)."""


def read(ctx):
    return ctx.setup_s
