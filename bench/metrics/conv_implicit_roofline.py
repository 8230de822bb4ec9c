"""conv_implicit_roofline: the implicit-GEMM conv kernels' share of their
roofline (kernels/conv_implicit.py).  The least time of every dense conv
of the model, on the microbatch the window ran (opcount.py, peaks.py),
times the stage programs that ran wholly inside the traced window, over
the device time of that family's kernels inside those programs.  Read
only where every microbatch of the window was full, so that the least
time is of the batch the kernels saw."""
import roofline


def read(ctx):
    return roofline.share(ctx, "conv", "conv_implicit")
