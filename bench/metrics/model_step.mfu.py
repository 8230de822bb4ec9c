"""model_step.mfu: the whole forward's share of the chip's int8 peak —
the model's operations per image (2 x MACs of every conv and the
classifier, opcount.py) times the images answered inside the window,
over the window's length, the chips and the peak (peaks.py)."""
import loop
import opcount
import peaks


def read(ctx):
    ops = opcount.model_ops_per_image(ctx.layers)
    pk = peaks.peak(ctx.device_kind)
    return (100.0 * ops * loop.rows_per_s(ctx.window)
            / (ctx.n_chips * pk["int8_ops_per_s"]))
