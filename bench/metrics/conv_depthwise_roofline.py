"""conv_depthwise_roofline: the depthwise conv kernel's share of its
roofline (kernels/conv_depthwise.py), read as conv_implicit_roofline is,
over the model's depthwise convs."""
import roofline


def read(ctx):
    return roofline.share(ctx, "dwconv", "conv_depthwise")
