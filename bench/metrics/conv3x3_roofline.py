"""Share of the roofline reached by the 3x3 convs on the conv2d kernel (kernels/conv2d.py), with their pads."""
from bench import roofline


def read(ctx):
    return roofline.share(ctx, "conv3x3")
