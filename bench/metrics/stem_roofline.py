"""Share of the roofline reached by the 7x7/2 stem: its im2col pads, gathers and concatenation in kernels/ops.py and its GEMM."""
from bench import roofline


def read(ctx):
    return roofline.share(ctx, "stem")
