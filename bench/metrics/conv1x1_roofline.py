"""Share of the roofline reached by the 1x1 convs, projections included, on both GEMM stationarities (kernels/matmul.py)."""
from bench import roofline


def read(ctx):
    return roofline.share(ctx, "conv1x1")
