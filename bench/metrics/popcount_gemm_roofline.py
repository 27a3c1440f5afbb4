"""Share of its roofline for the popcount_gemm kernel (bench/kernels/popcount_gemm.py):
the least time for all its calls in the traced window over their summed
device time (%)."""
from roofline import share


def read(ctx):
    return share(ctx, "popcount_gemm")
