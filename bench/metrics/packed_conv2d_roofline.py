"""Share of its roofline for the packed_conv2d kernel (bench/kernels/packed_conv2d.py):
the least time for all its calls in the traced window over their summed
device time (%)."""
from roofline import share


def read(ctx):
    return share(ctx, "packed_conv2d")
