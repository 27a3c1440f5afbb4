"""Share of its roofline for the residual_epilogue kernel (bench/kernels/residual_epilogue.py):
the least time for all its calls in the traced window over their summed
device time (%)."""
from roofline import share


def read(ctx):
    return share(ctx, "residual_epilogue")
