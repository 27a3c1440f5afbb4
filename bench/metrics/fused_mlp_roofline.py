"""Share of its roofline for the fused_mlp kernel (bench/kernels/fused_mlp.py):
the least time for all its calls in the traced window over their summed
device time (%)."""
from roofline import share


def read(ctx):
    return share(ctx, "fused_mlp")
