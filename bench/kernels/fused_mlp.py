"""The fused binary MLP megakernel (the program's kernels/fused_mlp.py):
one call per planned ``fused_stack`` segment, every layer of it in one
launch with the activations resident in VMEM.

Its HLO name is ``_lambda_`` until the program names the pallas_call;
``fused_mlp`` is listed for when it does.

Operations: 2 x the MACs of every layer of the segment at the rows of
the call.  Bytes: the packed input, every layer's packed weights and
int32 thresholds, and the packed output of the last layer."""

from geometry import macs

NAMES = ("fused_mlp", "_lambda_")


def cost(step, rows):
    if step["kind"] != "fused_stack":
        return None
    lys = step["layers"]
    nbytes = (rows * lys[0]["n_in"] / 8
              + sum(ly["n_in"] * ly["n_out"] / 8 + 4 * ly["n_out"] for ly in lys)
              + rows * lys[-1]["n_out"] / 8)
    return 2.0 * sum(macs(ly) for ly in lys) * rows, nbytes
