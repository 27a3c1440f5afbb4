"""The direct packed conv kernel (the program's kernels/packed_conv.py):
one call per binary conv layer planned ``impl=direct``.

Operations: 2 x the layer's MACs at the rows of the call.  Bytes: the
packed input map, the packed filters, the int32 thresholds and the
packed output map, each once (1 bit a value)."""

from geometry import macs

NAMES = ("packed_conv2d",)


def cost(step, rows):
    if step["kind"] != "binary_conv" or step["impl"] != "direct":
        return None
    ly = step["layers"][0]
    out_px = ly["h_out"] * ly["w_out"]
    nbytes = (rows * ly["h_in"] * ly["w_in"] * ly["c_in"] / 8
              + ly["k"] ** 2 * ly["c_in"] * ly["c_out"] / 8
              + 4 * ly["c_out"] + rows * out_px * ly["c_out"] / 8)
    return 2.0 * macs(ly) * rows, nbytes
