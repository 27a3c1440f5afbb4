"""The direct packed conv kernel (the program's kernels/packed_conv.py):
one call per step that runs a single ``binary_conv`` part planned
``impl=direct``.

Operations: 2 x the part's MACs at the rows of the call.  Bytes: the
packed input map, the packed filters and the output map, each once:
where the part is thresholded (the default), its int32 thresholds and
a packed output (1 bit a value), else int32 sums (4 bytes a value)."""

from geometry import macs

NAMES = ("packed_conv2d",)


def cost(step, rows):
    lys = step["layers"]
    if len(lys) != 1 or lys[0]["kind"] != "binary_conv" or step["impl"] != "direct":
        return None
    ly = lys[0]
    thr = ly.get("threshold", True)
    out_vals = rows * (ly["h_out"] * ly["w_out"]) * ly["c_out"]
    nbytes = (rows * ly["h_in"] * ly["w_in"] * ly["c_in"] / 8
              + ly["k"] ** 2 * ly["c_in"] * ly["c_out"] / 8
              + (4 * ly["c_out"] if thr else 0)
              + (out_vals / 8 if thr else 4 * out_vals))
    return 2.0 * macs(ly) * rows, nbytes
