"""The XNOR-popcount GEMM kernel (the program's kernels/popcount_gemm.py):
one call per binary conv layer planned ``impl=im2col`` and per dense
layer launched alone (the classifier head).

Operations: 2 x the layer's MACs at the rows of the call.  Bytes: the
packed left operand (for a conv the im2col patch matrix, k*k*c_in bits
per output pixel), the packed weights, the int32 thresholds, and the
output (packed where thresholded, int32 logits for the head)."""

from geometry import macs

NAMES = ("popcount_gemm",)


def cost(step, rows):
    if step["kind"] == "binary_conv" and step["impl"] == "im2col":
        ly = step["layers"][0]
        out_px = ly["h_out"] * ly["w_out"]
        kk = ly["k"] ** 2 * ly["c_in"]
        nbytes = (rows * out_px * kk / 8 + kk * ly["c_out"] / 8
                  + 4 * ly["c_out"] + rows * out_px * ly["c_out"] / 8)
        return 2.0 * macs(ly) * rows, nbytes
    if step["kind"] == "dense":
        ly = step["layers"][0]
        thr = ly.get("threshold", True)
        out_bytes = ly["n_out"] / 8 if thr else 4 * ly["n_out"]
        nbytes = (rows * ly["n_in"] / 8 + ly["n_in"] * ly["n_out"] / 8
                  + (4 * ly["n_out"] if thr else 0) + rows * out_bytes)
        return 2.0 * macs(ly) * rows, nbytes
    return None
