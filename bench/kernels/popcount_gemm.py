"""The XNOR-popcount GEMM kernel (the program's kernels/popcount_gemm.py):
one call per step that runs a single part: a ``binary_conv`` part
planned ``impl=im2col``, or a ``dense`` part in a ``dense`` step, launched
alone (the classifier head; a ``fused_stack`` runs several).  A dense
step carries no ``impl``, so its step kind tells it from another step
of the same name.

Operations: 2 x the part's MACs at the rows of the call.  Bytes: the
packed left operand (for a conv the im2col patch matrix, k*k*c_in bits
per output pixel), the packed weights and the output: where the part
is thresholded (the default), its int32 thresholds and a packed output
(1 bit a value), else int32 sums (4 bytes a value, the head's
logits)."""

from geometry import macs

NAMES = ("popcount_gemm",)


def cost(step, rows):
    lys = step["layers"]
    if len(lys) != 1:
        return None
    ly = lys[0]
    if ly["kind"] == "binary_conv" and step["impl"] == "im2col":
        out_px = ly["h_out"] * ly["w_out"]
        n_in, n_out = ly["k"] ** 2 * ly["c_in"], ly["c_out"]
    elif ly["kind"] == "dense" and step["kind"] == "dense":
        out_px, n_in, n_out = 1, ly["n_in"], ly["n_out"]
    else:
        return None
    thr = ly.get("threshold", True)
    out_vals = rows * out_px * n_out
    nbytes = (rows * out_px * n_in / 8 + n_in * n_out / 8
              + (4 * n_out if thr else 0)
              + (out_vals / 8 if thr else 4 * out_vals))
    return 2.0 * macs(ly) * rows, nbytes
