"""The residual epilogue kernel (the program's kernels/residual.py):
one call per ``residual_epilogue`` step, the float epilogue of one
binary conv of a residual block (bench/layers/reactnet_block.py).

Operations: OPS_PER_VALUE for each output value at the rows of the
call: the border correction add, the int to float convert, the BN
multiply and add, the shortcut add, the RPReLU input add, compare,
multiply and select, its output add, and the next sign's add and
compare.  Bytes, each operand and result once: the int32 sums and the
float32 stream out (4 bytes a value of every output channel), the
float32 shortcut (4 bytes a value of the block's input channels, which
a doubled output adds twice), the six float32 per-channel rows, the
int32 border table of a 3x3 conv (a row of channels for each output
column of each row class), and, unless the part is the last block's,
the packed signs (1 bit a value)."""

NAMES = ("residual_epilogue",)
OPS_PER_VALUE = 12
VEC_ROWS = 6


def cost(step, rows):
    lys = step["layers"]
    if step["kind"] != "residual_epilogue" or len(lys) != 1:
        return None
    ly = lys[0]
    px = rows * ly["h_out"] * ly["w_out"]
    cp = ly["channels"]
    nbytes = (4 * px * cp + 4 * px * ly["shortcut"] + 4 * px * cp + 4 * VEC_ROWS * cp
              + 4 * ly["table_rows"] * ly["w_out"] * cp
              + (px * cp / 8 if ly["pack"] else 0))
    return float(OPS_PER_VALUE * px * cp), nbytes
