"""Pallas TPU kernel: the float epilogue of a residual binary conv.

A residual BNN (ReActNet, arXiv:2003.03488) keeps a float32 stream
beside its 1-bit convs: every conv reads ``sign(x + b)`` of the stream
as packed words and adds its batch-normalized integer sums back onto
the stream.  The conv itself is a shared kernel (``packed_conv2d`` or
an im2col ``popcount_gemm``) run without a threshold, so it returns the
int32 sums; this module holds what follows them:

* ``border_table``: the exact integer correction that turns the
  kernels' -1 spatial border (all-zero words, DESIGN.md §7) into the
  zero border a residual net is trained with.  A tap that falls in
  the border contributes ``-sum_c sign(W)`` under the -1 border and
  nothing under a zero border, so the zero-border sum is the kernel's
  sum plus ``sum_c sign(W)`` over the taps in the border.  Which taps
  those are depends only on the output position's *class* (first,
  interior or last row and column), so the correction is an int32
  table, one row of channels per class, added to the sums before any
  float op.
* ``residual_epilogue``: one pass over the int32 sums that applies the
  correction, the batch norm (per-channel scale and bias), the
  shortcut add, RPReLU (``PReLU(v + b_in; slope) + b_out``) and writes
  both the float stream and the channel-packed ``sign(out + b_next)``
  words the next conv reads.  Its jnp twin ``residual_epilogue_ref``
  runs the same expression (``_stream``), op for op, so every backend
  is bit-identical (tests/test_residual.py).  One condition: the BN
  scale must be a bfloat16 value (8 significant bits).  Its product
  with an integer sum below 2**16 is then exact in float32, so the
  scale-and-bias gives the same bits whether a compiler fuses the
  multiply into the add (XLA's CPU backend does, inside one fusion) or
  not; every other multiply feeds a select, not an add.

Layout: sums ``[N, HO, WO, G*C]`` int32, shortcut ``[N, HO, WO, C]``
float32 (added to each of the ``G`` channel groups: ``G = 2`` where a
block doubles its width and both 1x1 halves add the same stream),
per-channel vectors stacked as rows of one ``[6, G*C]`` float32 array
(``VEC_ROWS``).  The grid runs (image, block of ``bh`` output rows);
every step loops over its rows, each a 2-D ``[WO, G*C]`` tile.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.csa import pack_bit_planes
from repro.kernels.packed import PackedArray, pack_words, popcount_u32

__all__ = ["VEC_ROWS", "avg_pool2", "border_classes", "border_table",
           "epilogue_bytes", "residual_epilogue", "residual_epilogue_ref"]

# rows of the stacked per-channel operand, in the order the epilogue
# uses them
VEC_ROWS = ("scale", "bias", "move_in", "slope", "move_out", "next")
_SCALE, _BIAS, _MOVE_IN, _SLOPE, _MOVE_OUT, _NEXT = range(len(VEC_ROWS))
# target bytes of one grid step's int32 sums block
_BLOCK_BYTES = 1 << 20


def _stream(s, corr, vec, sc, groups: int):
    """THE epilogue expression, shared by the kernel body and its jnp
    twin: ``s`` int32 sums [..., G*C]; ``corr`` int32 border
    correction broadcastable to ``s`` or None; ``vec`` [6, G*C]
    float32; ``sc`` float32 shortcut [..., C].  Returns the float32
    stream ``out`` and the decision ``out + next > 0``."""
    if corr is not None:
        s = s + corr
    if groups > 1:
        sc = jnp.concatenate([sc] * groups, axis=-1)
    y = s.astype(jnp.float32) * vec[_SCALE] + vec[_BIAS]
    v = (y + sc) + vec[_MOVE_IN]
    p = jnp.where(v > 0, v, vec[_SLOPE] * v)
    out = p + vec[_MOVE_OUT]
    return out, (out + vec[_NEXT]) > 0


def avg_pool2(x: jax.Array) -> jax.Array:
    """2x2 stride-2 average pool of NHWC ``x`` (even H, W), summed in a
    fixed order: ``((a + b) + (c + d)) * 0.25``."""
    a, b = x[:, 0::2, 0::2], x[:, 0::2, 1::2]
    c, d = x[:, 1::2, 0::2], x[:, 1::2, 1::2]
    return ((a + b) + (c + d)) * 0.25


# ------------------------------------------------------------------ #
# the zero-border correction                                           #
# ------------------------------------------------------------------ #
def _axis_classes(n: int, k: int, stride: int, pad: int
                  ) -> Tuple[Tuple[int, ...], Tuple[frozenset, ...]]:
    """Per output index along one axis, the index of its class, and per
    class the set of kernel taps that fall in the border."""
    out = (n + 2 * pad - k) // stride + 1
    sets, idx = [frozenset()], []
    for i in range(out):
        taps = frozenset(t for t in range(k)
                         if not 0 <= stride * i - pad + t < n)
        if taps not in sets:
            sets.append(taps)
        idx.append(sets.index(taps))
    return tuple(idx), tuple(sets)


def border_classes(h: int, w: int, k: int, stride: int, pad: int):
    """(row class per output row, col class per output column, border
    taps per row class, per col class) of a k x k conv; class 0 is the
    interior (no tap in the border) on both axes."""
    rows, rsets = _axis_classes(h, k, stride, pad)
    cols, csets = _axis_classes(w, k, stride, pad)
    return rows, cols, rsets, csets


def border_table(wf: PackedArray, h: int, w: int, stride: int, pad: int
                 ) -> Tuple[jax.Array, Tuple[int, ...]]:
    """The zero-border correction of the conv of packed filters ``wf``
    ([KH, KW, C, F] packed on C) over an ``h`` x ``w`` map.

    Returns ``(table, rows)``: ``table`` int32 [R, WO, F], the
    correction of an output row of row class ``r`` at each column
    (``sum_c sign(W)`` over the taps in the border), and ``rows`` the
    row class of each output row.  Added to the -1-border sums it gives
    the zero-border sums exactly."""
    kh, kw = wf.words.shape[0], wf.words.shape[1]
    if kh != kw:
        raise ValueError(f"border correction takes square kernels, "
                         f"got {kh}x{kw}")
    rows, cols, rsets, csets = border_classes(h, w, kh, stride, pad)
    # sum over C of sign(W) per tap: 2 * popcount - C (pad bits are 0)
    tap = (2 * jnp.sum(popcount_u32(wf.words), axis=2, dtype=jnp.int32)
           - wf.length)                                  # [KH, KW, F]
    per_class = []
    for rs in rsets:
        line = []
        for cs in csets:
            m = np.array([[a in rs or b in cs for b in range(kw)]
                          for a in range(kh)])
            line.append(jnp.sum(jnp.where(m[:, :, None], tap, 0),
                                axis=(0, 1), dtype=jnp.int32))
        per_class.append(jnp.stack(line))                # [CC, F]
    table = jnp.stack(per_class)[:, np.asarray(cols)]    # [R, WO, F]
    return table, rows


def epilogue_bytes(positions: int, wo: int, cp: int, c: int, pack: bool,
                   table_rows: Optional[int]) -> int:
    """HBM bytes of one ``residual_epilogue`` call over ``positions``
    output positions (N * HO * WO): the int32 sums and the float32
    stream out of ``cp`` channels, the float32 shortcut of ``c``, the
    [6, cp] float32 rows and, for a 3x3 conv, the int32 border table
    [table_rows, WO, cp], each once, and with ``pack`` the signs at 1
    bit a value."""
    nbytes = 4 * positions * (2 * cp + c) + 4 * len(VEC_ROWS) * cp
    if table_rows:
        nbytes += 4 * table_rows * wo * cp
    if pack:
        nbytes += positions * cp // 8
    return nbytes


def _block_rows(ho: int, wo: int, channels: int) -> int:
    """Output rows per grid step: the largest divisor of ``ho`` whose
    int32 sums block (lanes padded to 128) stays near _BLOCK_BYTES."""
    row = 4 * wo * max(channels, 128)
    cap = max(1, _BLOCK_BYTES // row)
    return max(d for d in range(1, ho + 1) if ho % d == 0 and d <= cap)


def _row_class(rows: Tuple[int, ...], bh: int):
    """(first block's classes, middle, last block's) per row of a
    block: only the first and last output rows touch the border, so
    every middle block is interior."""
    nb = len(rows) // bh
    first = rows[:bh]
    last = rows[(nb - 1) * bh:]
    mid = set(rows[bh:(nb - 1) * bh])
    if mid - {0}:
        raise ValueError(f"border rows beyond the first and last block "
                         f"of {bh} rows: {rows}")
    return first, last, nb


# ------------------------------------------------------------------ #
# the kernel                                                           #
# ------------------------------------------------------------------ #
def _kernel(s_ref, sc_ref, vec_ref, *rest, bh: int, groups: int,
            rows: Optional[Tuple[int, ...]], pack: bool):
    rest = list(rest)
    tab_ref = rest.pop(0) if rows is not None else None
    out_ref = rest.pop(0)
    bits_ref = rest.pop(0) if pack else None
    vec = vec_ref[...]
    vec = [vec[i:i + 1] for i in range(len(VEC_ROWS))]
    if rows is not None:
        first, last, nb = _row_class(rows, bh)
        blk = pl.program_id(1)
    for r in range(bh):
        corr = None
        if rows is not None:
            rc = jnp.where(blk == 0, first[r],
                           jnp.where(blk == nb - 1, last[r], 0))
            corr = tab_ref[rc]
        out, bit = _stream(s_ref[0, r], corr, vec, sc_ref[0, r], groups)
        out_ref[0, r] = out
        if pack:
            bits_ref[0, r] = pack_bit_planes(bit)


@functools.partial(jax.jit, static_argnames=("rows", "groups", "pack",
                                             "interpret"))
def _epilogue_call(s, sc, vec, table, *, rows, groups: int, pack: bool,
                   interpret: bool):
    n, ho, wo, cp = s.shape
    c = sc.shape[-1]
    bh = _block_rows(ho, wo, cp)
    in_specs = [pl.BlockSpec((1, bh, wo, cp), lambda i, j: (i, j, 0, 0)),
                pl.BlockSpec((1, bh, wo, c), lambda i, j: (i, j, 0, 0)),
                pl.BlockSpec(vec.shape, lambda i, j: (0, 0))]
    operands = [s, sc, vec]
    if rows is not None:
        in_specs.append(pl.BlockSpec(table.shape, lambda i, j: (0, 0, 0)))
        operands.append(table)
    out_specs = [pl.BlockSpec((1, bh, wo, cp), lambda i, j: (i, j, 0, 0))]
    out_shape = [jax.ShapeDtypeStruct((n, ho, wo, cp), jnp.float32)]
    if pack:
        out_specs.append(pl.BlockSpec((1, bh, wo, cp // 32),
                                      lambda i, j: (i, j, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((n, ho, wo, cp // 32),
                                              jnp.uint32))
    res = pl.pallas_call(
        functools.partial(_kernel, bh=bh, groups=groups, rows=rows,
                          pack=pack),
        grid=(n, ho // bh),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name="residual_epilogue",
    )(*operands)
    return res[0], (res[1] if pack else None)


def _check(s, sc, vec, table, rows):
    n, ho, wo, cp = s.shape
    if s.dtype != jnp.int32:
        raise ValueError(f"sums must be int32, got {s.dtype}")
    if sc.shape[:3] != (n, ho, wo) or cp % sc.shape[-1]:
        raise ValueError(f"shortcut {sc.shape} does not tile sums "
                         f"{s.shape}")
    if vec.shape != (len(VEC_ROWS), cp):
        raise ValueError(f"per-channel rows {vec.shape}, expected "
                         f"{(len(VEC_ROWS), cp)}")
    if (table is None) != (rows is None):
        raise ValueError("pass the border table with its row classes")
    if table is not None and (len(rows) != ho or table.shape[1:] != (wo, cp)):
        raise ValueError(f"border table {table.shape} for {len(rows)} "
                         f"rows does not fit sums {s.shape}")
    return cp // sc.shape[-1]


def residual_epilogue(s: jax.Array, sc: jax.Array, vec: jax.Array,
                      table: Optional[jax.Array] = None,
                      rows: Optional[Tuple[int, ...]] = None,
                      pack: bool = True, interpret: bool = False
                      ) -> Tuple[jax.Array, Optional[PackedArray]]:
    """The Pallas epilogue: ``(out, bits)`` with ``out`` the float32
    stream [N, HO, WO, G*C] and ``bits`` the channel-packed
    ``sign(out + next)`` PackedArray (None without ``pack``).

    s: int32 sums [N, HO, WO, G*C] (-1 border); sc: float32 shortcut
    [N, HO, WO, C]; vec: float32 [6, G*C] rows ``VEC_ROWS``; table,
    rows: ``border_table``'s output for a zero-border conv, or None.
    Packing needs G*C % 32 == 0."""
    groups = _check(s, sc, vec, table, rows)
    if pack and s.shape[-1] % 32:
        raise ValueError(f"packing needs channels % 32 == 0, got "
                         f"{s.shape[-1]}")
    out, words = _epilogue_call(s, sc, vec.astype(jnp.float32), table,
                                rows=None if rows is None else tuple(rows),
                                groups=groups, pack=pack,
                                interpret=interpret)
    bits = None if words is None else PackedArray(
        words, length=s.shape[-1], axis=-1)
    return out, bits


def residual_epilogue_ref(s: jax.Array, sc: jax.Array, vec: jax.Array,
                          table: Optional[jax.Array] = None,
                          rows: Optional[Tuple[int, ...]] = None,
                          pack: bool = True
                          ) -> Tuple[jax.Array, Optional[PackedArray]]:
    """The jnp twin of ``residual_epilogue``: the same ``_stream``
    expression over whole arrays, the correction gathered per row, the
    decision packed by the canonical packer."""
    groups = _check(s, sc, vec, table, rows)
    corr = None if table is None else table[np.asarray(rows)][None]
    out, bit = _stream(s, corr, vec.astype(jnp.float32), sc, groups)
    if not pack:
        return out, None
    return out, PackedArray(pack_words(bit, axis=-1), length=s.shape[-1],
                            axis=-1)
