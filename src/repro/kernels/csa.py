"""Harley-Seal carry-save popcount accumulation over uint32 bit planes.

The TULIP adder tree (paper §III) sums XNOR bits through a network of
threshold-logic full adders.  The straight VPU translation popcounts
every packed word (15 ops/word); Harley-Seal does better by running the
full-adder network *symbolically* on whole 32-bit planes: a carry-save
adder (CSA) compresses three planes into a sum plane and a carry plane
(5 bitwise ops), so a group of 8 planes collapses through 7 CSAs into
one "eights" carry plane plus residues, and the expensive SWAR popcount
runs once per group instead of once per word — ~3x less VPU work and no
[bm, bn, bk32] XNOR cube in VMEM (one [bm, bn] plane at a time).

Three consumers build on these helpers: the popcount GEMM (residues
threaded across K grid blocks in VMEM scratch), the fused-MLP
megakernel (a whole layer's K folded in registers), and the packed
conv kernel (one plane per window tap word — conv is a different
gather in front of the identical reduction, DESIGN.md §7).  The
historical [bm, bn, bk32]-cube kernel this restructuring replaced is
gone from the tree; its jnp twin survives as `ref.popcount_gemm_ref`
(the bit-exactness oracle) and is what kernels_bench.py races the CSA
twin (`ref.popcount_gemm_csa_ref`) against.  Derivation: DESIGN.md §6.

Inputs/outputs: all plane arguments are uint32 arrays of one common
shape (any rank); `csa_fold` consumes an iterable of such planes plus
the 4-tuple state and returns the updated state; `csa_finalize` collapses
the state to the int32 popcount total.

Invariants / failure modes:
* after every `csa_fold` call,
  ``total = acc + pc(ones) + 2*pc(twos) + 4*pc(fours)`` — the state
  may be cut at ANY K split (grid blocks, layer boundaries) and
  resumed, which is what makes the VMEM-scratch threading sound;
* a partial group (< 8 planes) is padded with zero planes, which add
  nothing — callers never need to align their plane counts;
* `pack_bit_planes` requires bn % 32 == 0 (it emits whole words) and
  zeroes columns >= valid_n so its output satisfies the PackedArray
  pad contract; `col_block` keeps a pack_out block a whole number of
  words (the kernels reject N % 32 != 0 up front);
* `tile_divisor` / `col_block` are the one rule for block sizes: the
  TPU compiler refuses a block whose two minor dims are neither whole
  (8, 128) tiles nor the array's whole dims.
"""
from __future__ import annotations

import itertools
from typing import Iterable, Optional, Tuple

import jax
import jax.numpy as jnp

# THE canonical SWAR popcount (kernels.packed owns it; packed does not
# import csa, so no cycle)
from repro.kernels.packed import popcount_u32 as popcount_word

GROUP = 8  # planes folded per popcount; weights 1/2/4 remain as residues
SUBLANES, LANES = 8, 128   # the (8, 128) tile every TPU block is made of
# words in one XNOR plane (rows x columns) of a GEMM kernel: the v5e
# compiler keeps about thirty planes of a block live in VMEM at once, so
# 32K words (128 KB) keeps popcount_gemm and fused_mlp inside the 16 MB
# scoped VMEM at BinaryNet widths (compile-checked: tests/test_tpu_compile.py)
PLANE_WORDS = 32 * 1024


def csa(a, b, c):
    """Carry-save full adder on bit planes: returns (sum, carry) with
    a + b + c == sum + 2 * carry, bitwise-parallel across all lanes."""
    u = a ^ b
    return u ^ c, (a & b) | (u & c)


def csa_fold(planes: Iterable[jnp.ndarray], acc, ones, twos, fours
             ) -> Tuple[jnp.ndarray, ...]:
    """Fold bit planes into a Harley-Seal state.

    State: ``acc`` (int32 popcount partial sum) plus the uint32 residue
    planes ``ones``/``twos``/``fours`` holding not-yet-counted bits of
    weight 1/2/4.  Each full GROUP of 8 planes emits one "eights" carry
    plane, popcounted with weight 8; a trailing partial group is padded
    with zero planes (zeros add nothing).  The invariant
    ``total = acc + pc(ones) + 2*pc(twos) + 4*pc(fours)`` holds after
    every call, so the state may be threaded across any block split of
    the K axis (csa_finalize collapses it).

    ``planes`` is consumed lazily, one group at a time: pass a
    generator, and each group's planes are traced just before they are
    folded, so the kernel's live working set is one group plus the
    state rather than every plane of the block (which overflows VMEM
    at real widths)."""
    it = iter(planes)
    while True:
        d = list(itertools.islice(it, GROUP))
        if not d:
            return acc, ones, twos, fours
        d += [jnp.zeros_like(d[0])] * (GROUP - len(d))
        ones, t0 = csa(ones, d[0], d[1])
        ones, t1 = csa(ones, d[2], d[3])
        twos, f0 = csa(twos, t0, t1)
        ones, t0 = csa(ones, d[4], d[5])
        ones, t1 = csa(ones, d[6], d[7])
        twos, f1 = csa(twos, t0, t1)
        fours, e = csa(fours, f0, f1)
        acc = acc + GROUP * popcount_word(e)


def csa_finalize(acc, ones, twos, fours):
    """Collapse the Harley-Seal state to the total popcount (int32)."""
    return (acc + popcount_word(ones) + 2 * popcount_word(twos)
            + 4 * popcount_word(fours))


def pack_bit_planes(bits, valid_n: Optional[int] = None, col0=0):
    """Shift-or a [bm, bn] boolean decision plane into uint32 words
    [bm, bn // 32], zeroing columns >= ``valid_n`` (global column index
    = ``col0`` + local index) so pad bits are 0 per the PackedArray
    contract — the words feed the next layer's K axis directly.

    Mosaic can neither split the lane axis into (words, 32) nor reduce
    unsigned integers, so the shift-or runs on the MXU instead: word j
    is ``bits @ P`` with ``P[32j + b, j] = 2**b``, done as two bf16
    matmuls over the low and high 16 bit positions.  Every partial sum
    is an integer below 2**16, exact in the f32 accumulator, so the
    words are bit-identical to the canonical packer."""
    bm, bn = bits.shape
    nw = bn // 32
    if valid_n is not None:
        col = col0 + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 1)
        bits = bits & (col < valid_n)
    r = jax.lax.broadcasted_iota(jnp.int32, (bn, nw), 0)
    own = (r >> 5) == jax.lax.broadcasted_iota(jnp.int32, (bn, nw), 1)
    pos = r & 31
    weight = jnp.left_shift(1, pos & 15)
    b16 = bits.astype(jnp.bfloat16)

    def half(sel):
        p = jnp.where(own & sel, weight, 0).astype(jnp.float32)
        return jnp.dot(b16, p.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32).astype(jnp.int32)

    words = half(pos < 16) | (half(pos >= 16) << 16)
    return jax.lax.bitcast_convert_type(words, jnp.uint32)


def tile_divisor(n: int, cap: int, tile: int) -> int:
    """Largest divisor of ``n`` that is a multiple of ``tile`` and at
    most ``max(cap, tile)``; ``n`` itself when there is none.  A TPU
    block's two minor dims must be whole (8, 128) tiles or span the
    array's dim, so this is the one legal clamp for every block size a
    kernel takes from a caller or the tuning table."""
    cap = max(cap, tile)
    for d in range(min(cap, n) // tile * tile, 0, -tile):
        if n % d == 0:
            return d
    return n


def col_block(n: int, bn: int, pack_out: bool) -> int:
    """Legal output-column block of an ``n``-column launch.  An int32
    output blocks its columns in lane tiles; a ``pack_out`` output
    emits ``bn // 32`` words per block, so its *word* count is what
    must be lane-tiled (or span every word)."""
    if not pack_out:
        return tile_divisor(n, bn, LANES)
    return 32 * tile_divisor(n // 32, bn // 32, LANES)


def row_block(m: int, bm: int, plane_n: int) -> int:
    """Legal row block whose [rows, ``plane_n``] XNOR planes stay
    within PLANE_WORDS."""
    return tile_divisor(m, min(bm, PLANE_WORDS // plane_n), SUBLANES)
