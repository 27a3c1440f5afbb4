"""Pallas TPU kernel: fully-binary GEMM — both operands bit-packed,
XNOR + Harley-Seal carry-save popcount on the VPU.

This is the literal TPU translation of the TULIP adder tree (§III), now
run symbolically: instead of materializing a [bm, bn, bk32] XNOR cube
and popcounting every word (the removed original kernel's layout, kept
only as the jnp oracle ref.popcount_gemm_ref), the kernel streams one
[bm, bn] XNOR plane per K-word through a carry-save adder network
(kernels/csa.py), so the
SWAR popcount fires once per group of 8 planes — ~3x less VPU work and
~16x less live VMEM.  The CSA residues live in VMEM scratch and thread
across K grid blocks.  Both operands move at 1 bit/value: 32x less
VMEM/HBM traffic than bf16 on activations *and* weights.

Grid (M/bm, N/bn, K32/bk32); the final K block finalizes the popcount,
converts to a signed dot (dot = 2*pc - K) and optionally applies the
folded threshold (paper §IV-D) — scalar or per-output-channel — and,
with ``pack_out=True``, shift-ors the {-1,+1} decisions straight into
uint32 words ([bm, bn/32] output blocks), so the inter-layer activation
never exists in HBM as int32.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.csa import (LANES, col_block, csa_finalize, csa_fold,
                               pack_bit_planes, row_block, tile_divisor)


def _xnor_planes(xp, wpt):
    """One [bm, bn] uint32 XNOR plane per K-word, generated lazily so
    csa_fold traces each group just before folding it.

    xp: [bm, bk32]; wpt: [bk32, bn] (weight block pre-transposed once
    per grid step — cheap vs the cube it replaces)."""
    return (~(xp[:, t:t + 1] ^ wpt[t:t + 1, :])
            for t in range(xp.shape[1]))


def _kernel(xp_ref, wp_ref, *rest, n_k_blocks: int, k: int, k_packed: int,
            threshold: Optional[int], has_tvec: bool, pack_out: bool,
            valid_n: int, bn: int, out_dtype):
    if has_tvec:
        tvec_ref, out_ref, acc_ref, ones_ref, twos_ref, fours_ref = rest
    else:
        out_ref, acc_ref, ones_ref, twos_ref, fours_ref = rest
    k_idx = pl.program_id(2)
    col0 = pl.program_id(1) * bn

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        ones_ref[...] = jnp.zeros_like(ones_ref)
        twos_ref[...] = jnp.zeros_like(twos_ref)
        fours_ref[...] = jnp.zeros_like(fours_ref)

    xp = xp_ref[...]                      # [bm, bk32] uint32
    wpt = wp_ref[...].T                   # [bk32, bn] uint32
    acc, ones, twos, fours = csa_fold(
        _xnor_planes(xp, wpt),
        acc_ref[...], ones_ref[...], twos_ref[...], fours_ref[...])
    acc_ref[...], ones_ref[...] = acc, ones
    twos_ref[...], fours_ref[...] = twos, fours

    @pl.when(k_idx == n_k_blocks - 1)
    def _done():
        pc = csa_finalize(acc_ref[...], ones_ref[...], twos_ref[...],
                          fours_ref[...])
        dot = 2 * (pc - (k_packed - k)) - k
        if threshold is not None or has_tvec:
            thr = tvec_ref[...].astype(jnp.int32) if has_tvec else threshold
            bit = dot >= thr
            if pack_out:
                out_ref[...] = pack_bit_planes(bit, valid_n, col0)
            else:
                out_ref[...] = jnp.where(bit, 1, -1).astype(out_dtype)
        else:
            out_ref[...] = dot.astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("k", "threshold", "pack_out",
                                             "valid_n", "bm", "bn", "bk32",
                                             "interpret"))
def popcount_gemm(xp: jax.Array, wp: jax.Array, k: int,
                  threshold: Optional[int] = None,
                  threshold_vec: Optional[jax.Array] = None,
                  pack_out: bool = False, valid_n: Optional[int] = None,
                  bm: int = 128, bn: int = 128, bk32: int = LANES,
                  interpret: bool = False) -> jax.Array:
    """xp: [M, K32] uint32; wp: [N, K32] uint32; k = valid bit count.

    Returns int32 [M, N]: the signed dot, or {-1,+1} after a threshold
    (static scalar ``threshold`` or int32 [N] ``threshold_vec`` — the
    per-channel folded-BN form).  With ``pack_out=True`` the epilogue
    is fused: the kernel emits uint32 [M, N/32] packed sign words
    directly (bits at columns >= ``valid_n`` forced to 0 so the words
    satisfy the PackedArray pad contract).  Block sizes clamp to the
    largest divisor of each dim that whole TPU tiles make (csa.
    tile_divisor — a packed output tiles its words, so its bn covers
    32*128 columns or all N), and bm shrinks until a [bm, bn] plane
    fits csa.PLANE_WORDS; impossible constraints raise ValueError
    instead of an opaque assert.
    """
    M, K32 = xp.shape
    N, K32w = wp.shape
    if K32 != K32w:
        raise ValueError(f"packed K mismatch: xp has {K32} words, "
                         f"wp has {K32w}")
    has_thr = threshold is not None or threshold_vec is not None
    if threshold is not None and threshold_vec is not None:
        raise ValueError("pass either threshold or threshold_vec, not both")
    if pack_out:
        if not has_thr:
            raise ValueError("pack_out requires a threshold "
                             "(binary output to pack)")
        if N % 32:
            raise ValueError(f"pack_out needs N % 32 == 0, got N={N}; "
                             f"pad N (ops.py dispatch does)")
    bn = col_block(N, bn, pack_out)
    bm = row_block(M, bm, bn)
    bk32 = tile_divisor(K32, bk32, LANES)
    valid_n = N if valid_n is None else valid_n

    grid = (M // bm, N // bn, K32 // bk32)
    if pack_out:
        out_spec = pl.BlockSpec((bm, bn // 32), lambda i, j, kk: (i, j))
        out_shape = jax.ShapeDtypeStruct((M, N // 32), jnp.uint32)
    else:
        out_spec = pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j))
        out_shape = jax.ShapeDtypeStruct((M, N), jnp.int32)
    in_specs = [
        pl.BlockSpec((bm, bk32), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((bn, bk32), lambda i, j, kk: (j, kk)),
    ]
    operands = [xp, wp]
    if threshold_vec is not None:
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)))
        operands.append(threshold_vec.reshape(1, N).astype(jnp.int32))
    return pl.pallas_call(
        functools.partial(_kernel, n_k_blocks=grid[2], k=k,
                          k_packed=32 * K32, threshold=threshold,
                          has_tvec=threshold_vec is not None,
                          pack_out=pack_out, valid_n=valid_n, bn=bn,
                          out_dtype=jnp.int32),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32),
                        pltpu.VMEM((bm, bn), jnp.uint32),
                        pltpu.VMEM((bm, bn), jnp.uint32),
                        pltpu.VMEM((bm, bn), jnp.uint32)],
        interpret=interpret,
        name="popcount_gemm",
    )(*operands)
