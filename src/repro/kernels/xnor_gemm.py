"""Pallas TPU kernel: binarized GEMM with bit-packed weights.

The TULIP insight on TPU: binary-weight layers are HBM-bandwidth bound
at decode, so weights travel packed (32 per uint32, 16x less traffic
than bf16).  The MXU eats +-1 matmuls at full rate, so the kernel
unpacks each weight tile to +-1 bf16 *in VMEM/VREGs* and feeds the MXU
— the paper's XNOR+popcount becomes unpack+dot via the identity
dot = 2*popcount(xnor) - K.

Grid (M/bm, N/bn, K/bk); fp32 VMEM accumulator; optional fused epilogue
applying the per-channel scale alpha and a threshold->sign (the paper's
batch-norm-folded-into-T trick, §IV-D; scalar or per-channel).  With
``pack_out=True`` the final K block shift-ors the sign decisions into
uint32 words ([bm, bn/32] blocks) so the binarized activation never
exists in HBM as float — the producer side of the fully-binary stack.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.csa import (LANES, SUBLANES, col_block, pack_bit_planes,
                               tile_divisor)


def _kernel(x_ref, wp_ref, alpha_ref, *rest, n_k_blocks: int,
            threshold: Optional[float], has_tvec: bool, pack_out: bool,
            valid_n: int, bn: int, out_dtype):
    if has_tvec:
        tvec_ref, out_ref, acc_ref = rest
    else:
        out_ref, acc_ref = rest
    k_idx = pl.program_id(2)
    col0 = pl.program_id(1) * bn

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]                                   # [bm, bk]
    wp = wp_ref[...]                                 # [bk//32, bn] uint32
    shifts = jax.lax.broadcasted_iota(jnp.uint32, (1, 32, 1), 1)
    bits = (wp[:, None, :] >> shifts) & jnp.uint32(1)
    # Mosaic has no uint32 -> float cast; the bits fit int32 exactly
    w = (2.0 * bits.astype(jnp.int32).astype(jnp.float32) - 1.0
         ).astype(x.dtype)
    w = w.reshape(wp.shape[0] * 32, wp.shape[1])     # [bk, bn] +-1
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k_idx == n_k_blocks - 1)
    def _done():
        y = acc_ref[...] * alpha_ref[...].astype(jnp.float32)
        if threshold is not None or has_tvec:
            thr = tvec_ref[...].astype(jnp.float32) if has_tvec \
                else threshold
            bit = y >= thr
            if pack_out:
                out_ref[...] = pack_bit_planes(bit, valid_n, col0)
            else:
                out_ref[...] = jnp.where(bit, 1.0, -1.0).astype(out_dtype)
        else:
            out_ref[...] = y.astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "threshold",
                                             "pack_out", "valid_n",
                                             "interpret"))
def xnor_gemm(x: jax.Array, wp: jax.Array, alpha: jax.Array,
              threshold: Optional[float] = None,
              threshold_vec: Optional[jax.Array] = None,
              pack_out: bool = False, valid_n: Optional[int] = None,
              bm: int = 128, bn: int = 128, bk: int = 32 * LANES,
              interpret: bool = False) -> jax.Array:
    """x: [M, K] bf16/f32; wp: [K//32, N] uint32; alpha: [N].

    Returns [M, N] in x.dtype (fp32 accumulation); with a threshold
    (static scalar or float [N] ``threshold_vec``), {-1,+1} in x.dtype.
    ``pack_out=True`` fuses the binarize+pack epilogue and returns
    uint32 [M, N/32] (bits at columns >= ``valid_n`` zeroed).  Block
    sizes clamp to the largest divisor of each dim that whole TPU tiles
    make (csa.tile_divisor; bk/32 weight rows are a sublane dim);
    impossible constraints raise ValueError instead of an opaque
    assert.
    """
    M, K = x.shape
    K32, N = wp.shape
    if K != K32 * 32:
        raise ValueError(f"K {K} vs packed {K32 * 32}: x's contraction "
                         f"dim must equal 32x the packed word count")
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
    bm = tile_divisor(M, bm, SUBLANES)
    bn = col_block(N, bn, pack_out)
    # the weight block is bk/32 rows tall (whole sublane tiles make
    # x's bk lanes whole lane tiles too)
    bk = 32 * tile_divisor(K32, bk // 32, SUBLANES)
    valid_n = N if valid_n is None else valid_n

    grid = (M // bm, N // bn, K // bk)
    if pack_out:
        out_spec = pl.BlockSpec((bm, bn // 32), lambda i, j, k: (i, j))
        out_shape = jax.ShapeDtypeStruct((M, N // 32), jnp.uint32)
    else:
        out_spec = pl.BlockSpec((bm, bn), lambda i, j, k: (i, j))
        out_shape = jax.ShapeDtypeStruct((M, N), x.dtype)
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
        pl.BlockSpec((bk // 32, bn), lambda i, j, k: (k, j)),
        pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
    ]
    operands = [x, wp, alpha.reshape(1, N)]
    if threshold_vec is not None:
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, k: (0, j)))
        operands.append(threshold_vec.reshape(1, N).astype(jnp.float32))
    return pl.pallas_call(
        functools.partial(_kernel, n_k_blocks=grid[2], threshold=threshold,
                          has_tvec=threshold_vec is not None,
                          pack_out=pack_out, valid_n=valid_n, bn=bn,
                          out_dtype=x.dtype),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name="xnor_gemm",
    )(*operands)
