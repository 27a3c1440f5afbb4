"""Pallas TPU kernel: binarize + bit-pack activations.

sign(x) packed 32-per-uint32 along the last axis — the producer side of
popcount_gemm.  Grid (M/bm, K/bk); each block packs 32 consecutive
lanes into one word through csa.pack_bit_planes.  Same bit layout as
the canonical jnp packer in kernels.packed (validated against it in
tests).  The output block holds bk/32 words on the lane axis, so bk is
a multiple of 32*128 bits or the whole K — the registry's pad policy
(m_align=128, k_align=4096) makes dispatch-padded shapes tile that way.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.csa import (LANES, SUBLANES, pack_bit_planes,
                               tile_divisor)


def _kernel(x_ref, out_ref):
    out_ref[...] = pack_bit_planes(x_ref[...] > 0)   # [bm, bk] -> words


@functools.partial(jax.jit, static_argnames=("bm", "bk", "interpret"))
def pack(x: jax.Array, bm: int = 128, bk: int = 32 * LANES,
         interpret: bool = False) -> jax.Array:
    """x: [M, K] (K % 32 == 0) -> uint32 [M, K//32]."""
    M, K = x.shape
    if K % 32:
        raise ValueError(f"pack kernel needs K % 32 == 0, got K={K}; "
                         f"use ops.binarize_pack for unaligned lengths")
    bm = tile_divisor(M, bm, SUBLANES)
    bk = 32 * tile_divisor(K // 32, bk // 32, LANES)
    grid = (M // bm, K // bk)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bk), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((bm, bk // 32), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, K // 32), jnp.uint32),
        interpret=interpret,
        name="pack",
    )(x)
