"""Pallas TPU kernel: fully-binary conv2d on channel-packed NHWC words.

The paper's headline workloads (BinaryNet CIFAR-10, XNOR-AlexNet,
Tables III-V) are convolutional: the TULIP-PE schedule slides a k x k
window of XNOR products through the adder tree, one output pixel per
pass, never materializing an im2col matrix.  This kernel is the TPU
translation of that schedule:

* Activations travel channel-packed: NHWC with C packed 32-per-uint32
  along the last axis -> ``[N, H, W, C/32]`` words (the PackedArray
  layout, DESIGN.md SS1/SS7).  Spatial "same" padding is **-1 padding**
  (all-zero words), which the pm1 bit encoding represents exactly —
  unlike real zeros, which a 1-bit code cannot express.
* Filters travel as ``[KH*KW*C/32, F]`` words, tap-major: the C axis is
  packed per (kh, kw) tap, taps concatenated row-major, so the word at
  index ``(kh*KW + kw)*C32 + t`` aligns with activation word ``t`` of
  the window pixel ``(kh, kw)``.  Per-tap channel pad bits are 0 on
  both sides, so they XNOR to 1 and cancel through the same closed
  form as the GEMMs: ``dot = 2*(pc - (K_padded - K)) - K`` with
  ``K = KH*KW*C`` and ``K_padded = 32*KH*KW*C32``.
* The inner loop is im2col-free: grid (N, F/bf); each step holds one
  sample's padded image resident in VMEM and streams one
  ``[HO*WO, bf]`` XNOR plane per (tap, word) through the Harley-Seal
  carry-save network (kernels/csa.py) — the window gather is a strided
  re-slice of resident words, so the 9x (3x3) input re-read of an
  im2col materialization never touches HBM.
* The epilogue is the PR-2 fused threshold->pack: the folded-BN integer
  threshold (static scalar or per-channel int32 [F] operand) is applied
  in-kernel and, with ``pack_out=True``, the +-1 decisions are
  shift-or'd into uint32 words, so inter-layer conv activations never
  exist in HBM as int32 NHWC (jaxpr-asserted in tests/test_conv.py).

``im2col_words`` is the fallback path: it gathers the window patches at
*word* granularity into a ``[M, KH*KW*C32]`` matrix that drops straight
into ``popcount_gemm`` via ops.py — same closed form, same epilogue,
but it pays the patch-matrix HBM round-trip (benchmarks
``kernels_bench.py --conv`` quantifies the gap).  The jnp sign-conv
oracle twin is ``ref.sign_conv2d_ref``; all three paths are bit-exact
on pallas / interpret / xla (tests/test_conv.py).

Failure modes: shapes are validated up front (C mismatch, F % bf,
pack_out without threshold, pack_out with F % 32 != 0) and raise
ValueError — dispatch in ops.py pads F and classifies thresholds so
end users never construct a bad launch by hand.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.csa import (col_block, csa_finalize, csa_fold,
                               pack_bit_planes)
from repro.kernels.packed import VMEM_BUDGET_BYTES

__all__ = ["VMEM_BUDGET_BYTES", "conv_vmem_bytes", "im2col_words",
           "out_size", "packed_conv2d", "pad_words_spatial"]


def out_size(n: int, k: int, stride: int, pad: int) -> int:
    """Output extent of a VALID conv over the padded extent."""
    return (n + 2 * pad - k) // stride + 1


def conv_vmem_bytes(h_pad: int, w_pad: int, c32: int, kh: int, kw: int,
                    m: int, bf: int) -> int:
    """Rough per-grid-step resident footprint of the direct kernel:
    the padded image, one filter block, the CSA working set (acc +
    3 residue planes + the live XNOR plane), and the output block —
    the estimate ops.binary_conv2d's impl="auto" dispatch compares to
    VMEM_BUDGET_BYTES before falling back to im2col."""
    image = 4 * h_pad * w_pad * c32
    wblock = 4 * kh * kw * c32 * bf
    planes = 5 * 4 * m * bf
    return image + wblock + planes + 4 * m * bf


def _window(x, i_kh: int, i_kw: int, stride: int, ho: int, wo: int):
    """Strided window gather on the resident image: the (i_kh, i_kw)
    tap's word for every output pixel -> [ho, wo, C32]."""
    return x[i_kh:i_kh + (ho - 1) * stride + 1:stride,
             i_kw:i_kw + (wo - 1) * stride + 1:stride, :]


def _conv_kernel(x_ref, w_ref, *rest, kh: int, kw: int, stride: int,
                 ho: int, wo: int, k: int, k_packed: int,
                 threshold: Optional[int], has_tvec: bool, pack_out: bool,
                 valid_f: int, bf: int):
    if has_tvec:
        tvec_ref, out_ref = rest
    else:
        out_ref, = rest
    col0 = pl.program_id(1) * bf

    x = x_ref[0]                          # [H_pad, W_pad, C32] uint32
    w = w_ref[...]                        # [KH*KW*C32, bf]    uint32
    c32 = x.shape[-1]
    m = ho * wo

    # one [m, bf] XNOR plane per (tap, word) through the CSA network —
    # identical fold order to popcount_gemm, just a different gather;
    # generated lazily so only one group of planes is live at a time
    def planes():
        for i_kh in range(kh):
            for i_kw in range(kw):
                xm = _window(x, i_kh, i_kw, stride, ho, wo).reshape(m, c32)
                base = (i_kh * kw + i_kw) * c32
                for t in range(c32):
                    yield ~(xm[:, t:t + 1] ^ w[base + t:base + t + 1, :])

    zero = jnp.zeros((m, bf), jnp.uint32)
    acc, ones, twos, fours = csa_fold(
        planes(), jnp.zeros((m, bf), jnp.int32), zero, zero, zero)
    pc = csa_finalize(acc, ones, twos, fours)
    dot = 2 * (pc - (k_packed - k)) - k

    if threshold is not None or has_tvec:
        thr = tvec_ref[...].astype(jnp.int32) if has_tvec else threshold
        bit = dot >= thr
        if pack_out:
            out_ref[...] = pack_bit_planes(bit, valid_f, col0)[None]
        else:
            out_ref[...] = jnp.where(bit, 1, -1).astype(jnp.int32)[None]
    else:
        out_ref[...] = dot.astype(jnp.int32)[None]


@functools.partial(jax.jit, static_argnames=(
    "kh", "kw", "c", "stride", "ho", "wo", "threshold", "pack_out",
    "valid_f", "bf", "interpret"))
def packed_conv2d(xw: jax.Array, ww: jax.Array, *, kh: int, kw: int,
                  c: int, stride: int, ho: int, wo: int,
                  threshold: Optional[int] = None,
                  threshold_vec: Optional[jax.Array] = None,
                  pack_out: bool = False, valid_f: Optional[int] = None,
                  bf: int = 128, interpret: bool = False) -> jax.Array:
    """Direct (im2col-free) binary conv2d on packed words.

    xw: uint32 [N, H_pad, W_pad, C32] — channel-packed activations,
        spatial padding already applied as all-zero words (= -1 pixels);
    ww: uint32 [KH*KW*C32, F] — tap-major packed filters;
    c:  logical channel count (pad-bit correction);
    ho, wo: output spatial extent for this stride/padding.

    Returns int32 [N, HO*WO, F] (signed dot, or {-1,+1} with a
    threshold), or uint32 [N, HO*WO, F/32] with ``pack_out=True`` —
    the caller reshapes to NHWC.  ``bf`` blocks the F axis (clamped by
    csa.col_block to whole lane tiles — of words under pack_out, so a
    packed launch below 4096 channels takes all F in one block); each
    grid step keeps one sample's image VMEM-resident.
    """
    n, h_pad, w_pad, c32 = xw.shape
    taps_words, f = ww.shape
    if taps_words != kh * kw * c32:
        raise ValueError(f"filter has {taps_words} words per output "
                         f"channel, expected KH*KW*C32 = {kh * kw * c32}")
    has_thr = threshold is not None or threshold_vec is not None
    if threshold is not None and threshold_vec is not None:
        raise ValueError("pass either threshold or threshold_vec, not both")
    if pack_out:
        if not has_thr:
            raise ValueError("pack_out requires a threshold "
                             "(binary output to pack)")
        if f % 32:
            raise ValueError(f"pack_out needs F % 32 == 0, got F={f}; "
                             f"pad F (ops.py dispatch does)")
    bf = col_block(f, bf, pack_out)
    valid_f = f if valid_f is None else valid_f
    m = ho * wo

    grid = (n, f // bf)
    if pack_out:
        out_spec = pl.BlockSpec((1, m, bf // 32), lambda i, j: (i, 0, j))
        out_shape = jax.ShapeDtypeStruct((n, m, f // 32), jnp.uint32)
    else:
        out_spec = pl.BlockSpec((1, m, bf), lambda i, j: (i, 0, j))
        out_shape = jax.ShapeDtypeStruct((n, m, f), jnp.int32)
    in_specs = [
        pl.BlockSpec((1, h_pad, w_pad, c32), lambda i, j: (i, 0, 0, 0)),
        pl.BlockSpec((kh * kw * c32, bf), lambda i, j: (0, j)),
    ]
    operands = [xw, ww]
    if threshold_vec is not None:
        in_specs.append(pl.BlockSpec((1, bf), lambda i, j: (0, j)))
        operands.append(threshold_vec.reshape(1, f).astype(jnp.int32))
    return pl.pallas_call(
        functools.partial(_conv_kernel, kh=kh, kw=kw, stride=stride,
                          ho=ho, wo=wo, k=kh * kw * c,
                          k_packed=32 * kh * kw * c32,
                          threshold=threshold,
                          has_tvec=threshold_vec is not None,
                          pack_out=pack_out, valid_f=valid_f, bf=bf),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret,
        name="packed_conv2d",
    )(*operands)


def pad_words_spatial(xw: jax.Array, pad_h: int, pad_w: int) -> jax.Array:
    """Zero-word spatial padding of [N, H, W, C32] — a zero word decodes
    to 32 pixels of -1, the exactly-representable pm1 border."""
    if pad_h == 0 and pad_w == 0:
        return xw
    return jnp.pad(xw, ((0, 0), (pad_h, pad_h), (pad_w, pad_w), (0, 0)))


def im2col_words(xw: jax.Array, kh: int, kw: int, stride: int,
                 ho: int, wo: int) -> jax.Array:
    """Word-granularity im2col: [N, H_pad, W_pad, C32] -> patch matrix
    [N*HO*WO, KH*KW*C32] in the same tap-major word order the direct
    kernel (and the packed filter) uses.

    No unpacking happens — the gather moves whole uint32 words, so the
    patch rows drop straight into popcount_gemm with
    ``k = KH*KW*C`` (the per-tap pad bits sit mid-row rather than at
    the end, but the GEMM's closed form only counts them, so the result
    is identical; the patch matrix is internal and never unpacked).
    This is the fallback path: it materializes the KH*KW-fold input
    re-read in HBM that the direct kernel's resident window avoids.
    """
    n = xw.shape[0]
    cols = []
    for i_kh in range(kh):
        for i_kw in range(kw):
            cols.append(xw[:, i_kh:i_kh + (ho - 1) * stride + 1:stride,
                           i_kw:i_kw + (wo - 1) * stride + 1:stride, :])
    patches = jnp.stack(cols, axis=-2)        # [N, HO, WO, KH*KW, C32]
    return patches.reshape(n * ho * wo, kh * kw * xw.shape[-1])
