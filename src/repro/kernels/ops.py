"""Jit'd public wrappers for the binarized-compute kernels.

Dispatch goes through the backend registry (kernels.packed): a
BackendSpec owns the padding/blocking policy, and the wrappers here
normalize PackedArray operands, flatten leading dims, pad M / N / K to
the spec, run the kernel (or the jnp oracle for "xla"), and slice the
logical result back out.  Block sizes come from the autotuner's cached
tuning table (kernels.autotune) instead of hard-coded tiles.  Both
GEMMs accept legacy raw-uint32 operands for callers that manage their
own layout.

The fully-binary hot path is HBM-minimal: with ``pack_out=True`` the
threshold+bitpack epilogue is fused into the kernel, which emits uint32
sign words directly — the wrapper returns a PackedArray straight from
the kernel and the inter-layer activation never exists in HBM as int32
(the xla oracle stays bit-identical, see tests/test_fused.py).

Backends (see kernels.packed.register_backend):
  "pallas"     real TPU lowering (pl.pallas_call, compiled)
  "interpret"  Pallas interpret mode — kernel body runs on CPU; used by
               the test suite for bit-exact validation vs ref.py
  "xla"        pure-jnp fallback (ref.py) — hosts without Pallas
Default: pallas on TPU, xla elsewhere.
"""
from __future__ import annotations

from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import packed_conv as _pconv
from repro.kernels import ref
from repro.kernels.autotune import best_blocks, best_conv_blocks
from repro.kernels.csa import col_block
from repro.kernels.pack import pack as _pack_kernel
from repro.kernels.packed import (PackedArray, adopt_packed,
                                  default_backend, get_backend, round_up)
from repro.kernels.packed_conv import (conv_vmem_bytes, im2col_words,
                                       out_size, packed_conv2d,
                                       pad_words_spatial)
from repro.kernels.popcount_gemm import popcount_gemm as _pop_kernel
from repro.kernels.xnor_gemm import xnor_gemm as _xnor_kernel

__all__ = ["binarize_pack", "binary_binary_dense", "binary_conv2d",
           "binary_dense", "conv_padding", "default_backend", "mask_rows",
           "plan_conv_launch", "plan_dense_launch"]

Packable = Union[PackedArray, jax.Array]
Threshold = Union[int, float, jax.Array]


def _pad_dim(x: jax.Array, target: int, axis: int) -> jax.Array:
    if x.shape[axis] == target:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - x.shape[axis])
    return jnp.pad(x, pads)


def _adopt_rows(a: Packable, k: Optional[int]) -> PackedArray:
    """Normalize to the row-major packed layout ([..., K/32], axis -1).
    Raw uint32 words go through THE shared adoption/deprecation path
    (kernels.packed.adopt_packed)."""
    if not isinstance(a, PackedArray) and k is None:
        raise ValueError("raw packed words need an explicit k")
    return adopt_packed(a, length=k, axis=-1,
                        context="binary GEMM operand").move_pack_axis_last()


def classify_threshold(threshold: Optional[Threshold], n: int
                       ) -> Tuple[Optional[Union[int, float]],
                                  Optional[jax.Array]]:
    """THE threshold scalar-vs-vector classification (every consumer —
    both GEMM dispatches and the megakernel — must agree, or backends
    drift): python/numpy scalars stay static compile-time constants;
    anything array-like becomes a per-channel [n] vector (0-d arrays
    broadcast — they may be traced, so they cannot be static)."""
    if threshold is None:
        return None, None
    if isinstance(threshold, (int, np.integer)):
        return int(threshold), None
    if isinstance(threshold, (float, np.floating)):
        return float(threshold), None
    arr = jnp.asarray(threshold)
    if arr.ndim == 0:
        arr = jnp.broadcast_to(arr, (n,))
    arr = arr.reshape(-1)
    if arr.shape[0] != n:
        raise ValueError(f"per-channel threshold has {arr.shape[0]} "
                         f"entries for N={n}")
    return None, arr


def _split_threshold(threshold: Optional[Threshold], n: int, np_: int
                     ) -> Tuple[Optional[Union[int, float]],
                                Optional[jax.Array]]:
    """classify_threshold + pad the vector form to the blocked N (pad
    values are masked by valid_n / sliced off)."""
    thr, tvec = classify_threshold(threshold, n)
    return thr, None if tvec is None else _pad_dim(tvec, np_, 0)


def _as_packed_result(words: jax.Array, lead, m: int, n: int
                      ) -> PackedArray:
    """Slice the kernel's padded uint32 output down to the logical rows
    and word count; bits >= n are already zeroed in-kernel (valid_n)."""
    nw = (n + 31) // 32
    return PackedArray(words[:m, :nw].reshape(*lead, nw), length=n,
                       axis=-1)


def mask_rows(x: Packable, valid_m: int) -> Packable:
    """Row-validity masking for bucketed serving: keep only the first
    ``valid_m`` rows of a batch (leading axis), statically.

    This is the M-axis twin of the pack epilogue's ``valid_n`` column
    masking: ``valid_n`` zeroes the pad *bits* a blocked launch would
    otherwise leak into packed words, while ``mask_rows`` drops the pad
    *rows* a bucket-padded batch would otherwise pay GEMM work for.
    ``valid_m`` must be static (it changes the launch shape): the GEMM
    wrappers then re-pad M only to the backend block multiple
    (``pad_m``), so a 33-row request masked to 40 on the 64 bucket
    launches a 40-row grid, not a 64-row one.  Rows are independent
    throughout the datapath, so the kept rows are bit-identical to the
    unmasked dispatch (tests/test_serving.py asserts this).
    """
    if isinstance(x, PackedArray):
        rows = int(x.words.shape[0])
        if not 1 <= valid_m <= rows:
            raise ValueError(f"valid_m must be in [1, {rows}], "
                             f"got {valid_m}")
        if valid_m == rows:
            return x
        return x.with_words(x.words[:valid_m])
    rows = int(np.shape(x)[0])
    if not 1 <= valid_m <= rows:
        raise ValueError(f"valid_m must be in [1, {rows}], got {valid_m}")
    return x if valid_m == rows else x[:valid_m]


def binarize_pack(x: jax.Array,
                  backend: Optional[str] = None) -> PackedArray:
    """sign+pack along the last axis -> PackedArray (length=x.shape[-1]).

    Any length is accepted; the backend pads to its word/block multiple
    and the PackedArray records the logical length."""
    be = get_backend(backend)
    if not be.uses_kernels:
        return PackedArray.pack(x, axis=-1)
    lead, K = x.shape[:-1], x.shape[-1]
    x2 = x.reshape(-1, K)
    M = x2.shape[0]
    Mp, Kp = be.pad_m(M), be.pad_k(K)
    x2 = _pad_dim(_pad_dim(x2, Kp, 1), Mp, 0)
    # slice block padding back off: output words are bit-identical to
    # the canonical packer on every backend
    nw = (K + 31) // 32
    words = _pack_kernel(x2, interpret=be.interpret)[:M, :nw]
    return PackedArray(words.reshape(*lead, nw), length=K, axis=-1)


def binary_dense(x: jax.Array, wp: Packable, alpha: jax.Array,
                 threshold: Optional[Threshold] = None,
                 backend: Optional[str] = None,
                 pack_out: bool = False):
    """Binary-weight dense: x [..., K] float x packed weights -> [.., N].

    wp: PackedArray packed over K in [K, N] orientation (words
    [K/32, N], pack axis -2) or legacy raw uint32 [K/32, N].
    Output is x.dtype; with `threshold` (scalar or per-channel [N]),
    {-1,+1} in x.dtype on every backend (fused in-kernel on pallas,
    post-hoc in the oracle).  With ``pack_out=True`` the binarize+pack
    epilogue is fused too and the result is a PackedArray (length N) —
    the float->binary boundary layer of a fully-binary stack.
    """
    if pack_out and threshold is None:
        raise ValueError("pack_out requires a threshold (binary output)")
    if not isinstance(wp, PackedArray):
        wp = PackedArray(jnp.asarray(wp), length=x.shape[-1], axis=-2)
    if wp.axis != -2:
        raise ValueError(f"binary_dense wants weights packed over K in "
                         f"[K, N] orientation (axis -2), got {wp.axis}")
    if wp.length != x.shape[-1]:
        raise ValueError(f"x K={x.shape[-1]} vs packed K={wp.length}")
    be = get_backend(backend)
    lead, K = x.shape[:-1], x.shape[-1]
    x2 = x.reshape(-1, K)
    M, N = x2.shape[0], wp.words.shape[-1]
    if not be.uses_kernels:
        # pad x with zeros to the word boundary: 0 * (pad weight) == 0
        x2p = _pad_dim(x2, wp.padded_length, 1)
        thr_s, tvec = _split_threshold(threshold, N, N)
        y = ref.xnor_gemm_ref(x2p, wp.words, alpha,
                              thr_s if tvec is None else tvec
                              ).astype(x.dtype)
        y = y.reshape(*lead, N)
        return PackedArray.pack(y, axis=-1) if pack_out else y
    wpad = wp.pad_to(be.pad_k(wp.padded_length))
    Mp, Np = be.pad_m(M), be.pad_n(N)
    x2p = _pad_dim(_pad_dim(x2, wpad.padded_length, 1), Mp, 0)
    words = _pad_dim(wpad.words, Np, 1)
    al = _pad_dim(alpha.reshape(-1), Np, 0)
    thr, tvec = _split_threshold(threshold, N, Np)
    # the fused launch has an extra bn % 32 constraint -> its own key
    op = "xnor_gemm+pack" if pack_out else "xnor_gemm"
    blocks = best_blocks(op, Mp, Np, wpad.n_words, be.name)
    y = _xnor_kernel(x2p, words, al, threshold=thr, threshold_vec=tvec,
                     pack_out=pack_out, valid_n=N,
                     bm=blocks.bm, bn=blocks.bn, bk=blocks.bk_bits,
                     interpret=be.interpret)
    if pack_out:
        return _as_packed_result(y, lead, M, N)
    return y[:M, :N].reshape(*lead, N)


def binary_binary_dense(xp: Packable, wp: Packable, k: Optional[int] = None,
                        threshold: Optional[Threshold] = None,
                        backend: Optional[str] = None,
                        pack_out: bool = False):
    """Fully-binary dense: packed acts x packed weights -> int32 dot.

    xp: PackedArray [..., K] packed on the last axis (or raw uint32
    [..., K/32] with explicit k); wp: PackedArray [N, K] packed on
    the last axis (or raw uint32 [N, K/32]).

    threshold: integer dot threshold, scalar or per-channel int32 [N]
    (the folded-BN form) — the output becomes {-1,+1} int32 on EVERY
    backend (fused in-kernel on pallas/interpret, post-hoc on xla;
    bit-identical, see tests/test_packed.py).

    pack_out: with threshold, emit the {-1,+1} output as a PackedArray
    so the next binary layer consumes it directly.  On kernel backends
    this is FUSED: the final K block of the popcount GEMM shift-ors the
    threshold decisions straight into uint32 words, so the int32 [M, N]
    dot never exists in HBM — a fully-binary MLP chains binarize_pack
    -> binary_binary_dense -> ... at 1 bit/activation end to end.
    """
    if pack_out and threshold is None:
        raise ValueError("pack_out requires a threshold (binary output)")
    xp = _adopt_rows(xp, k)
    wp = _adopt_rows(wp, k)
    if xp.length != wp.length:
        raise ValueError(f"contraction length mismatch: xp K={xp.length} "
                         f"vs wp K={wp.length}")
    k = xp.length
    be = get_backend(backend)
    # align both operands to a common padded K (zero words on both
    # sides cancel via the closed form in the kernel/oracle)
    nbits = 32 * max(xp.n_words, wp.n_words)
    if be.uses_kernels:
        nbits = be.pad_k(nbits)
    xp, wp = xp.pad_to(nbits), wp.pad_to(nbits)
    lead = xp.words.shape[:-1]
    x2 = xp.words.reshape(-1, xp.n_words)
    M, N = x2.shape[0], wp.words.shape[0]
    if be.uses_kernels:
        Mp, Np = be.pad_m(M), be.pad_n(N)
        x2p = _pad_dim(x2, Mp, 0)
        w2p = _pad_dim(wp.words, Np, 0)
        thr, tvec = _split_threshold(threshold, N, Np)
        # the fused launch has an extra bn % 32 constraint -> own key
        op = "popcount_gemm+pack" if pack_out else "popcount_gemm"
        blocks = best_blocks(op, Mp, Np, xp.n_words, be.name)
        y = _pop_kernel(x2p, w2p, k, threshold=thr, threshold_vec=tvec,
                        pack_out=pack_out, valid_n=N,
                        bm=blocks.bm, bn=blocks.bn, bk32=blocks.bk32,
                        interpret=be.interpret)
        if pack_out:
            return _as_packed_result(y, lead, M, N)
        y = y[:M, :N]
    else:
        y = ref.popcount_gemm_ref(x2, wp.words, k)
        if threshold is not None:
            thr_s, tvec = _split_threshold(threshold, N, N)
            # per-channel thresholds carry int32 semantics on every
            # backend (the kernel operand is cast the same way)
            thr = thr_s if tvec is None else tvec.astype(jnp.int32)
            y = jnp.where(y >= thr, 1, -1).astype(jnp.int32)
    y = y.reshape(*lead, N)
    if pack_out:
        return binarize_pack(y, backend=backend)
    return y


def plan_dense_launch(m: int, n: int, k: int, backend: Optional[str] = None,
                      pack_out: bool = False,
                      op: str = "popcount_gemm") -> dict:
    """Static twin of the GEMM dispatch: padded launch geometry + the
    tuning-table key for an [m, k] x [k, n] binary GEMM, without
    touching any operand.  The graph compiler (graph/passes.py) records
    these decisions in the plan and prefetches the key into the tuning
    table.  Non-kernel backends plan under the "pallas" spec — the
    deployment target the plan describes."""
    be = get_backend(backend)
    kb = be if be.uses_kernels else get_backend("pallas")
    nbits = kb.pad_k(round_up(k, 32))
    mp, np_ = kb.pad_m(m), kb.pad_n(n)
    opk = op + "+pack" if pack_out else op
    blocks = best_blocks(opk, mp, np_, nbits // 32, kb.name)
    return {"op": opk, "backend": kb.name, "mp": mp, "np": np_,
            "k32": nbits // 32, "blocks": blocks,
            "key": (opk, kb.name, mp, np_, nbits // 32)}


def plan_conv_launch(h: int, w: int, c: int, f: int, kh: int, kw: int,
                     stride: int = 1, padding: Union[str, int] = "same",
                     backend: Optional[str] = None, pack_out: bool = False,
                     impl: str = "auto", c32: Optional[int] = None,
                     vmem_budget: Optional[int] = None,
                     nb: int = 1) -> dict:
    """Static twin of the binary_conv2d dispatch decisions: output
    geometry, the direct-vs-im2col choice via the VMEM-residency
    estimate, and the tuning key of the launch that actually runs.
    binary_conv2d routes its own ``impl="auto"`` decision through here,
    so the compiled plan (graph/passes.py) can never drift from what
    dispatch actually does.  A direct launch keys under
    ``packed_conv[+pack]``; an im2col launch (explicit or
    auto-resolved) re-keys under ``popcount_gemm[+pack]`` with the
    flattened patch-matrix shape (M = nb*HO*WO rows — pass ``nb`` for
    a batch-accurate key), exactly as binary_binary_dense will at
    trace time."""
    be = get_backend(backend)
    kb = be if be.uses_kernels else get_backend("pallas")
    pad_h, pad_w = conv_padding(padding, kh, kw)
    ho = out_size(h, kh, stride, pad_h)
    wo = out_size(w, kw, stride, pad_w)
    if c32 is None:
        c32 = (c + 31) // 32
    fp = kb.pad_n(f)
    d = {"ho": ho, "wo": wo, "pad_h": pad_h, "pad_w": pad_w,
         "c32": c32, "fp": fp, "backend": kb.name, "impl": impl}
    if impl != "im2col":
        op = "packed_conv+pack" if pack_out else "packed_conv"
        blocks = best_conv_blocks(op, ho, wo, fp, kh * kw * c32, kb.name)
        # estimate with the bf the kernel will actually launch with
        # (the same clamp as packed_conv2d)
        bf_run = col_block(fp, blocks.bn, pack_out)
        budget = (_pconv.VMEM_BUDGET_BYTES if vmem_budget is None
                  else vmem_budget)
        vmem = conv_vmem_bytes(h + 2 * pad_h, w + 2 * pad_w, c32, kh, kw,
                               ho * wo, bf_run)
        if impl == "auto":
            # image/planes can't sit resident -> im2col
            impl = "im2col" if vmem > budget else "direct"
        d.update(impl=impl, op=op, blocks=blocks, vmem_bytes=vmem,
                 vmem_budget=budget,
                 key=(op, kb.name, ho * wo, fp, kh * kw * c32))
    if impl == "im2col":
        # the fallback is a plain GEMM over the word-granularity patch
        # matrix: per-tap pad bits sit mid-row, so the contraction is
        # 32*KH*KW*C32 bits, not round_up(KH*KW*C, 32)
        g = plan_dense_launch(nb * ho * wo, f, 32 * kh * kw * c32,
                              backend=kb.name, pack_out=pack_out)
        d.update(impl="im2col", op=g["op"], blocks=g["blocks"],
                 key=g["key"])
    return d


def conv_padding(padding: Union[str, int], kh: int, kw: int
                 ) -> Tuple[int, int]:
    """Symmetric per-side spatial pad: "same" (odd kernels; preserves
    H/W at stride 1), "valid", or an explicit int."""
    if padding == "same":
        return (kh - 1) // 2, (kw - 1) // 2
    if padding == "valid":
        return 0, 0
    if isinstance(padding, (int, np.integer)):
        return int(padding), int(padding)
    raise ValueError(f"padding must be 'same', 'valid', or an int, "
                     f"got {padding!r}")


def binary_conv2d(xp: PackedArray, wf: PackedArray, stride: int = 1,
                  padding: Union[str, int] = "same",
                  threshold: Optional[Threshold] = None,
                  backend: Optional[str] = None,
                  pack_out: bool = False, impl: str = "auto"):
    """Fully-binary conv2d: channel-packed NHWC acts x packed filters.

    xp: PackedArray [N, H, W, C] packed on the channel axis (-1);
    wf: PackedArray [KH, KW, C, F] packed on the channel axis (-2).
    Spatial padding is -1 padding (all-zero words — the only border a
    pm1 bit code represents exactly; DESIGN.md SS7).

    threshold: integer dot threshold, scalar or per-channel int32 [F]
    (the folded-BN form) — output becomes {-1,+1} int32 on EVERY
    backend.  pack_out: with threshold, emit the activations as a
    channel-packed PackedArray [N, HO, WO, F] so the next binary conv
    consumes them directly; on kernel backends this is FUSED (the
    int32 NHWC activation never exists in HBM).

    impl: "direct" (im2col-free sliding window, one VMEM-resident image
    per grid step), "im2col" (word-granularity patch matrix through
    popcount_gemm), or "auto" (default: direct unless the estimated
    resident footprint exceeds the VMEM budget, then im2col — the same
    silent perf fallback fused_mlp uses).  The xla backend runs the
    jnp sign-conv oracle; all paths are bit-identical
    (tests/test_conv.py).
    """
    if pack_out and threshold is None:
        raise ValueError("pack_out requires a threshold (binary output)")
    if impl not in ("auto", "direct", "im2col"):
        raise ValueError(f"impl must be 'auto', 'direct', or 'im2col', "
                         f"got {impl!r}")
    if not isinstance(xp, PackedArray) or not isinstance(wf, PackedArray):
        raise ValueError("binary_conv2d takes PackedArray operands "
                         "(PackedArray.pack acts on axis -1, filters on "
                         "axis 2 of [KH, KW, C, F])")
    if xp.ndim != 4 or xp.axis != -1:
        raise ValueError(f"activations must be [N, H, W, C] packed on "
                         f"the channel axis, got ndim={xp.ndim} "
                         f"axis={xp.axis}")
    if wf.ndim != 4 or wf.axis != -2:
        raise ValueError(f"filters must be [KH, KW, C, F] packed on the "
                         f"channel axis (-2), got ndim={wf.ndim} "
                         f"axis={wf.axis}")
    if xp.length != wf.length:
        raise ValueError(f"channel mismatch: activations C={xp.length} "
                         f"vs filters C={wf.length}")
    c = xp.length
    kh, kw = wf.words.shape[0], wf.words.shape[1]
    f = wf.words.shape[-1]
    nb, h, w = xp.words.shape[0], xp.words.shape[1], xp.words.shape[2]
    pad_h, pad_w = conv_padding(padding, kh, kw)
    ho = out_size(h, kh, stride, pad_h)
    wo = out_size(w, kw, stride, pad_w)
    if ho <= 0 or wo <= 0:
        raise ValueError(f"empty output: {h}x{w} conv {kh}x{kw} "
                         f"stride {stride} pad {pad_h}")
    be = get_backend(backend)

    if not be.uses_kernels:
        x = xp.unpack(jnp.float32)
        wd = wf.unpack(jnp.float32)
        y = ref.sign_conv2d_ref(x, wd, stride=stride, pad=pad_h,
                                pad_w=pad_w)
        if threshold is not None:
            thr_s, tvec = _split_threshold(threshold, f, f)
            thr = thr_s if tvec is None else tvec.astype(jnp.int32)
            y = jnp.where(y >= thr, 1, -1).astype(jnp.int32)
        return PackedArray.pack(y, axis=-1) if pack_out else y

    # align the word counts (odd C: both sides pad to the same C32)
    c32 = max(xp.n_words, wf.n_words)
    xp = xp.pad_to(32 * c32)
    wf = wf.pad_to(32 * c32)
    xw = pad_words_spatial(xp.words, pad_h, pad_w)
    ww = wf.words.reshape(kh * kw * c32, f)    # tap-major word order
    fp = be.pad_n(f)
    ww = _pad_dim(ww, fp, 1)
    thr, tvec = _split_threshold(threshold, f, fp)
    # direct-vs-im2col + tuning key through the shared static planner
    # (the graph compiler records the same decision in its plan)
    d = plan_conv_launch(h, w, c, f, kh, kw, stride=stride,
                         padding=padding, backend=be.name,
                         pack_out=pack_out, impl=impl, c32=c32)
    use_im2col = d["impl"] == "im2col"

    if use_im2col:
        patches = im2col_words(xw, kh, kw, stride, ho, wo)
        # length counts the valid bits; the per-tap pad bits sit mid-row
        # but the GEMM closed form only counts them (packed_conv.py) —
        # this PackedArray is internal and never unpacked
        xp2 = PackedArray(patches, length=kh * kw * c)
        wp2 = PackedArray(ww[:, :f].T, length=kh * kw * c)
        y = binary_binary_dense(xp2, wp2, threshold=threshold,
                                pack_out=pack_out, backend=be.name)
        if pack_out:
            return PackedArray(y.words.reshape(nb, ho, wo, y.n_words),
                               length=f, axis=-1)
        return y.reshape(nb, ho, wo, f)

    y = packed_conv2d(xw, ww, kh=kh, kw=kw, c=c, stride=stride,
                      ho=ho, wo=wo, threshold=thr, threshold_vec=tvec,
                      pack_out=pack_out, valid_f=f, bf=d["blocks"].bn,
                      interpret=be.interpret)
    if pack_out:
        nw = (f + 31) // 32
        return PackedArray(y[:, :, :nw].reshape(nb, ho, wo, nw),
                           length=f, axis=-1)
    return y[:, :, :f].reshape(nb, ho, wo, f)
