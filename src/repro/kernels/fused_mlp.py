"""Pallas TPU megakernel: a whole fully-binary MLP in one pallas_call.

The TULIP-PE schedule (paper §V) never lets an intermediate activation
leave the processing element: the threshold neuron's 1-bit output feeds
the next operation in place.  This kernel is the TPU analogue — the
grid runs over M only, and for each row block the packed activations
ping-pong between two VMEM scratch buffers across consecutive binary
layers, while per-layer weights sit VMEM-resident (constant index map).
Between layers nothing touches HBM: layer l's threshold decisions are
shift-or'd into uint32 words in registers (kernels/csa.py) and written
to scratch, which layer l+1 reads as its packed K operand.  Only the
first-layer input and last-layer output cross the HBM boundary, at
1 bit/value.

Per layer the inner product runs the same Harley-Seal carry-save
popcount as popcount_gemm, but over the layer's full K at once (static
unroll — layer widths are compile-time constants), so no CSA residue
scratch is needed.  Pad-bit correctness is inductive: layer inputs have
zero pad bits (the PackedArray contract for the entry input; the
valid_n mask for every scratch interface), weight pad words are zero,
and the closed form dot = 2*(pc - (K_padded - K)) - K cancels the rest.

Inputs/outputs: `fused_binary_mlp` takes a PackedArray [..., K0] (or
raw uint32 words + explicit k), per-layer [N_l, K_l] PackedArray
weights chained K_l == N_{l-1}, and one threshold per layer (static
scalar, or per-channel int32 [N_l] — the folded-BN form from
core.bnn_layers.fold_to_channel_thresholds); it returns the last
layer's activations as a PackedArray [..., N_L].

Invariants / failure modes:
* every layer MUST have a threshold — without one the intermediate
  would be int32 and could not stay packed in scratch (ValueError);
* chain-width mismatches and weight/threshold count mismatches raise
  ValueError before anything is traced;
* scalar-vs-vector threshold classification is ops.classify_threshold,
  shared with the chained fallback and both GEMM dispatches — the one
  rule that keeps backends from drifting on 0-d/numpy spellings;
* pad-bit correctness is inductive (entry input and every scratch
  interface have zero pad bits; the §3 closed form cancels the rest),
  so the megakernel's words are bit-identical to chaining
  binary_binary_dense(pack_out=True), which is itself bit-identical to
  the xla oracle (tests/test_fused.py);
* dispatch estimates the resident footprint (_vmem_bytes) and falls
  back to the layer-by-layer fused chain when the stack exceeds
  VMEM_BUDGET_BYTES — a *silent* perf fallback, never a correctness
  change — and always chains on "xla", the oracle backend.

Unlike popcount_gemm, no CSA residue scratch is needed here: each
layer's K is folded in full inside one grid step (the historical
[bm, bn, bk32]-cube layout never existed in this kernel).
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.autotune import best_blocks
from repro.kernels.csa import (csa_finalize, csa_fold, pack_bit_planes,
                               row_block)
from repro.kernels.ops import binary_binary_dense, classify_threshold
from repro.kernels.packed import (VMEM_BUDGET_BYTES, PackedArray,
                                  get_backend)

LayerThreshold = Union[int, jax.Array]


def _layer_dot(h, w_ref, k_logical: int):
    """CSA popcount inner product for one resident layer.

    h: [bm, kw] uint32 packed activations (in registers/scratch);
    w_ref: [n_p, kw] uint32 resident weight block.  Returns the signed
    int32 dot [bm, n_p] over the k_logical valid bits."""
    wpt = w_ref[...].T                              # [kw, n_p]
    kw = wpt.shape[0]
    n_p = wpt.shape[1]
    bm = h.shape[0]
    zero = jnp.zeros((bm, n_p), jnp.uint32)
    planes = (~(h[:, t:t + 1] ^ wpt[t:t + 1, :]) for t in range(kw))
    acc, ones, twos, fours = csa_fold(
        planes, jnp.zeros((bm, n_p), jnp.int32), zero, zero, zero)
    pc = csa_finalize(acc, ones, twos, fours)
    return 2 * (pc - (32 * kw - k_logical)) - k_logical


def _kernel(x_ref, *refs, meta):
    """meta: (w_kw, w_np, k_logical, valid_n, thr_static, has_tvec) per
    layer + (n_layers, n_tvecs, out_words).  Buffers: the last two refs
    are the ping-pong scratch; before them the output ref; weights then
    threshold vectors lead."""
    layers, out_words = meta
    n_layers = len(layers)
    n_tvecs = sum(1 for L in layers if L["has_tvec"])
    w_refs = refs[:n_layers]
    tvec_refs = refs[n_layers:n_layers + n_tvecs]
    out_ref = refs[n_layers + n_tvecs]
    bufs = refs[n_layers + n_tvecs + 1:]

    bufs[0][:, :x_ref.shape[1]] = x_ref[...]
    tv = 0
    for li, L in enumerate(layers):
        src, dst = bufs[li % 2], bufs[(li + 1) % 2]
        h = src[:, :L["kw"]]
        dot = _layer_dot(h, w_refs[li], L["k_logical"])
        if L["has_tvec"]:
            thr = tvec_refs[tv][...].astype(jnp.int32)
            tv += 1
        else:
            thr = L["thr"]
        words = pack_bit_planes(dot >= thr, L["valid_n"], 0)
        dst[:, :words.shape[1]] = words
    out_ref[...] = bufs[n_layers % 2][:, :out_words]


@functools.lru_cache(maxsize=None)
def _build_call(meta_key) -> callable:
    """Build (and cache) the jitted pallas_call for one static stack
    configuration.  meta_key: (mp, bm, w0, layers, interpret) with
    layers a tuple of (kw, n_p, k_logical, valid_n, thr_or_None,
    has_tvec)."""
    mp, bm, w0, layer_key, interpret = meta_key
    layers = [dict(kw=kw, n_p=n_p, k_logical=kl, valid_n=vn, thr=thr,
                   has_tvec=tvec)
              for (kw, n_p, kl, vn, thr, tvec) in layer_key]
    out_np = layers[-1]["n_p"]
    out_words = out_np // 32
    buf_words = max([w0] + [L["n_p"] // 32 for L in layers])

    in_specs = [pl.BlockSpec((bm, w0), lambda i: (i, 0))]
    for L in layers:
        kw, n_p = L["kw"], L["n_p"]
        in_specs.append(
            pl.BlockSpec((n_p, kw), lambda i: (0, 0)))
    for L in layers:
        if L["has_tvec"]:
            in_specs.append(
                pl.BlockSpec((1, L["n_p"]), lambda i: (0, 0)))

    call = pl.pallas_call(
        functools.partial(_kernel, meta=(layers, out_words)),
        grid=(mp // bm,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, out_words), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((mp, out_np // 32), jnp.uint32),
        scratch_shapes=[pltpu.VMEM((bm, buf_words), jnp.uint32),
                        pltpu.VMEM((bm, buf_words), jnp.uint32)],
        interpret=interpret,
        name="fused_mlp",
    )
    return jax.jit(lambda *ops: call(*ops))


def _vmem_bytes(bm: int, w0: int, shapes) -> int:
    """Rough resident footprint: weights + tvecs + ping-pong buffers +
    the per-layer CSA working set (4 int32/uint32 planes of the widest
    layer)."""
    weights = sum(n_p * kw * 4 for (kw, n_p, _, _, _, has_tvec) in shapes)
    tvecs = sum(4 * n_p for (_, n_p, _, _, _, has_tvec) in shapes
                if has_tvec)
    buf_words = max([w0] + [n_p // 32 for (_, n_p, _, _, _, _) in shapes])
    planes = 5 * bm * max(n_p for (_, n_p, _, _, _, _) in shapes) * 4
    return weights + tvecs + 2 * bm * buf_words * 4 + planes


def stack_plan(m: int, k0: int, ns: Sequence[int],
               has_tvec: Sequence[bool], backend: Optional[str] = None,
               budget: Optional[int] = None,
               w0: Optional[int] = None) -> dict:
    """Static geometry + residency decision for one fused-stack launch.

    THE megakernel-vs-chained rule: ``fused_binary_mlp`` routes its own
    fallback through this (so does the graph compiler's dense-run
    segmentation pass, which is how the plan can never disagree with
    what dispatch does at trace time).  ``m`` rows of a ``k0``-bit
    input through layers of widths ``ns``; ``has_tvec[l]`` marks
    per-channel (vector) thresholds, which cost extra resident bytes.
    Non-kernel backends plan under the "pallas" spec (the deployment
    target).  Returns mp/bm/w0, the per-layer geometry tuples
    ``(kw, n_p, k_logical, n, None, has_tvec)``, the footprint
    estimate, whether it fits the budget, and the fused_mlp tuning key.
    """
    be = get_backend(backend)
    kb = be if be.uses_kernels else get_backend("pallas")
    if w0 is None:
        w0 = (k0 + 31) // 32
    geom = []
    kw, k_logical = w0, k0
    for n, tv in zip(ns, has_tvec):
        n_p = kb.pad_n(n)
        geom.append((kw, n_p, k_logical, n, None, bool(tv)))
        kw, k_logical = n_p // 32, n
    mp = kb.pad_m(m)
    n_max = max(g[1] for g in geom)
    # clamp the tuned bm to a divisor of the padded M like every other
    # kernel — a stale table entry must not drop grid steps — and keep
    # the widest layer's [bm, n_max] planes within csa.PLANE_WORDS
    bm = row_block(mp, best_blocks("fused_mlp", mp, n_max, w0,
                                   kb.name).bm, n_max)
    vmem = _vmem_bytes(bm, w0, geom)
    budget = VMEM_BUDGET_BYTES if budget is None else budget
    return {"mp": mp, "bm": bm, "w0": w0, "geom": tuple(geom),
            "vmem_bytes": vmem, "fits": vmem <= budget,
            "key": ("fused_mlp", kb.name, mp, n_max, w0)}


def fused_binary_mlp(xp: Union[PackedArray, jax.Array],
                     weights: Sequence[PackedArray],
                     thresholds: Sequence[LayerThreshold],
                     k: Optional[int] = None,
                     backend: Optional[str] = None,
                     vmem_budget: Optional[int] = None) -> PackedArray:
    """Run a stack of fully-binary thresholded dense layers fused.

    xp: PackedArray [..., K0] packed on the last axis (or raw uint32
    words with explicit ``k``); weights[l]: PackedArray [N_l, K_l]
    packed on the last axis with K_l == N_{l-1} (K_0 == xp.length);
    thresholds[l]: static int or per-channel int32 [N_l] (folded-BN
    form, see core.bnn_layers.fold_to_channel_thresholds).

    Returns the last layer's activations as a PackedArray [..., N_L] —
    bit-identical to chaining binary_binary_dense(pack_out=True), but
    on kernel backends the whole stack runs in ONE pallas_call with
    activations resident in VMEM scratch (the TULIP-PE schedule).
    """
    if len(weights) != len(thresholds):
        raise ValueError(f"{len(weights)} weights vs "
                         f"{len(thresholds)} thresholds")
    if not weights:
        raise ValueError("fused_binary_mlp needs at least one layer")
    if not isinstance(xp, PackedArray):
        if k is None:
            raise ValueError("raw packed words need an explicit k")
        xp = PackedArray(jnp.asarray(xp), length=k, axis=-1)
    else:
        xp = xp.move_pack_axis_last()
    ws = [w.move_pack_axis_last() for w in weights]
    d = xp.length
    ns = []
    for li, w in enumerate(ws):
        if w.length != d:
            raise ValueError(f"layer {li}: weight K={w.length} but the "
                             f"incoming activation width is {d}")
        d = w.words.shape[0]                        # logical N_l
        ns.append(d)

    if any(t is None for t in thresholds):
        raise ValueError("every megakernel layer needs a threshold "
                         "(the output must be binary to stay packed)")
    # ops.classify_threshold is THE scalar-vs-vector rule, shared with
    # the chained fallback so backends cannot disagree; vectors carry
    # the kernel operand's int32 semantics
    thresholds = [
        thr if tvec is None else tvec.astype(jnp.int32)
        for thr, tvec in (classify_threshold(t, n)
                          for t, n in zip(thresholds, ns))]
    be = get_backend(backend)

    def chained() -> PackedArray:
        h = xp
        for w, t in zip(ws, thresholds):
            h = binary_binary_dense(h, w, threshold=t, pack_out=True,
                                    backend=be.name)
        return h

    if not be.uses_kernels:
        return chained()

    # ---- static stack geometry (shared with the graph compiler) ---- #
    lead = xp.words.shape[:-1]
    x2 = xp.words.reshape(-1, xp.n_words)
    M = x2.shape[0]
    has_tvec = [not isinstance(t, (int, float))      # normalized above
                for t in thresholds]
    sp = stack_plan(M, xp.length, ns, has_tvec, backend=be.name,
                    budget=vmem_budget,
                    w0=max(xp.n_words, ws[0].n_words))
    if not sp["fits"]:
        return chained()              # stack too big to sit resident
    mp, bm, w0 = sp["mp"], sp["bm"], sp["w0"]
    # inject the static scalar thresholds into the geometry tuples
    # (vector thresholds travel as operands instead)
    shapes = [(kw, n_p, kl, n, None if tv else t, tv)
              for (kw, n_p, kl, n, _, tv), t in zip(sp["geom"],
                                                    thresholds)]
    tvec_ops = [jnp.pad(t, (0, n_p - n)).reshape(1, n_p)
                for (_, n_p, _, n, _, tv), t in zip(shapes, thresholds)
                if tv]

    # ---- operands (zero padding everywhere: §3 closed form) --------- #
    x2p = jnp.pad(x2, ((0, mp - M), (0, w0 - x2.shape[1])))
    w_ops = []
    for (kw_l, n_p, _, n, _, _), w in zip(shapes, ws):
        w_ops.append(jnp.pad(w.words, ((0, n_p - w.words.shape[0]),
                                       (0, kw_l - w.words.shape[1]))))

    meta_key = (mp, bm, w0, tuple(shapes), be.interpret)
    words = _build_call(meta_key)(x2p, *w_ops, *tvec_ops)

    n_last = shapes[-1][3]
    nw = (n_last + 31) // 32
    return PackedArray(words[:M, :nw].reshape(*lead, nw),
                       length=n_last, axis=-1)
