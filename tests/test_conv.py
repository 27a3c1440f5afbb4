"""Packed binary conv2d datapath (ISSUE 3).

Pins (1) bit-exactness of the direct (im2col-free) Pallas conv, the
word-level im2col fallback and the int8 MXU conv of int32 sums against
the jnp sign-conv oracle across backends, over odd C/F, stride-2, 1x1
and valid-padding edge cases; (2) the
fused threshold->pack conv path materializing no int32 NHWC
intermediate (jaxpr regression); (3) OR-max-pooling on packed words;
(4) the conv folded-BN -> per-channel-threshold rewrite; (5) geometry
inference from the paper's Workload dims and the BinaryNet CIFAR-10
topology running end to end from workloads.binarynet_cifar10()."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.jaxpr_audit import eqn_shapes, iter_eqns
from repro.core.bnn_layers import (binary_conv, binary_weight_conv,
                                   fold_bn_threshold,
                                   fold_conv_to_channel_thresholds,
                                   maxpool_packed)
from repro.core.workloads import alexnet_imagenet, binarynet_cifar10
from repro.kernels import ref
from repro.kernels.ops import binary_conv2d, plan_conv_launch
from repro.kernels.packed import PackedArray, pack_words
from repro.models.layers import (infer_conv_geometry, infer_pool,
                                 packed_cnn_apply, packed_cnn_init,
                                 packed_cnn_traffic)


def _pm1(rng, *shape):
    return rng.choice([-1.0, 1.0], size=shape).astype(np.float32)


def _pack_io(rng, nb, h, w, c, f, k):
    x = _pm1(rng, nb, h, w, c)
    wts = _pm1(rng, k, k, c, f)
    return (x, wts, PackedArray.pack(jnp.asarray(x), axis=-1),
            PackedArray.pack(jnp.asarray(wts), axis=2))


# ------------------------------------------------------------------ #
# conv vs the sign-conv oracle, across backends and impls              #
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("nb,h,w,c,f,k,s,pad", [
    (2, 8, 8, 33, 20, 3, 1, "same"),     # odd C and F
    (1, 9, 9, 64, 32, 3, 2, "same"),     # stride 2
    (1, 7, 7, 16, 10, 5, 1, "valid"),    # valid padding, k=5
    (2, 6, 6, 3, 40, 3, 1, "same"),      # C < 32 (single partial word)
    (2, 8, 8, 32, 32, 3, 1, "same"),     # one whole word
    (2, 6, 6, 64, 128, 1, 1, "same"),    # 1x1, ReActNet's doubling
])
@pytest.mark.parametrize("impl", ["direct", "im2col", "mxu"])
def test_conv_bit_exact_vs_oracle(nb, h, w, c, f, k, s, pad, impl):
    rng = np.random.default_rng(nb * 11 + c * 3 + f + k + s)
    x, wts, xp, wf = _pack_io(rng, nb, h, w, c, f, k)
    y_i = binary_conv2d(xp, wf, stride=s, padding=pad,
                        backend="interpret", impl=impl)
    y_x = binary_conv2d(xp, wf, stride=s, padding=pad, backend="xla")
    np.testing.assert_array_equal(np.asarray(y_i), np.asarray(y_x))
    # and against the dense sign conv computed independently in numpy
    p = (k - 1) // 2 if pad == "same" else 0
    xp_np = np.pad(x, ((0, 0), (p, p), (p, p), (0, 0)),
                   constant_values=-1.0)
    ho, wo = y_x.shape[1], y_x.shape[2]
    want = np.zeros((nb, ho, wo, f), np.int32)
    for i in range(ho):
        for j in range(wo):
            win = xp_np[:, i * s:i * s + k, j * s:j * s + k, :]
            want[:, i, j, :] = np.tensordot(
                win, wts, axes=([1, 2, 3], [0, 1, 2])).astype(np.int32)
    np.testing.assert_array_equal(np.asarray(y_x), want)


@pytest.mark.parametrize("thr", ["scalar", "vector"])
@pytest.mark.parametrize("impl", ["direct", "im2col"])
def test_conv_pack_out_bit_exact(thr, impl):
    """Fused threshold->pack conv: identical uint32 words (incl. zeroed
    pad bits) on every backend/impl, odd C and F."""
    rng = np.random.default_rng(77)
    nb, h, w, c, f, k = 2, 6, 6, 50, 33, 3
    x, wts, xp, wf = _pack_io(rng, nb, h, w, c, f, k)
    t = 2 if thr == "scalar" else jnp.asarray(
        rng.integers(-4, 4, size=f).astype(np.int32))
    p_i = binary_conv2d(xp, wf, threshold=t, pack_out=True,
                        backend="interpret", impl=impl)
    p_x = binary_conv2d(xp, wf, threshold=t, pack_out=True, backend="xla")
    assert isinstance(p_i, PackedArray) and p_i.length == f
    assert p_i.words.dtype == jnp.uint32
    np.testing.assert_array_equal(np.asarray(p_i.words),
                                  np.asarray(p_x.words))
    # equals packing the thresholded unfused dot
    y = binary_conv2d(xp, wf, backend="xla")
    tnp = 2 if thr == "scalar" else np.asarray(t)
    dec = np.where(np.asarray(y) >= tnp, 1.0, -1.0)
    want = pack_words(jnp.asarray(dec), axis=-1)
    np.testing.assert_array_equal(np.asarray(p_i.words), np.asarray(want))


def test_conv_non_square_kernel_same_pad():
    """kh != kw with "same" padding: pad_h and pad_w differ, and the
    oracle must honor both (regression: the xla path once dropped
    pad_w)."""
    rng = np.random.default_rng(23)
    nb, h, w, c, f = 1, 5, 6, 32, 32
    x = _pm1(rng, nb, h, w, c)
    wts = _pm1(rng, 1, 3, c, f)                  # kh=1, kw=3
    xp = PackedArray.pack(jnp.asarray(x), axis=-1)
    wf = PackedArray.pack(jnp.asarray(wts), axis=2)
    y_x = binary_conv2d(xp, wf, backend="xla")
    y_i = binary_conv2d(xp, wf, backend="interpret", impl="direct")
    assert y_x.shape == (nb, h, w, f)            # same-pad preserves H, W
    np.testing.assert_array_equal(np.asarray(y_x), np.asarray(y_i))


def test_conv_auto_falls_back_to_im2col(monkeypatch):
    """impl="auto" must route a thresholded conv to the im2col path
    when the direct kernel's estimated footprint exceeds the VMEM
    budget — and stay bit-identical."""
    from repro.kernels import packed_conv

    rng = np.random.default_rng(31)
    _, _, xp, wf = _pack_io(rng, 1, 6, 6, 32, 32, 3)
    want = binary_conv2d(xp, wf, threshold=0, backend="xla")
    auto = binary_conv2d(xp, wf, threshold=0, backend="interpret",
                         impl="auto")
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(want))

    monkeypatch.setattr(packed_conv, "VMEM_BUDGET_BYTES", 0)
    fell_back = binary_conv2d(xp, wf, threshold=0, backend="interpret",
                              impl="auto")
    np.testing.assert_array_equal(np.asarray(fell_back), np.asarray(want))
    # routing check: with budget 0 the jaxpr contains the im2col patch
    # matrix; with the real budget it does not
    m, k32 = 36, 9                       # 6x6 out, 3*3*1 words
    def shapes(fn):
        return eqn_shapes(fn, xp, wf, dtype=jnp.uint32)
    assert (m, k32) in shapes(
        lambda a, b: binary_conv2d(a, b, threshold=0, backend="interpret",
                                   impl="auto"))
    monkeypatch.undo()
    assert (m, k32) not in shapes(
        lambda a, b: binary_conv2d(a, b, threshold=0, backend="interpret",
                                   impl="auto"))


def _eqns(fn, *args):
    return list(iter_eqns(jax.make_jaxpr(fn)(*args).jaxpr))


@pytest.mark.parametrize("k,prim", [(3, "conv_general_dilated"),
                                    (1, "dot_general")])
def test_auto_int32_sums_take_the_mxu(k, prim):
    """With no threshold, impl="auto" runs one XLA convolution (a dot
    for a 1x1) of int8 +-1 values summing in int32, and no Pallas
    kernel; a thresholded conv keeps its Pallas launch."""
    rng = np.random.default_rng(12)
    _, _, xp, wf = _pack_io(rng, 2, 6, 6, 40, 24, k)
    eqns = _eqns(lambda a, b: binary_conv2d(a, b, backend="interpret"),
                 xp, wf)
    (conv,) = [e for e in eqns if e.primitive.name == prim]
    assert [v.aval.dtype for v in conv.invars] == [jnp.int8, jnp.int8]
    assert conv.outvars[0].aval.dtype == jnp.int32
    assert not [e for e in eqns if e.primitive.name == "pallas_call"]
    thr = _eqns(lambda a, b: binary_conv2d(a, b, threshold=0,
                                           backend="interpret"), xp, wf)
    assert [e for e in thr if e.primitive.name == "pallas_call"]


@pytest.mark.parametrize("pack_out", [False, True])
@pytest.mark.parametrize("backend", ["interpret", "xla"])
def test_mxu_conv_refuses_a_threshold(backend, pack_out):
    """impl="mxu" gives int32 sums: a threshold, with or without the
    packed output, is refused, in dispatch and in the planner."""
    rng = np.random.default_rng(13)
    _, _, xp, wf = _pack_io(rng, 1, 5, 5, 32, 32, 3)
    with pytest.raises(ValueError, match="mxu"):
        binary_conv2d(xp, wf, threshold=0, pack_out=pack_out,
                      backend=backend, impl="mxu")
    with pytest.raises(ValueError, match="mxu"):
        plan_conv_launch(5, 5, 32, 32, 3, 3, backend=backend,
                         pack_out=pack_out, thresholded=True, impl="mxu")


def test_conv_validates_operands():
    rng = np.random.default_rng(0)
    _, _, xp, wf = _pack_io(rng, 1, 5, 5, 32, 32, 3)
    with pytest.raises(ValueError, match="pack_out requires a threshold"):
        binary_conv2d(xp, wf, pack_out=True, backend="xla")
    with pytest.raises(ValueError, match="channel mismatch"):
        bad = PackedArray.pack(jnp.asarray(_pm1(rng, 3, 3, 64, 32)), axis=2)
        binary_conv2d(xp, bad, backend="xla")
    with pytest.raises(ValueError, match="impl"):
        binary_conv2d(xp, wf, impl="winograd", backend="xla")
    with pytest.raises(ValueError, match="packed on the channel axis"):
        binary_conv2d(PackedArray.pack(jnp.asarray(_pm1(rng, 4, 32))),
                      wf, backend="xla")


# ------------------------------------------------------------------ #
# jaxpr regression: no int32 NHWC intermediate on the fused path       #
# (walker lives in repro.analysis.jaxpr_audit — THE shared detector)   #
# ------------------------------------------------------------------ #
def _int32_avals(fn, *args):
    return eqn_shapes(fn, *args, dtype=jnp.int32)


def test_fused_conv_has_no_int32_nhwc_intermediate():
    """With pack_out=True the int32 activation — NHWC, flattened, or
    F-padded — must not exist anywhere in the jaxpr; per-sample VMEM
    blocks inside the kernel are the only int32 planes allowed."""
    rng = np.random.default_rng(5)
    nb, h, w, c, f, k = 2, 6, 6, 40, 40, 3
    _, _, xp, wf = _pack_io(rng, nb, h, w, c, f, k)
    m = h * w                                   # stride 1, same pad

    banned = {(nb, h, w, f), (nb, m, f), (nb * m, f),
              (nb, h, w, 128), (nb, m, 128), (nb * m, 128)}
    fused = _int32_avals(
        lambda a, b: binary_conv2d(a, b, threshold=0, pack_out=True,
                                   backend="interpret").words, xp, wf)
    assert not (fused & banned), f"int32 {fused & banned} in fused conv"

    # detector sanity: the unfused conv DOES materialize it
    unfused = _int32_avals(
        lambda a, b: binary_conv2d(a, b, threshold=0,
                                   backend="interpret"), xp, wf)
    assert unfused & banned, unfused


# ------------------------------------------------------------------ #
# OR-max-pool on packed words                                          #
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("win,stride,h", [(2, 2, 8), (3, 2, 9), (2, 1, 5)])
def test_maxpool_packed_equals_dense_max(win, stride, h):
    rng = np.random.default_rng(win * 10 + h)
    c = 45                                       # odd: pad bits in play
    x = _pm1(rng, 2, h, h, c)
    xp = PackedArray.pack(jnp.asarray(x), axis=-1)
    got = maxpool_packed(xp, win, stride)
    want = jax.lax.reduce_window(
        jnp.asarray(x), -jnp.inf, jax.lax.max, (1, win, win, 1),
        (1, stride, stride, 1), "VALID")
    np.testing.assert_array_equal(np.asarray(got.unpack(jnp.float32)),
                                  np.asarray(want))
    # pad bits stay zero (PackedArray contract survives the OR)
    pad_mask = ~np.uint32(0) << np.uint32(c % 32)
    assert not np.any(np.asarray(got.words)[..., -1] & pad_mask)


def test_maxpool_packed_validates():
    rng = np.random.default_rng(1)
    xp = PackedArray.pack(jnp.asarray(_pm1(rng, 1, 2, 2, 32)), axis=-1)
    with pytest.raises(ValueError, match="empties"):
        maxpool_packed(xp, window=3)
    flat = PackedArray.pack(jnp.asarray(_pm1(rng, 4, 32)))
    with pytest.raises(ValueError, match="N, H, W, C"):
        maxpool_packed(flat)


# ------------------------------------------------------------------ #
# folded BN -> per-channel conv threshold                              #
# ------------------------------------------------------------------ #
def test_fold_conv_thresholds_match_bn_reference():
    """Flip absorption on conv filters: rewritten words + T' = 1 - T
    reproduce sign(BN(conv)) exactly, gamma<0 channels included, and
    the flipped words keep pad bits zero."""
    rng = np.random.default_rng(9)
    nb, h, w, c, f, k = 2, 5, 5, 40, 24, 3
    x, wts, xp, wf = _pack_io(rng, nb, h, w, c, f, k)
    gamma = rng.normal(size=f)
    mu, sigma = rng.normal(size=f), rng.uniform(0.5, 2.0, size=f)
    beta = rng.normal(size=f)
    fold = fold_bn_threshold(mu, sigma, gamma, beta, k * k * c, eps=0.0)
    assert bool(np.asarray(fold.flip).any()), "need gamma<0 channels"

    wf2, tvec = fold_conv_to_channel_thresholds(wf, fold)
    got = binary_conv2d(xp, wf2, threshold=tvec, backend="interpret")

    s = np.asarray(ref.sign_conv2d_ref(jnp.asarray(x), jnp.asarray(wts),
                                       stride=1, pad=1))
    sd = np.sqrt(sigma ** 2)
    bn = gamma * (s - mu) / sd + beta
    want = np.where(bn >= 0, 1, -1).astype(np.int32)
    np.testing.assert_array_equal(np.asarray(got), want)
    pad_mask = ~np.uint32(0) << np.uint32(c % 32)
    assert not np.any(np.asarray(wf2.words)[:, :, -1, :]
                      & pad_mask[..., None])


def test_binary_conv_accepts_foldedthreshold():
    rng = np.random.default_rng(14)
    _, _, xp, wf = _pack_io(rng, 1, 5, 5, 32, 16, 3)
    f = 16
    fold = fold_bn_threshold(rng.normal(size=f), rng.uniform(0.5, 2, f),
                             rng.normal(size=f), rng.normal(size=f),
                             9 * 32, eps=0.0)
    a = binary_conv(xp, wf, fold, backend="interpret")
    wf2, tvec = fold_conv_to_channel_thresholds(wf, fold)
    b = binary_conv2d(xp, wf2, threshold=tvec, backend="interpret")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------------------ #
# workload geometry + the BinaryNet CIFAR-10 topology                  #
# ------------------------------------------------------------------ #
def test_conv_geometry_recovered_from_paper_tables():
    bn = binarynet_cifar10()
    assert [infer_conv_geometry(c) for c in bn.conv] == [(1, 1)] * 6
    al = alexnet_imagenet()
    geo = [infer_conv_geometry(c) for c in al.conv]
    assert geo == [(4, 0), (1, 2), (1, 1), (1, 1), (1, 1)]
    assert infer_pool(32, 16) == (2, 2)          # BinaryNet
    assert infer_pool(55, 27) == (3, 2)          # AlexNet pool1
    assert infer_pool(13, 6) == (3, 2)           # AlexNet pool5
    assert infer_pool(16, 16) is None
    with pytest.raises(ValueError, match="max-pool"):
        infer_pool(16, 5)


def test_binarynet_cifar10_forward():
    """The paper's headline workload, end to end from the Workload
    dataclass: 6 packed binary convs (first integer), OR-pools, packed
    FC tail, logits out — on the oracle backend (interpret would take
    minutes; the kernel paths are covered above on small shapes)."""
    wl = binarynet_cifar10()
    params = packed_cnn_init(jax.random.PRNGKey(0), wl)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 32, 32, 3),
                          jnp.float32)
    logits = packed_cnn_apply(params, x, wl, backend="xla")
    assert logits.shape == (1, 10)
    assert logits.dtype == jnp.float32
    # integer dot of the 1024-wide fc3: bounded and non-degenerate
    assert np.all(np.abs(np.asarray(logits)) <= 1024)
    assert np.asarray(logits).std() > 0

    tr = packed_cnn_traffic(wl, batch=1)
    assert 10 < tr["ratio_bf16_over_packed"] <= 16
    assert len(tr["layers"]) == 9


def test_binary_weight_conv_first_layer():
    """Integer first layer: float input x alpha*sign(w), real
    zero-padding — matches the dense reference."""
    rng = np.random.default_rng(3)
    x = rng.normal(size=(2, 6, 6, 3)).astype(np.float32)
    w = rng.normal(size=(3, 3, 3, 8)).astype(np.float32)
    y = binary_weight_conv(jnp.asarray(x), jnp.asarray(w))
    alpha = np.mean(np.abs(w), axis=(0, 1, 2))
    xp = np.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    want = np.zeros((2, 6, 6, 8), np.float32)
    wb = np.where(w > 0, 1.0, -1.0)
    for i in range(6):
        for j in range(6):
            want[:, i, j, :] = np.tensordot(
                xp[:, i:i + 3, j:j + 3, :], wb,
                axes=([1, 2, 3], [0, 1, 2]))
    np.testing.assert_allclose(np.asarray(y), want * alpha, rtol=1e-5)
