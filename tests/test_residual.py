"""The float residual domain (graph/ir.py ResidualEntry / ResidualBlock
/ PoolFC, kernels/residual.py), on a tiny ReActNet-shaped spec: 32x32
input, widths 32 -> 64 -> 128 -> 128, so a stride-1 doubling block, a
stride-2 doubling block and a stride-1 block of one width.

* compiled and served through BNNServer on the xla and interpret
  backends against a plain float32 reference of the block equations
  on seeded random weights: the float stream bit-identical, the logits
  within the benchmark configuration's row gap;
* the zero-border correction exact for every border class at strides
  1 and 2, even and odd maps, direct, im2col and mxu, against a conv
  of zero-padded +-1 values;
* the whole forward the same bits under the MXU plan and the pinned
  im2col plan;
* the ``residual_epilogue`` kernel bit-identical to its jnp twin;
* the validator: residual specs accepted, a width mismatch, a residual
  block fed packed input and other misuse rejected;
* the BinaryNet and AlexNet plans as they were before the residual
  domain existed; the plan, launch count, traffic and spans of a
  residual spec.
"""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import graph
from repro.core.workloads import alexnet_imagenet, binarynet_cifar10
from repro.graph import (Binarize, BinaryConv, BNNSpec, BNThreshold,
                         IntegerEntry, PoolFC, ResidualBlock, ResidualEntry)
from repro.graph.ir import reactnet_a_imagenet, residual_net
from repro.kernels import ops as kops
from repro.kernels import residual as R
from repro.kernels.packed import PackedArray
from repro.kernels.packed_conv import conv_vmem_bytes
from repro.runtime import spans
from repro.serving import BNNServer

HI = jax.lax.Precision.HIGHEST
ROW_GAP = 1e-3        # bench/configs/reactnet-a-imagenet.json compare.row_gap


def _tiny():
    return residual_net("tiny-reactnet", (32, 32, 3), 32,
                        ((64, 1), (128, 2), (128, 1)), 10)


def _images(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, (n, 32, 32, 3)).astype(np.float32)


# ------------------------------------------------------------------ #
# the plain reference                                                  #
# ------------------------------------------------------------------ #
def _sign(v):
    return jnp.where(v > 0, 1.0, -1.0)


def _conv(a, w, stride, pad):
    return jax.lax.conv_general_dilated(
        a, w, (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI)


def _reference(spec, params, x):
    """(stream before the head, logits) of the block equations, float32
    jnp at highest precision, the binary weights unpacked to +-1."""
    stem = params["conv"][0]
    h = _conv(jnp.asarray(x), _sign(stem["w"]), 2, 1) * stem["alpha"] \
        + stem["beta"]
    for nd, p in zip(spec.residual_blocks, params["res"]):
        a = _sign(h + p["rsign1"])
        y1 = _conv(a, p["w1"].unpack(jnp.float32), nd.stride, 1) \
            * p["bn1_scale"] + p["bn1_bias"]
        if nd.stride == 2:
            sc = ((h[:, 0::2, 0::2] + h[:, 0::2, 1::2])
                  + (h[:, 1::2, 0::2] + h[:, 1::2, 1::2])) * 0.25
        else:
            sc = h
        v = (y1 + sc) + p["move12"]
        u = jnp.where(v > 0, v, p["prelu1"] * v) + p["move13"]
        b = _sign(u + p["rsign2"])
        y2 = _conv(b, p["w2"].unpack(jnp.float32), 1, 0) * p["bn2_scale"] \
            + p["bn2_bias"]
        v = (y2 + jnp.concatenate([u] * (nd.c_out // nd.c_in), -1)) \
            + p["move22"]
        h = jnp.where(v > 0, v, p["prelu2"] * v) + p["move23"]
    head = params["head"][0]
    return h, jnp.dot(jnp.mean(h, axis=(1, 2)), head["w"],
                      precision=HI) + head["b"]


def _stream(cb, params, x):
    """The program's float stream after its last residual epilogue."""
    h = jnp.asarray(x)
    for step in cb.plan[:-1]:
        h = cb._apply_step(step, params, h)
    return h[0]


@pytest.mark.parametrize("backend", ["xla", "interpret"])
def test_served_matches_reference(backend):
    spec = _tiny()
    cb = graph.compile(spec, backend=backend, batch=8)
    params = cb.init(jax.random.PRNGKey(16))
    x = _images(8)
    want_stream, want = _reference(spec, params, x)
    got_stream = jax.jit(lambda p, v: _stream(cb, p, v))(params, x)
    np.testing.assert_array_equal(np.asarray(got_stream).view(np.uint32),
                                  np.asarray(want_stream).view(np.uint32))
    srv = BNNServer(cb, params, max_batch=8, fallback_backend=None).start()
    try:
        got = np.asarray(srv.submit(x[:5]).result())
    finally:
        srv.stop()
    assert got.shape == (5, 10)
    assert np.abs(got - np.asarray(want)[:5]).max() <= ROW_GAP


def test_backends_bit_identical():
    """The whole forward is the same bits on xla and interpret."""
    spec = _tiny()
    x = _images(4, seed=1)
    outs = []
    for backend in ("xla", "interpret"):
        cb = graph.compile(spec, backend=backend, batch=4)
        params = cb.init(jax.random.PRNGKey(3))
        outs.append(np.asarray(jax.jit(cb.apply)(params, x)))
    np.testing.assert_array_equal(outs[0], outs[1])


def test_mxu_plan_matches_im2col_plan():
    """The auto plan's int8 MXU convs and the pinned im2col plan's
    popcount GEMMs give the same logits, bit for bit."""
    spec = _tiny()
    x = _images(4, seed=4)
    auto = graph.compile(spec, backend="interpret", batch=4)
    pinned = graph.compile(spec, backend="interpret", batch=4,
                           conv_impl="im2col")
    assert {s.args["impl"] for s in auto.plan
            if s.kind == "residual_conv"} == {"mxu"}
    params = auto.init(jax.random.PRNGKey(6))
    got = np.asarray(jax.jit(auto.apply)(params, x))
    want = np.asarray(jax.jit(pinned.apply)(params, x))
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))


def test_bind_prepares_border_tables():
    """``bind`` computes each block's zero-border table once from its
    packed w1; the forward reads that table (zeroing it changes the
    answer); a spec without residual blocks keeps its tree."""
    spec = _tiny()
    cb = graph.compile(spec, backend="xla", batch=4)
    params = cb.init(jax.random.PRNGKey(5))
    for nd, p in zip(spec.residual_blocks, params["res"]):
        want, _ = R.border_table(p["w1"], nd.h_in, nd.w_in, nd.stride, 1)
        np.testing.assert_array_equal(p["border"], want)
    x = _images(4, seed=2)
    out = np.asarray(cb.apply(params, x))
    np.testing.assert_array_equal(np.asarray(cb.apply(cb.bind(params), x)),
                                  out)
    zeroed = dict(params, res=[dict(p, border=jnp.zeros_like(p["border"]))
                               for p in params["res"]])
    assert not np.array_equal(np.asarray(cb.apply(zeroed, x)), out)
    plain = graph.compile(binarynet_cifar10(), backend="xla", batch=2)
    pp = plain.init(jax.random.PRNGKey(0))
    assert plain.bind(pp) is pp


# ------------------------------------------------------------------ #
# the zero-border correction                                           #
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("backend,impl", [("xla", "auto"),
                                          ("interpret", "direct"),
                                          ("interpret", "im2col"),
                                          ("interpret", "mxu")])
@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("h,w", [(6, 6), (7, 5), (1, 4), (2, 3)])
def test_zero_border_correction(backend, impl, stride, h, w):
    rng = np.random.default_rng(h * 10 + w + stride)
    x = _sign(jnp.asarray(rng.standard_normal((2, h, w, 40)), jnp.float32))
    wt = jnp.asarray(rng.standard_normal((3, 3, 40, 64)), jnp.float32)
    xp, wf = PackedArray.pack(x, axis=-1), PackedArray.pack(wt, axis=2)
    s = kops.binary_conv2d(xp, wf, stride=stride, padding=1,
                           backend=backend, impl=impl)
    table, rows = R.border_table(wf, h, w, stride, 1)
    got = s + table[np.asarray(rows)][None]
    want = _conv(x, _sign(wt), stride, 1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n,stride,classes", [
    (6, 1, (1, 0, 0, 0, 0, 2)),       # even, stride 1: first and last
    (7, 2, (1, 0, 0, 2)),             # odd, stride 2: the last leaves
    (6, 2, (1, 0, 0)),                # even, stride 2: it does not
    (1, 1, (1,)),                     # one row: both borders at once
])
def test_border_classes(n, stride, classes):
    rows, cols, rsets, _ = R.border_classes(n, n, 3, stride, 1)
    assert rows == cols == classes
    assert rsets[0] == frozenset() and rsets[1] >= {0}


# ------------------------------------------------------------------ #
# the epilogue kernel                                                  #
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("hw,c,groups,stride,border,pack", [
    ((8, 8), 32, 1, 1, True, True),
    ((9, 7), 64, 1, 2, True, True),
    ((6, 6), 32, 2, 1, False, True),
    ((4, 4), 64, 2, 1, False, False),
    ((7, 7), 128, 1, 1, True, False),
])
def test_epilogue_kernel_matches_twin(hw, c, groups, stride, border, pack):
    h, w = hw
    ks = jax.random.split(jax.random.PRNGKey(c + h), 4)
    table = rows = None
    if border:
        ho, wo = (h - 1) // stride + 1, (w - 1) // stride + 1
        wf = PackedArray.pack(jax.random.normal(ks[0], (3, 3, c, c)), axis=2)
        table, rows = R.border_table(wf, h, w, stride, 1)
    else:
        ho, wo = h, w
    s = jax.random.randint(ks[1], (2, ho, wo, groups * c), -300, 300,
                           jnp.int32)
    sc = jax.random.normal(ks[2], (2, ho, wo, c))
    vec = jax.random.normal(ks[3], (6, groups * c))
    # BN scales are bfloat16 values: the product with a sum is exact
    vec = vec.at[0].set(vec[0].astype(jnp.bfloat16).astype(jnp.float32))
    got = R.residual_epilogue(s, sc, vec, table, rows, pack=pack,
                              interpret=True)
    want = R.residual_epilogue_ref(s, sc, vec, table, rows, pack=pack)
    np.testing.assert_array_equal(np.asarray(got[0]).view(np.uint32),
                                  np.asarray(want[0]).view(np.uint32))
    if pack:
        np.testing.assert_array_equal(np.asarray(got[1].words),
                                      np.asarray(want[1].words))
        assert got[1].length == groups * c
    else:
        assert got[1] is None and want[1] is None


def test_epilogue_rejects_bad_operands():
    s = jnp.zeros((1, 4, 4, 64), jnp.int32)
    with pytest.raises(ValueError, match="does not tile"):
        R.residual_epilogue_ref(s, jnp.zeros((1, 4, 4, 48)),
                                jnp.zeros((6, 64)))
    with pytest.raises(ValueError, match="int32"):
        R.residual_epilogue_ref(s.astype(jnp.float32),
                                jnp.zeros((1, 4, 4, 64)), jnp.zeros((6, 64)))
    with pytest.raises(ValueError, match="row classes"):
        R.residual_epilogue_ref(s, jnp.zeros((1, 4, 4, 64)),
                                jnp.zeros((6, 64)),
                                table=jnp.zeros((2, 4, 64), jnp.int32))


# ------------------------------------------------------------------ #
# the IR                                                               #
# ------------------------------------------------------------------ #
def test_validator_accepts_residual_specs():
    for spec in (_tiny(), reactnet_a_imagenet()):
        spec.validate()
    full = reactnet_a_imagenet()
    assert len(full.residual_blocks) == 13
    assert [b.stride for b in full.residual_blocks].count(2) == 4
    assert full.nodes[-1] == PoolFC("fc", 1024, 1000)


def _stem(c=32, hw=16):
    return [IntegerEntry("stem", 3, 3, 3, c, 2 * hw, 2 * hw, hw, hw,
                         stride=2, pad=1), ResidualEntry("stem.bn", c)]


@pytest.mark.parametrize("nodes,match", [
    (_stem() + [ResidualBlock("b", 64, 64, 16, 16), PoolFC("fc", 64, 10)],
     "expects 16x16x64, the stream is 16x16x32"),
    (_stem() + [ResidualBlock("b", 32, 96, 16, 16), PoolFC("fc", 96, 10)],
     "doubles it"),
    (_stem(hw=15) + [ResidualBlock("b", 32, 64, 15, 15, 2),
                     PoolFC("fc", 64, 10)], "even map"),
    (_stem() + [ResidualBlock("b", 32, 32, 16, 16), PoolFC("fc", 64, 10)],
     "n_in=64 but the stream is 32 wide"),
    (_stem() + [ResidualBlock("b", 32, 32, 16, 16)],
     "left through a terminal PoolFC"),
    (_stem() + [PoolFC("fc", 32, 10), PoolFC("fc2", 10, 10)],
     "terminal node"),
    (_stem() + [BinaryConv("c", 3, 3, 32, 32, 16, 16, 16, 16, 1, 1),
                BNThreshold("c.bn", 32)],
     "can only be read by ResidualBlock"),
    (_stem() + [Binarize("bin")], "can only be read by ResidualBlock"),
    ([IntegerEntry("stem", 3, 3, 3, 32, 32, 32, 16, 16, stride=2, pad=1),
      Binarize("bin"), ResidualBlock("b", 32, 32, 16, 16),
      PoolFC("fc", 32, 10)],
     "a residual block reads the float residual stream, not packed_conv"),
    ([IntegerEntry("stem", 3, 3, 3, 32, 32, 32, 16, 16, stride=2, pad=1),
      ResidualEntry("stem.bn", 64)], "64 channels for a 32-wide"),
    ([ResidualEntry("stem.bn", 3)], "directly after an IntegerEntry"),
    ([IntegerEntry("stem", 3, 3, 3, 32, 32, 32, 16, 16, stride=2, pad=1),
      PoolFC("fc", 32, 10)], "PoolFC reads the float residual stream"),
])
def test_validator_rejects(nodes, match):
    first = nodes[0]
    shape = (first.h_in, first.w_in, 3) if isinstance(first, IntegerEntry) \
        else (32, 32, 3)
    with pytest.raises(ValueError, match=match):
        BNNSpec("bad", shape, tuple(nodes)).validate()


def test_spec_to_workload_lists_block_convs():
    wl = graph.spec_to_workload(_tiny())
    assert [c.name for c in wl.conv] == [
        "stem", "block1.conv3x3", "block1.conv1x1", "block2.conv3x3",
        "block2.conv1x1", "block3.conv3x3", "block3.conv1x1"]
    assert [(c.z1, c.z2, c.x1, c.x2, c.k) for c in wl.conv[3:5]] == [
        (64, 64, 16, 8, 3), (64, 128, 8, 8, 1)]
    assert [(f.name, f.n_in, f.n_out, f.integer) for f in wl.fc] == [
        ("fc", 128, 10, True)]
    assert len(graph.compile(_tiny()).tulip_mapping()) == 8


# ------------------------------------------------------------------ #
# the plan                                                             #
# ------------------------------------------------------------------ #
# sha256 of describe() and the launch count as they were before the
# residual domain: the packed-only plans must not move
PLANS = {
    ("binarynet", 128, "auto"):
        ("3f7c59974790884b664ba5836385b11b23fb694ec0293751ea03ec5964304bf2", 8),
    ("alexnet", 64, "im2col"):
        ("2270375c215b61ad1c87c7e0702baaac2615700735a47d551a0ae749e0475326", 6),
    ("alexnet", 64, "auto"):
        ("c78eed7454c3c2f118e5f16fb446a128b7bcc19a03e8dce4573ec1922c964137", 6),
    ("binarynet", 8, "im2col"):
        ("9a445dee452c69d441ca826809c4a2ad15e56bf7f48205e23c770877b67404bc", 8),
}


@pytest.mark.parametrize("key", list(PLANS))
def test_packed_plans_unchanged(key):
    name, batch, impl = key
    wl = binarynet_cifar10() if name == "binarynet" else alexnet_imagenet()
    cb = graph.compile(wl, backend="pallas", batch=batch, conv_impl=impl)
    digest, launches = PLANS[key]
    assert hashlib.sha256(cb.describe().encode()).hexdigest() == digest
    assert cb.launch_count() == launches


def test_reactnet_plan():
    cb = graph.compile(reactnet_a_imagenet(), backend="pallas", batch=64,
                       conv_impl="im2col")
    kinds = [s.kind for s in cb.plan]
    assert kinds[:2] == ["integer_conv", "residual_entry"]
    assert kinds[2:-1] == ["residual_conv", "residual_epilogue"] * 26
    assert kinds[-1] == "pool_fc"
    assert cb.launch_count() == 1 + 4 * 13
    assert all(s.args["impl"] == "im2col" for s in cb.plan
               if s.kind == "residual_conv")
    ep = [s for s in cb.plan if s.kind == "residual_epilogue"]
    assert [s.args["pool"] for s in ep].count(True) == 4
    assert [s.args["next"] for s in ep][-2:] == [True, False]
    # every residual conv resolves a tuning key at another batch
    assert len(cb.tuning_keys_for_batch(8)) == len(cb.tuning_keys) == 26


def test_auto_conv_impl_takes_im2col_at_stride_2():
    """The direct kernel's window is a strided gather above stride 1,
    which the TPU cannot lower: auto dispatch of a thresholded conv
    takes im2col there.  The shape is one whose stride-1 direct form
    compiles for v5e."""
    d1 = kops.plan_conv_launch(16, 16, 128, 128, 3, 3, stride=1,
                               padding=1, backend="pallas", nb=8,
                               thresholded=True)
    d2 = kops.plan_conv_launch(16, 16, 128, 128, 3, 3, stride=2,
                               padding=1, backend="pallas", nb=8,
                               thresholded=True)
    assert d1["impl"] == "direct" and d2["impl"] == "im2col"


def test_auto_int32_conv_estimate_bounds_the_compiler():
    """A direct conv of int32 sums is judged by conv_int32_vmem_bytes,
    which bounds the v5e compiler's scoped-VMEM asks from above: at
    every stride-1 3x3 shape of ReActNet-A the forced direct kernel
    (which asks 32-52 MB there) is estimated over the budget, where
    auto puts the conv on the MXU; a small map's estimate fits, and
    the packed form keeps its estimate."""
    for hw, c, asked_mb in ((112, 32, 41.61), (56, 128, 48.28),
                            (28, 256, 42.34), (14, 512, 31.98),
                            (7, 1024, 48.32)):
        auto = kops.plan_conv_launch(hw, hw, c, c, 3, 3, stride=1,
                                     padding=1, backend="pallas", nb=64)
        assert auto["impl"] == "mxu" and "vmem_bytes" not in auto
        d = kops.plan_conv_launch(hw, hw, c, c, 3, 3, stride=1, padding=1,
                                  backend="pallas", nb=64, impl="direct")
        assert d["impl"] == "direct"
        assert d["vmem_bytes"] >= asked_mb * 2 ** 20
        assert d["vmem_bytes"] > d["vmem_budget"]
    small = kops.plan_conv_launch(8, 8, 1024, 1024, 3, 3, stride=1,
                                  padding=1, backend="pallas", nb=64,
                                  impl="direct")
    assert small["impl"] == "direct"
    assert 5.37 * 2 ** 20 <= small["vmem_bytes"] <= small["vmem_budget"]
    packed = kops.plan_conv_launch(32, 32, 128, 128, 3, 3, stride=1,
                                   padding=1, backend="pallas",
                                   pack_out=True, nb=64)
    assert packed["impl"] == "direct"
    assert packed["vmem_bytes"] == conv_vmem_bytes(34, 34, 4, 3, 3, 1024,
                                                   128)


def test_reactnet_auto_plan_is_the_im2col_plan():
    """The builder's default plan (conv_impl="auto") puts all 26
    residual convs on the MXU: no tuning keys, 27 launches (the stem's
    pack and 26 epilogues); the pinned im2col plan still compiles, with
    53."""
    auto = graph.compile(reactnet_a_imagenet(), backend="pallas", batch=64)
    pinned = graph.compile(reactnet_a_imagenet(), backend="pallas",
                           batch=64, conv_impl="im2col")
    convs = [s for s in auto.plan if s.kind == "residual_conv"]
    assert len(convs) == 26
    assert all(s.args["impl"] == "mxu" and not s.keys for s in convs)
    assert all("impl=mxu (int32 sums: XLA int8 conv)" in s.detail
               for s in convs)
    assert auto.tuning_keys == auto.tuning_keys_for_batch(8) == ()
    assert auto.launch_count() == 27
    assert pinned.launch_count() == 53 and len(pinned.tuning_keys) == 26


def test_residual_traffic():
    cb = graph.compile(_tiny(), backend="xla", batch=4)
    layers = {d["name"]: d for d in cb.traffic(4)["layers"]}
    # block2.epilogue2: 8x8 positions of 4 images, 128 channels of sums
    # and stream, the 64-wide u, [6, 128] rows, packed signs
    px = 4 * 8 * 8
    assert layers["block2.epilogue2"]["packed_bytes"] == \
        4 * px * 128 * 2 + 4 * px * 64 + 4 * 6 * 128 + px * 128 // 8
    # block2.epilogue1 adds the 2-class border table [2, 8, 64]
    assert layers["block2.epilogue1"]["packed_bytes"] == \
        4 * px * 64 * 3 + 4 * 6 * 64 + 4 * 2 * 8 * 64 + px * 64 // 8
    assert "block2.shortcut" in layers and "block1.shortcut" not in layers
    assert layers["block3.epilogue2"]["packed_bytes"] == \
        4 * px * 128 * 3 + 4 * 6 * 128      # the last: no signs packed


def test_compile_records_residual_spans():
    spans.enable()
    try:
        graph.compile(_tiny(), backend="xla", batch=4)
    finally:
        spans.disable()
    got = [s for s in spans.collect() if s[0] == "setup.residual"]
    assert [s[5]["block"] for s in got] == ["block1", "block2", "block3"]
    assert all(s[5]["impl3"] == s[5]["impl1"] == "mxu" for s in got)
    assert all(s[2] >= s[1] for s in got)
