"""Tests for the declarative BNN graph IR + compile pipeline (ISSUE 4).

Covers: (1) lowering the paper workloads into the IR and back
(spec_to_workload round trip, tulip_mapping == table3_rows); (2)
GOLDEN bit-exactness — the compiled executable vs a frozen copy of the
legacy layer-by-layer builder chain, for BinaryNet-CIFAR10 and
XNOR-AlexNet on xla (full nets) and for a small spec on interpret
(kernel path); (3) megakernel segmentation boundaries (VMEM-budget
splits, the un-thresholded classifier tail breaking the segment); (4)
the no-int32-NHWC jaxpr regression on the compiled path; (5) traffic
parity, spec validation errors, and the single raw-words deprecation
path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import graph
from repro.core.bnn_layers import (binary_conv, binary_weight_conv,
                                   maxpool_packed, quantize_for_serving)
from repro.core.mapping import table3_rows
from repro.core.workloads import alexnet_imagenet, binarynet_cifar10
from repro.graph import (Binarize, BinaryConv, BinaryDense, BNNSpec,
                         BNThreshold, IntegerEntry, Logits, MaxPool)
from repro.graph.ir import (fc_entry_size, infer_conv_geometry,
                            infer_pool)
from repro.kernels import ops as kops
from repro.kernels.packed import PackedArray


# ------------------------------------------------------------------ #
# the frozen legacy builder chain (pre-compiler golden reference)      #
# ------------------------------------------------------------------ #
def _maxpool_float(x, window, stride):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, window, window, 1),
        (1, stride, stride, 1), "VALID")


def _legacy_cnn_apply(params, x, workload, backend=None, impl="auto"):
    """Verbatim copy of the pre-compiler models.layers.packed_cnn_apply
    body — the golden reference the compiled plan must reproduce bit
    for bit."""
    conv, fc = workload.conv, workload.fc
    h = x
    packed = False
    for i, (l, p) in enumerate(zip(conv, params["conv"])):
        s, pad = infer_conv_geometry(l)
        if l.integer:
            h = binary_weight_conv(h, p["w"], stride=s, padding=pad,
                                   alpha=p["alpha"])
        else:
            if not packed:
                h = kops.binarize_pack(h, backend=backend)
                packed = True
            h = binary_conv(h, p["wf"], fold=p["t"], stride=s,
                            padding=pad, pack_out=True, backend=backend,
                            impl=impl)
        nxt = conv[i + 1].x1 if i + 1 < len(conv) else \
            fc_entry_size(l, fc[0])
        pool = infer_pool(l.x2, nxt)
        if pool is not None:
            h = maxpool_packed(h, *pool) if packed else \
                _maxpool_float(h, *pool)
    if not packed:
        h = kops.binarize_pack(h.reshape(h.shape[0], -1),
                               backend=backend)
    else:
        nb = h.words.shape[0]
        spatial = h.words.shape[1] * h.words.shape[2]
        h = PackedArray(h.words.reshape(nb, -1),
                        length=spatial * h.length, axis=-1)
    for j, (l, p) in enumerate(zip(fc, params["fc"])):
        last = j == len(fc) - 1
        h = kops.binary_binary_dense(h, p["wp"], threshold=p.get("t"),
                                     pack_out=not last, backend=backend)
    return h.astype(jnp.float32)


def _legacy_cnn_init(key, workload, threshold_range=3,
                     dtype=jnp.float32):
    """Verbatim copy of the pre-compiler packed_cnn_init body."""
    ks = jax.random.split(key, len(workload.conv) + len(workload.fc))
    params = {"conv": [], "fc": []}
    for i, l in enumerate(workload.conv):
        w = jax.random.normal(ks[i], (l.k, l.k, l.z1, l.z2), dtype)
        if l.integer:
            alpha = jnp.mean(jnp.abs(w.astype(jnp.float32)),
                             axis=(0, 1, 2))
            params["conv"].append({"w": w, "alpha": alpha})
        else:
            t = jax.random.randint(jax.random.fold_in(ks[i], 1),
                                   (l.z2,), -threshold_range,
                                   threshold_range + 1, jnp.int32)
            params["conv"].append({"wf": PackedArray.pack(w, axis=2),
                                   "t": t})
    for j, l in enumerate(workload.fc):
        kj = ks[len(workload.conv) + j]
        w = jax.random.normal(kj, (l.n_out, l.n_in), dtype)
        p = {"wp": PackedArray.pack(w, axis=-1)}
        if j < len(workload.fc) - 1:
            p["t"] = jax.random.randint(jax.random.fold_in(kj, 1),
                                        (l.n_out,), -threshold_range,
                                        threshold_range + 1, jnp.int32)
        params["fc"].append(p)
    return params


# ------------------------------------------------------------------ #
# lowering                                                             #
# ------------------------------------------------------------------ #
def test_lower_binarynet_spec():
    spec = graph.from_workload(binarynet_cifar10())
    kinds = [type(n).__name__ for n in spec.nodes]
    assert kinds[:4] == ["IntegerEntry", "Binarize", "BinaryConv",
                        "BNThreshold"]
    assert kinds.count("BinaryConv") == 5
    assert kinds.count("MaxPool") == 3
    assert kinds.count("BinaryDense") == 3
    assert kinds[-1] == "Logits"
    convs = [n for n in spec.nodes if isinstance(n, BinaryConv)]
    assert all(c.stride == 1 and c.pad == 1 for c in convs)
    # round trip back to the workload dataclasses
    wl2 = graph.spec_to_workload(spec)
    assert wl2.conv == binarynet_cifar10().conv
    assert wl2.fc == binarynet_cifar10().fc


def test_lower_alexnet_spec():
    spec = graph.from_workload(alexnet_imagenet())
    kinds = [type(n).__name__ for n in spec.nodes]
    assert kinds[:2] != ["IntegerEntry", "Binarize"]  # pool1 between
    assert sum(k == "IntegerEntry" for k in kinds) == 2
    entries = [n for n in spec.nodes if isinstance(n, IntegerEntry)]
    assert (entries[0].stride, entries[0].pad) == (4, 0)
    assert entries[0].parts == 4
    pools = [n for n in spec.nodes if isinstance(n, MaxPool)]
    assert all(p.window == 3 and p.stride == 2 for p in pools)
    assert graph.spec_to_workload(spec).conv == alexnet_imagenet().conv


def test_spec_validation_errors():
    with pytest.raises(ValueError, match="n_in=100"):
        BNNSpec("bad", (64,), (BinaryDense("d0", 100, 32),
                               BNThreshold("t0", 32))).validate()
    with pytest.raises(ValueError, match="must be followed by a "
                                         "BNThreshold"):
        BNNSpec("bad", (8, 8, 32),
                (Binarize("b"),
                 BinaryConv("c", 3, 3, 32, 32, 8, 8, 8, 8, 1, 1),
                 MaxPool("p", 2, 2))).validate()
    with pytest.raises(ValueError, match="not representable"):
        BNNSpec("bad", (8, 8, 32),
                (Binarize("b"),
                 BinaryConv("c", 3, 3, 32, 32, 8, 8, 8, 8, 1, 1),
                 BNThreshold("t", 32),
                 IntegerEntry("i", 3, 3, 32, 32, 8, 8, 8, 8, 1, 1),
                 )).validate()
    with pytest.raises(ValueError, match="terminal"):
        BNNSpec("bad", (64,), (BinaryDense("d0", 64, 32),
                               Logits("l", 32),
                               BinaryDense("d1", 32, 8))).validate()


# ------------------------------------------------------------------ #
# golden bit-exactness: compiled vs the frozen legacy chain            #
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("wl_fn,img", [(binarynet_cifar10, 32),
                                       (alexnet_imagenet, 227)])
def test_compiled_matches_legacy_golden_xla(wl_fn, img):
    """Full paper workloads on the oracle backend: identical params
    from the same key, identical logits word-for-word."""
    wl = wl_fn()
    cb = graph.compile(wl, backend="xla")
    params = cb.init(jax.random.PRNGKey(0))
    legacy_params = _legacy_cnn_init(jax.random.PRNGKey(0), wl)
    la, lb = (jax.tree_util.tree_leaves_with_path(params),
              jax.tree_util.tree_leaves_with_path(legacy_params))
    assert [p for p, _ in la] == [p for p, _ in lb]
    for (_, a), (_, b) in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    x = jax.random.normal(jax.random.PRNGKey(1), (1, img, img,
                                                  wl.conv[0].z1),
                          jnp.float32)
    got = cb.apply(params, x)
    want = _legacy_cnn_apply(legacy_params, x, wl, backend="xla")
    assert got.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _small_spec():
    nodes = (Binarize("b"),
             BinaryConv("c1", 3, 3, 32, 64, 8, 8, 8, 8, 1, 1),
             BNThreshold("c1.bn", 64),
             MaxPool("p1", 2, 2),
             BinaryConv("c2", 3, 3, 64, 32, 4, 4, 4, 4, 1, 1),
             BNThreshold("c2.bn", 32),
             BinaryDense("d1", 4 * 4 * 32, 48),
             BNThreshold("d1.bn", 48),
             BinaryDense("d2", 48, 16),
             Logits("logits", 16))
    return BNNSpec("small", (8, 8, 32), nodes)


def test_compiled_small_spec_interpret_vs_xla():
    """Kernel path (interpret mode) vs the jnp oracle on a hand-built
    spec: packed words and logits bit-identical across backends and
    impl choices."""
    spec = _small_spec()
    params = graph.compile(spec).init(jax.random.PRNGKey(2))
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 8, 8, 32),
                          jnp.float32)
    outs = {}
    for be in ("xla", "interpret"):
        cb = graph.compile(spec, backend=be, batch=2)
        outs[be] = np.asarray(cb.apply(params, x))
    np.testing.assert_array_equal(outs["xla"], outs["interpret"])
    # forced im2col conv lowering is bit-identical too
    cb = graph.compile(spec, backend="interpret", conv_impl="im2col")
    assert all(s.args["impl"] == "im2col" for s in cb.plan
               if s.kind == "binary_conv")
    np.testing.assert_array_equal(np.asarray(cb.apply(params, x)),
                                  outs["xla"])


def test_serve_folded_stack_matches_fold_chain():
    """quantize_for_serving folds through the compiled pipeline ==
    the explicit fold + chained dense reference."""
    rng = np.random.default_rng(7)
    B, D, H = 5, 64, 48
    x = rng.normal(size=(B, D)).astype(np.float32)

    def mk(kin, kout):
        return quantize_for_serving(
            rng.normal(size=(kout, kin)).astype(np.float32),
            rng.normal(size=kout), rng.uniform(0.5, 2.0, size=kout),
            rng.normal(size=kout), rng.normal(size=kout))

    layers = [mk(D, H), mk(H, H)]
    xp = kops.binarize_pack(jnp.asarray(x), backend="xla")
    got = graph.serve_folded_stack(xp, layers, backend="interpret")
    from repro.core.bnn_layers import (bnn_dense_serve_folded,
                                      fold_to_channel_thresholds)
    h = xp
    for wpl, fo in layers:
        w2, tv = fold_to_channel_thresholds(wpl, fo)
        h = kops.binary_binary_dense(h, w2, threshold=tv,
                                     pack_out=True, backend="xla")
    np.testing.assert_array_equal(np.asarray(got.words),
                                  np.asarray(h.words))
    assert bnn_dense_serve_folded is not None  # import sanity


# ------------------------------------------------------------------ #
# megakernel segmentation boundaries                                   #
# ------------------------------------------------------------------ #
def _dense_steps(cb):
    return [s for s in cb.plan if s.kind in ("dense", "fused_stack")]


def test_segmentation_default_budget_fuses_whole_stack():
    cb = graph.compile_dense_stack(2048, [2048] * 4)
    steps = _dense_steps(cb)
    assert [s.kind for s in steps] == ["fused_stack"]
    assert steps[0].args["fc_indices"] == (0, 1, 2, 3)
    assert cb.launch_count() == 1 and cb.legacy_launch_count() == 4


def test_segmentation_budget_splits_stack():
    """A budget that fits 2 resident layers but not 3 splits the run
    into two megakernel segments at the VMEM boundary (2048-wide planes
    cap the row block at 16: ~1.73 MB for 2 layers, ~2.26 MB for 3)."""
    cb = graph.compile_dense_stack(2048, [2048] * 4,
                                   vmem_budget=2_000_000)
    steps = _dense_steps(cb)
    assert [s.kind for s in steps] == ["fused_stack", "fused_stack"]
    assert steps[0].args["fc_indices"] == (0, 1)
    assert steps[1].args["fc_indices"] == (2, 3)


def test_segmentation_budget_too_small_chains_every_layer():
    cb = graph.compile_dense_stack(2048, [2048] * 4,
                                   vmem_budget=1_000_000)
    steps = _dense_steps(cb)
    assert [s.kind for s in steps] == ["dense"] * 4
    assert all("exceeds the VMEM budget" in s.detail for s in steps)
    assert cb.launch_count() == cb.legacy_launch_count() == 4


def test_segmentation_unthresholded_tail_breaks_segment():
    """The classifier head (no threshold -> int32 out) can never join
    a megakernel segment; BinaryNet's plan fuses fc1+fc2 only."""
    cb = graph.compile(binarynet_cifar10())
    steps = _dense_steps(cb)
    assert [s.kind for s in steps] == ["fused_stack", "dense"]
    assert steps[0].args["fc_indices"] == (0, 1)
    assert steps[1].args == {"fc_idx": 2, "thresholded": False,
                             "pack_out": False}
    # segmentation is perf-only: identical bits either way is covered
    # by test_compiled_matches_legacy_golden_xla (legacy never fuses)


def test_segmentation_decision_uses_stack_plan():
    """The compiler's fused/chained decision is the same shared rule
    fused_binary_mlp checks at trace time."""
    from repro.kernels.fused_mlp import stack_plan
    sp = stack_plan(1, 2048, [2048] * 4, [True] * 4, backend=None)
    assert sp["fits"]
    assert not stack_plan(1, 2048, [2048] * 4, [True] * 4,
                          budget=1_000_000)["fits"]
    assert sp["key"][0] == "fused_mlp"
    # scalar thresholds cost no resident tvec bytes, and the plan
    # threads the spec's per_channel flags into the same rule
    sc = stack_plan(1, 2048, [2048] * 4, [False] * 4, backend=None)
    assert sc["vmem_bytes"] < sp["vmem_bytes"]
    cb = graph.compile_dense_stack(2048, [2048] * 4,
                                   per_channel=[False] * 4,
                                   vmem_budget=sc["vmem_bytes"])
    assert [s.kind for s in _dense_steps(cb)] == ["fused_stack"]
    # with vector thresholds the same budget cannot hold all 4 layers
    cb2 = graph.compile_dense_stack(2048, [2048] * 4,
                                    vmem_budget=sc["vmem_bytes"])
    assert [s.kind for s in _dense_steps(cb2)] != ["fused_stack"]


def test_conv_plan_records_the_key_the_launch_consults():
    """A direct conv plan carries a packed_conv key; an im2col plan
    (explicit or auto-fallback) re-keys under popcount_gemm with the
    flattened patch-matrix shape, like binary_binary_dense will."""
    from repro.kernels.ops import plan_conv_launch
    d = plan_conv_launch(8, 8, 32, 64, 3, 3, backend="interpret",
                         pack_out=True, nb=2)
    assert d["impl"] == "direct" and d["key"][0] == "packed_conv+pack"
    i2 = plan_conv_launch(8, 8, 32, 64, 3, 3, backend="interpret",
                          pack_out=True, impl="im2col", nb=2)
    assert i2["key"][0] == "popcount_gemm+pack"
    assert i2["key"][2] == 128          # pad_m(2 * 8 * 8)
    auto = plan_conv_launch(8, 8, 32, 64, 3, 3, backend="interpret",
                            pack_out=True, vmem_budget=0, nb=2)
    assert auto["impl"] == "im2col"
    assert auto["key"] == i2["key"]


def test_compile_vmem_budget_reaches_the_kernel():
    """compile(vmem_budget=...) threads the budget into
    fused_binary_mlp so plan and trace-time residency agree."""
    import repro.kernels.fused_mlp as fm
    seen = []
    orig = fm.stack_plan

    def spy(*a, **k):
        seen.append(k.get("budget"))
        return orig(*a, **k)

    cb = graph.compile_dense_stack(64, [64, 64], vmem_budget=2 ** 26,
                                   backend="xla")
    params = cb.init(jax.random.PRNGKey(0))
    xp = kops.binarize_pack(
        jax.random.normal(jax.random.PRNGKey(1), (2, 64)),
        backend="xla")
    fm.stack_plan = spy
    try:
        # xla chains before geometry; interpret reaches stack_plan
        graph.compile_dense_stack(
            64, [64, 64], vmem_budget=2 ** 26,
            backend="interpret").apply(params, xp)
    finally:
        fm.stack_plan = orig
    assert 2 ** 26 in seen


# ------------------------------------------------------------------ #
# jaxpr regression: no int32 activation in HBM on the compiled path    #
# (the walker + banned-shape derivation live in repro.analysis)        #
# ------------------------------------------------------------------ #
def test_compiled_path_has_no_int32_activation():
    """Compiled small net on the kernel backend, audited: the int32
    NHWC conv activations and the int32 [M, N] dense activation must
    not exist anywhere in the jaxpr (fused threshold->pack epilogues).
    audit() derives the banned set from the plan itself — the shapes
    the legacy unfused chain would write to HBM; in-kernel [bm, bn]
    VMEM blocks (visible because interpret mode inlines the kernel
    body) stay allowed."""
    spec = _small_spec()
    cb = graph.compile(spec, backend="interpret", batch=2)
    params = cb.init(jax.random.PRNGKey(2))
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 8, 8, 32),
                          jnp.float32)
    report = cb.audit(params=params, x=x)
    # the audit's banned set covers the hand-maintained list this test
    # used to carry (conv1/conv2 activations + the d1 dense act)
    assert {(2, 8, 8, 64), (2, 64, 64),                # conv1 act
            (2, 4, 4, 32), (2, 16, 32),                # conv2 act
            (2, 48)} <= report.banned_shapes
    # detector sanity: the logits head's int32 dot IS materialized
    assert (2, 16) in report.int32_shapes
    assert (2, 16) not in report.banned_shapes


# ------------------------------------------------------------------ #
# traffic + TULIP mapping from the same spec                           #
# ------------------------------------------------------------------ #
def test_traffic_matches_legacy_math():
    wl = binarynet_cifar10()
    tr = graph.compile(wl).traffic(batch=1)
    assert len(tr["layers"]) == 9
    assert 10 < tr["ratio_bf16_over_packed"] <= 16
    # spot-check the byte math against hand computation (conv2, fc2)
    conv2 = next(d for d in tr["layers"] if d["name"] == "conv2")
    n_in, n_w = 32 * 32 * 128, 3 * 3 * 128 * 128
    assert conv2["packed_bytes"] == n_in // 8 + n_w // 8
    assert conv2["bf16_bytes"] == 2 * n_in + 2 * n_w
    fc2 = next(d for d in tr["layers"] if d["name"] == "fc2")
    assert fc2["packed_bytes"] == 1024 // 8 + 1024 * 1024 // 8


def test_tulip_mapping_reproduces_table3():
    """One spec, two targets: the same compiled artifact that executes
    on TPU reproduces the paper's Table III P/Z numbers through
    core/mapping.py."""
    for wl_fn in (binarynet_cifar10, alexnet_imagenet):
        cb = graph.compile(wl_fn())
        assert cb.table3_rows() == table3_rows(wl_fn())
        rows = cb.tulip_mapping()
        convs = [r for r in rows if r["kind"] == "conv"]
        assert len(convs) == len(wl_fn().conv)
        for r in convs:
            if r["mapping"].uses_pe:
                assert r["cmp_cycles"] and r["cmp_cycles"] > 0
        pools = [r for r in rows if r["kind"] == "pool"]
        assert all(p["pool_cycles"] > 0 for p in pools)


def test_describe_is_human_readable():
    text = graph.compile(binarynet_cifar10()).describe()
    for needle in ("megakernel", "impl=direct", "threshold->pack",
                   "bitwise OR", "kernel launches"):
        assert needle in text, f"{needle!r} missing from plan"


# ------------------------------------------------------------------ #
# the single raw-words deprecation path                                #
# ------------------------------------------------------------------ #
def test_raw_words_adoption_warns_once():
    from repro.kernels.packed import _RAW_WORDS_WARNED, adopt_packed
    _RAW_WORDS_WARNED.discard("test ctx")
    raw = jnp.zeros((2, 2), jnp.uint32)
    with pytest.warns(DeprecationWarning, match="raw uint32 words"):
        pa = adopt_packed(raw, length=64, axis=-1, context="test ctx")
    assert pa.length == 64
    # second adoption under the same context is silent
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error")
        adopt_packed(raw, length=64, axis=-1, context="test ctx")
    # PackedArray passes through, with the length cross-check
    with pytest.raises(ValueError, match="disagrees"):
        adopt_packed(PackedArray(raw, length=33), length=64,
                     context="test ctx")
