"""PackedArray contract tests: round-trip invariants for both value
conventions, odd-K padding, pytree/jit/vmap boundaries, the backend
registry, and the fully-binary packed MLP chain (DESIGN.md §2–§3)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ops import binarize_pack, binary_binary_dense
from repro.kernels.packed import (PM1, ZERO_ONE, BackendSpec, PackedArray,
                                  get_backend, pack_words, register_backend,
                                  tree_nbytes, unpack_words)
from repro.models.layers import dense, pack_dense_params, packed_dense


# ------------------------------------------------------------------ #
# round-trip invariants                                                #
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("k", [32, 64, 50, 97, 288])
def test_roundtrip_pm1_equals_sign(k):
    """pack -> unpack == sign(x) in {-1,+1}, including odd K where the
    pad bits must be sliced back off."""
    rng = np.random.default_rng(k)
    x = rng.normal(size=(7, k)).astype(np.float32)
    pa = PackedArray.pack(jnp.asarray(x), axis=-1)
    assert pa.values == PM1
    assert pa.shape == (7, k)
    assert pa.n_words == -(-k // 32)
    back = pa.unpack(jnp.float32)
    np.testing.assert_array_equal(np.asarray(back), np.where(x > 0, 1, -1))


@pytest.mark.parametrize("k", [32, 50])
def test_roundtrip_01_values(k):
    """The {0,1} convention unpacks to the raw bits."""
    rng = np.random.default_rng(k + 1)
    bits = (rng.random((5, k)) < 0.5).astype(np.float32)
    pa = PackedArray.pack(jnp.asarray(bits), axis=-1, values=ZERO_ONE)
    back = pa.unpack(jnp.int32)
    np.testing.assert_array_equal(np.asarray(back), bits.astype(np.int32))


def test_pack_axis0_matches_legacy_layout():
    """Packing over axis 0 stores words [K/32, N] with pack axis -2."""
    rng = np.random.default_rng(3)
    w = rng.choice([-1.0, 1.0], size=(64, 5)).astype(np.float32)
    pa = PackedArray.pack(jnp.asarray(w), axis=0)
    assert pa.axis == -2 and pa.words.shape == (2, 5)
    np.testing.assert_array_equal(np.asarray(pa.unpack(jnp.float32)), w)
    # words themselves match the canonical raw packer
    np.testing.assert_array_equal(np.asarray(pa.words),
                                  np.asarray(pack_words(jnp.asarray(w),
                                                        axis=0)))


def test_unpack_words_slices_length():
    x = np.ones((2, 40), np.float32)
    words = pack_words(jnp.asarray(x), axis=-1)
    full = unpack_words(words, axis=-1, dtype=jnp.float32)
    assert full.shape == (2, 64)
    cut = unpack_words(words, axis=-1, dtype=jnp.float32, length=40)
    assert cut.shape == (2, 40)
    np.testing.assert_array_equal(np.asarray(cut), x)


# ------------------------------------------------------------------ #
# pytree / jit / vmap boundaries                                       #
# ------------------------------------------------------------------ #
def test_packedarray_survives_jit():
    rng = np.random.default_rng(5)
    x = rng.normal(size=(4, 50)).astype(np.float32)
    pa = PackedArray.pack(jnp.asarray(x))

    @jax.jit
    def f(p):
        return p.pad_to(96)

    out = f(pa)
    assert isinstance(out, PackedArray)
    assert (out.length, out.axis, out.values) == (50, -1, PM1)
    assert out.n_words == 3
    np.testing.assert_array_equal(np.asarray(out.unpack(jnp.float32)),
                                  np.where(x > 0, 1, -1))


def test_packedarray_tree_util_roundtrip():
    pa = PackedArray.pack(jnp.ones((2, 64)), axis=-1)
    leaves, treedef = jax.tree_util.tree_flatten(pa)
    assert len(leaves) == 1 and leaves[0].dtype == jnp.uint32
    back = jax.tree_util.tree_unflatten(treedef, leaves)
    assert isinstance(back, PackedArray)
    assert (back.length, back.axis, back.values) == (64, -1, PM1)
    # tree_map reaches the words leaf, metadata is preserved
    mapped = jax.tree.map(lambda w: w, pa)
    assert isinstance(mapped, PackedArray) and mapped.length == 64
    # path-aware flatten exposes the .words key sharding rules match on
    (path, _), = jax.tree_util.tree_flatten_with_path(pa)[0]
    assert "words" in jax.tree_util.keystr(path)


def test_packedarray_vmap_keeps_axis_valid():
    """A vmap-added leading dim must not shift the pack axis — exactly
    the scan-stacked-parameters case in models.quantize."""
    rng = np.random.default_rng(7)
    stack = rng.normal(size=(3, 64, 8)).astype(np.float32)
    pa = jax.vmap(lambda w: PackedArray.pack(w, axis=0))(jnp.asarray(stack))
    assert pa.words.shape == (3, 2, 8) and pa.axis == -2
    np.testing.assert_array_equal(np.asarray(pa.unpack(jnp.float32)),
                                  np.where(stack > 0, 1, -1))


def test_packedarray_eval_shape():
    abs_w = jax.ShapeDtypeStruct((96, 16), jnp.float32)
    pa = jax.eval_shape(lambda w: PackedArray.pack(w, axis=0), abs_w)
    assert isinstance(pa, PackedArray)
    assert pa.words.shape == (3, 16) and pa.length == 96


def test_tree_nbytes_counts_words():
    tree = {"wp": PackedArray.pack(jnp.ones((4, 64))),
            "alpha": jnp.ones((4,), jnp.float32)}
    assert tree_nbytes(tree) == 4 * 2 * 4 + 4 * 4


# ------------------------------------------------------------------ #
# backend registry                                                     #
# ------------------------------------------------------------------ #
def test_backend_registry():
    assert get_backend("xla").uses_kernels is False
    assert get_backend("interpret").interpret is True
    assert get_backend("pallas").m_align == 128
    with pytest.raises(ValueError, match="unknown backend"):
        get_backend("cuda")
    spec = register_backend(BackendSpec("xla_test", uses_kernels=False,
                                        interpret=False))
    assert get_backend("xla_test") is spec
    # padding policy: K pads to a word below k_align, k_align above
    be = get_backend("interpret")
    # (k_align is 128 words: a word-axis block is whole lane tiles)
    assert be.pad_k(50) == 64 and be.pad_k(4096) == 4096
    assert be.pad_k(544) == 544 and be.pad_k(4128) == 8192
    assert be.pad_m(37) == 128 and be.pad_n(200) == 256


# ------------------------------------------------------------------ #
# the fully-binary packed MLP chain (acceptance criterion)             #
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("backend", ["xla", "interpret"])
def test_fully_binary_mlp_stays_packed(backend):
    """3-layer binary MLP: binarize_pack -> binary_binary_dense(+pack)
    -> ... -> final int32 dot.  Activations remain PackedArray between
    layers (never unpacked to bf16) and the result equals the dense
    sign-network oracle bit-for-bit."""
    rng = np.random.default_rng(42)
    D, H, O, B = 96, 80, 10, 6
    x = rng.normal(size=(B, D)).astype(np.float32)
    Ws = [rng.normal(size=(H, D)).astype(np.float32),
          rng.normal(size=(H, H)).astype(np.float32),
          rng.normal(size=(O, H)).astype(np.float32)]
    Wp = [PackedArray.pack(jnp.asarray(w), axis=-1) for w in Ws]

    hp = binarize_pack(jnp.asarray(x), backend=backend)
    for wp in Wp[:-1]:
        hp = binary_binary_dense(hp, wp, threshold=0, pack_out=True,
                                 backend=backend)
        assert isinstance(hp, PackedArray), "activation left packed form"
    logits = binary_binary_dense(hp, Wp[-1], backend=backend)
    assert logits.dtype == jnp.int32

    h = np.where(x > 0, 1.0, -1.0)
    for w in Ws[:-1]:
        h = np.where(h @ np.where(w > 0, 1.0, -1.0).T >= 0, 1.0, -1.0)
    want = (h @ np.where(Ws[-1] > 0, 1.0, -1.0).T).astype(np.int32)
    np.testing.assert_array_equal(np.asarray(logits), want)


def test_model_layer_packed_chain():
    """The model-layer surface: pack_dense_params -> packed_dense hidden
    layers -> dense() consuming the PackedArray for the final float
    projection, vs the same math run dense."""
    rng = np.random.default_rng(8)
    D, H, O, B = 64, 96, 12, 5
    x = jnp.asarray(rng.normal(size=(B, D)).astype(np.float32))
    p1 = pack_dense_params(
        {"w": jnp.asarray(rng.normal(size=(D, H)).astype(np.float32))})
    p2 = pack_dense_params(
        {"w": jnp.asarray(rng.normal(size=(H, O)).astype(np.float32))})
    assert isinstance(p1["wp"], PackedArray)

    hp = binarize_pack(x)                      # [B, D] packed
    hp = packed_dense(p1, hp, threshold=0)     # [B, H] still packed
    assert isinstance(hp, PackedArray)
    y = dense(p2, hp)                          # final: int dot * alpha

    xs = np.where(np.asarray(x) > 0, 1.0, -1.0)
    w1 = np.asarray(p1["wp"].unpack(jnp.float32))
    w2 = np.asarray(p2["wp"].unpack(jnp.float32))
    h = np.where(xs @ w1 >= 0, 1.0, -1.0)
    want = (h @ w2) * np.asarray(p2["alpha"])
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-5, atol=1e-5)
