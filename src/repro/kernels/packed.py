"""PackedArray — the canonical 1-bit tensor — and the backend registry.

Every packed-bit value in the repo flows through this module:

* ``pack_words`` / ``unpack_words`` / ``popcount_u32``: THE shift-or
  packing loop and its inverses.  This is the only jnp implementation
  in the tree — ``core.binarize.pack_bits`` and ``models.quantize``
  delegate here, and ``kernels/pack.py`` is the Pallas twin of the same
  layout, validated against it in tests.
* ``PackedArray``: a jax pytree bundling the uint32 words with the
  static metadata needed to interpret them — the logical bit length
  (pre-padding), the pack axis (stored negative so leading dims added
  by vmap/scan/stacking never shift it), and the value semantics
  ({-1,+1} vs {0,1}).
* ``BackendSpec`` registry: "pallas" / "interpret" / "xla" execution
  targets owning the padding/blocking policy that ``ops.py`` dispatch
  applies — one place instead of per-wrapper ``_pad_to`` copies.

Layout contract (DESIGN.md §1–§2): bit b of word j along the pack axis
holds ``[x[32*j + b] > 0]``; pad bits are 0 (the value -1 under the
pm1 convention) and every consumer corrects for them via the logical
``length`` — popcount paths use the closed form
``dot = 2*(pc - (K_padded - K)) - K``.

Nothing in ``repro.kernels`` may import ``repro.core`` (core.binarize
delegates *here*; the reverse edge would be a cycle).
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Dict, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

PM1 = "pm1"        # bit 1 <-> +1, bit 0 <-> -1
ZERO_ONE = "01"    # bit is the value

# headroom under the ~16 MB/core VMEM for pipelining and spills — THE
# residency budget every fused dispatch (fused_mlp stack residency,
# packed_conv impl="auto") compares its footprint estimate against
VMEM_BUDGET_BYTES = 12 * 1024 * 1024


def round_up(n: int, m: int) -> int:
    return -(-n // m) * m


# ------------------------------------------------------------------ #
# the single canonical pack / unpack / popcount implementation         #
# ------------------------------------------------------------------ #
def pack_words(x: jax.Array, axis: int = -1) -> jax.Array:
    """Pack sign bits ``x > 0`` into uint32 along ``axis``, 32 per word.

    A non-multiple-of-32 axis is zero-padded first (zeros pack to bit
    0 — the pm1 value -1, matching the padding every consumer corrects
    for through the logical length)."""
    axis = axis % x.ndim
    n = x.shape[axis]
    if n % 32:
        pads = [(0, 0)] * x.ndim
        pads[axis] = (0, (-n) % 32)
        x = jnp.pad(x, pads)
        n = x.shape[axis]
    bits = (x > 0).astype(jnp.uint32)
    x32 = jnp.moveaxis(bits, axis, -1).reshape(*bits.shape[:axis],
                                               *bits.shape[axis + 1:],
                                               n // 32, 32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    words = jnp.sum(x32 << shifts, axis=-1, dtype=jnp.uint32)
    return jnp.moveaxis(words, -1, axis)


def unpack_words(words: jax.Array, axis: int = -1, dtype=jnp.bfloat16,
                 values: str = PM1,
                 length: Optional[int] = None) -> jax.Array:
    """Inverse of pack_words; slices the axis to ``length`` bits when
    given (dropping pad bits)."""
    axis = axis % words.ndim
    shifts = jnp.arange(32, dtype=jnp.uint32)
    w = jnp.moveaxis(words, axis, -1)
    bits = (w[..., None] >> shifts) & jnp.uint32(1)
    if values == PM1:
        vals = (2.0 * bits.astype(jnp.float32) - 1.0).astype(dtype)
    else:
        vals = bits.astype(dtype)
    vals = vals.reshape(*w.shape[:-1], w.shape[-1] * 32)
    if length is not None:
        vals = vals[..., :length]
    return jnp.moveaxis(vals, -1, axis)


def popcount_u32(x: jax.Array) -> jax.Array:
    """SWAR popcount per uint32 lane (the VPU translation of the paper's
    adder tree: log-depth bit-slice accumulation instead of a ripple of
    full adders)."""
    x = x.astype(jnp.uint32)
    x = x - ((x >> 1) & jnp.uint32(0x55555555))
    x = (x & jnp.uint32(0x33333333)) + ((x >> 2) & jnp.uint32(0x33333333))
    x = (x + (x >> 4)) & jnp.uint32(0x0F0F0F0F)
    return ((x * jnp.uint32(0x01010101)) >> 24).astype(jnp.int32)


# ------------------------------------------------------------------ #
# PackedArray                                                          #
# ------------------------------------------------------------------ #
@jax.tree_util.register_pytree_with_keys_class
class PackedArray:
    """1-bit tensor: uint32 ``words`` + static (length, axis, values).

    The pack axis is stored negative so a leading batch dim added by
    vmap / scan / parameter stacking leaves it pointing at the same
    packed dim.  Registered as a pytree: crosses jit / vmap / scan /
    eval_shape / tree_map boundaries with its metadata intact (the
    metadata is hashable aux data, the words are the only leaf).
    """
    __slots__ = ("words", "length", "axis", "values")

    def __init__(self, words, length: int, axis: int = -1,
                 values: str = PM1):
        if axis >= 0:
            axis -= words.ndim
        self.words = words
        self.length = int(length)
        self.axis = int(axis)
        self.values = values

    # -- pytree protocol (aux must stay hashable/static) ------------- #
    def tree_flatten_with_keys(self):
        return (((jax.tree_util.GetAttrKey("words"), self.words),),
                (self.length, self.axis, self.values))

    @classmethod
    def tree_unflatten(cls, aux, children):
        obj = object.__new__(cls)
        obj.words, = children
        obj.length, obj.axis, obj.values = aux
        return obj

    # -- shape metadata ---------------------------------------------- #
    @property
    def ndim(self) -> int:
        return self.words.ndim

    @property
    def n_words(self) -> int:
        return self.words.shape[self.axis]

    @property
    def padded_length(self) -> int:
        return 32 * self.n_words

    @property
    def shape(self):
        """Logical (unpacked) shape."""
        s = list(self.words.shape)
        s[self.axis] = self.length
        return tuple(s)

    @property
    def nbytes(self) -> int:
        return int(np.prod(self.words.shape)) * 4

    def __repr__(self):
        return (f"PackedArray(shape={self.shape}, axis={self.axis}, "
                f"values={self.values!r}, words{tuple(self.words.shape)})")

    # -- construction / conversion ----------------------------------- #
    @classmethod
    def pack(cls, x: jax.Array, axis: int = -1,
             values: str = PM1) -> "PackedArray":
        """sign+pack: bit = ``[x > 0]``; pads the axis to a word
        boundary, recording ``x.shape[axis]`` as the logical length."""
        return cls(pack_words(x, axis=axis), length=x.shape[axis],
                   axis=axis, values=values)

    def unpack(self, dtype=jnp.bfloat16) -> jax.Array:
        """Back to dense values of ``dtype`` (pad bits sliced off)."""
        return unpack_words(self.words, axis=self.axis, dtype=dtype,
                            values=self.values, length=self.length)

    def with_words(self, words) -> "PackedArray":
        return PackedArray(words, self.length, self.axis, self.values)

    def pad_to(self, n_bits: int) -> "PackedArray":
        """Zero-pad words so the padded bit count reaches ``n_bits``
        (rounded up to a word); the logical length is unchanged, so
        consumers keep correcting for the pad bits."""
        tgt = round_up(n_bits, 32) // 32
        if tgt <= self.n_words:
            return self
        pads = [(0, 0)] * self.words.ndim
        pads[self.axis] = (0, tgt - self.n_words)
        return self.with_words(jnp.pad(self.words, pads))

    def move_pack_axis_last(self) -> "PackedArray":
        """Words with the pack axis last (the row-major GEMM operand
        layout); for a 2-D [K/32, N] weight this is the [N, K/32]
        transpose the popcount kernel consumes."""
        if self.axis == -1:
            return self
        return PackedArray(jnp.moveaxis(self.words, self.axis, -1),
                           self.length, -1, self.values)


# ------------------------------------------------------------------ #
# legacy raw-words adoption                                            #
# ------------------------------------------------------------------ #
_RAW_WORDS_WARNED: set = set()


def adopt_packed(a: Union["PackedArray", jax.Array],
                 length: Optional[int] = None, axis: int = -1,
                 context: str = "packed operand") -> "PackedArray":
    """THE adoption point for legacy raw-uint32-word operands.

    A PackedArray passes through unchanged (its recorded length is
    cross-checked against an explicit ``length`` when one is given).  A
    raw uint32 array is wrapped into a PackedArray over ``axis`` with
    the given logical ``length`` (defaulting to every bit of the
    words), after ONE DeprecationWarning per call-site ``context`` —
    raw words carry no layout metadata, so every consumer used to
    re-invent this adoption logic (ops dispatch, models.layers.dense,
    models.moe); this helper is the single deprecation path for all of
    them.
    """
    if isinstance(a, PackedArray):
        if length is not None and a.length != length:
            raise ValueError(f"{context}: explicit length={length} "
                             f"disagrees with "
                             f"PackedArray.length={a.length}")
        return a
    if context not in _RAW_WORDS_WARNED:
        _RAW_WORDS_WARNED.add(context)
        warnings.warn(
            f"{context}: raw uint32 words are deprecated — wrap them in "
            f"a PackedArray (repro.kernels.packed) so the logical "
            f"length and pack axis travel with the words",
            DeprecationWarning, stacklevel=3)
    words = jnp.asarray(a)
    if length is None:
        length = 32 * words.shape[axis]
    return PackedArray(words, length=length, axis=axis)


# ------------------------------------------------------------------ #
# backend registry                                                     #
# ------------------------------------------------------------------ #
@dataclasses.dataclass(frozen=True)
class BackendSpec:
    """One kernel execution target + the padding its blocking requires.

    ops.py pads every operand up front to these multiples (M rows, N
    output cols, K contraction bits), runs the padded problem, and
    slices the logical result back out.  K pads to a word (32 bits)
    below ``k_align`` — a single K block — and to ``k_align`` multiples
    above it, matching the kernels' default block sizes.
    """
    name: str
    uses_kernels: bool      # pallas_call path (compiled or interpret)
    interpret: bool         # Pallas interpret mode (CPU test path)
    m_align: int = 1
    n_align: int = 1
    k_align: int = 32       # bits

    def pad_m(self, m: int) -> int:
        return round_up(m, self.m_align)

    def pad_n(self, n: int) -> int:
        return round_up(n, self.n_align)

    def pad_k(self, k_bits: int) -> int:
        if k_bits <= self.k_align:
            return round_up(k_bits, 32)
        return round_up(k_bits, self.k_align)


_BACKENDS: Dict[str, BackendSpec] = {}


def register_backend(spec: BackendSpec) -> BackendSpec:
    _BACKENDS[spec.name] = spec
    return spec


# K pads to 128 words (4096 bits): a word-axis block is a whole number
# of lane tiles or the whole axis, so a K of up to 128 words is one block
register_backend(BackendSpec("pallas", uses_kernels=True, interpret=False,
                             m_align=128, n_align=128, k_align=4096))
register_backend(BackendSpec("interpret", uses_kernels=True, interpret=True,
                             m_align=128, n_align=128, k_align=4096))
register_backend(BackendSpec("xla", uses_kernels=False, interpret=False))


def default_backend() -> str:
    """pallas on TPU, xla elsewhere ("interpret" is opt-in for tests)."""
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def get_backend(name: Optional[str] = None) -> BackendSpec:
    name = name or default_backend()
    try:
        return _BACKENDS[name]
    except KeyError:
        raise ValueError(f"unknown backend {name!r}; registered: "
                         f"{sorted(_BACKENDS)}") from None


# ------------------------------------------------------------------ #
# small tree utilities                                                 #
# ------------------------------------------------------------------ #
def tree_nbytes(tree: Any) -> int:
    """Total bytes of all array leaves (PackedArray counts its words —
    i.e. the actual HBM footprint, not the logical unpacked one)."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        dt = getattr(leaf, "dtype", None)
        if dt is None:
            continue
        total += int(np.prod(getattr(leaf, "shape", ()))) \
            * jnp.dtype(dt).itemsize
    return total
