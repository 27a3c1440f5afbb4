"""The BNN graph IR (DESIGN.md §8).

A :class:`BNNSpec` is a declarative, purely-static description of a
binarized network as a chain of typed nodes — the paper's "arbitrary
nodes of a BNN" (§IV) as data.  The compiler (graph/compile.py) lowers
one spec into BOTH targets: the packed Pallas/XLA executable and the
TULIP-PE schedule model (core/mapping.py rows + core/schedules.py
fragments).

Node set:
  IntegerEntry   float-input conv, alpha*sign(w) weights (the XNOR-Net
                 boundary layer; "Integer" in the paper's Table III)
  Binarize       sign+pack — entry into the packed 1-bit domain
  BinaryConv     channel-packed conv (ops.binary_conv2d)
  MaxPool        max pool — bitwise OR in the packed domain
  BinaryDense    packed XNOR-popcount dense (ops.binary_binary_dense)
  BNThreshold    per-channel integer threshold (folded BN, §IV-D);
                 always FUSED into its producer's pack epilogue
  Logits         int32 dot -> float32 logits (the classifier output)

The float residual domain (a residual BNN such as ReActNet-A,
arXiv:2003.03488, DESIGN.md §8):
  ResidualEntry  the entry conv's BN bias: opens a float32 stream that
                 lives beside the 1-bit convs
  ResidualBlock  one composite block of the stream: sign(x + b) packed,
                 a zero-padded 3x3 binary conv (stride 1 or 2), its BN,
                 the shortcut add (x, or a 2x2 average pool of x at
                 stride 2), RPReLU, then the same around a 1x1 binary
                 conv whose output width is C or 2C (two halves that
                 both add the stream); lowered to four plan steps
  PoolFC         global average pool + float dense -> float32 logits

Lowering entry points:
  from_workload     core/workloads.py dataclass -> BNNSpec (subsumes
                    the geometry inference that used to live in
                    models/layers.py: infer_conv_geometry, infer_pool,
                    fc_entry_size)
  from_dense_stack  a fully-binary MLP stack -> BNNSpec
  spec_to_workload  the inverse bridge back to workloads.Workload for
                    the TULIP mapping/energy model

Specs are validated structurally (``BNNSpec.validate``): chain widths
must match, the packed domain can only be left through Logits, integer
layers cannot follow binary ones (a 1-bit activation cannot re-enter
the float domain — the same "not representable" rule the legacy
builder enforced), and every non-terminal BinaryConv/BinaryDense must
be thresholded (an int32 activation cannot stay packed).  The residual
domain has rules of its own: only ResidualEntry opens it, directly
after an IntegerEntry of the same width; inside it only ResidualBlock
nodes follow, each reading the stream's exact shape (C a multiple of
32, output C or 2C, stride 2 only on even maps); and only PoolFC,
the terminal node, leaves it.  No packed-domain node reads the stream
and no residual node reads packed input.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Sequence, Tuple, Union

from repro.core.workloads import ConvLayer, FCLayer, Workload

__all__ = ["Binarize", "BinaryConv", "BinaryDense", "BNNSpec",
           "BNThreshold", "IntegerEntry", "Logits", "MaxPool", "PoolFC",
           "REACTNET_A_BLOCKS", "ResidualBlock", "ResidualEntry",
           "fc_entry_size", "from_dense_stack", "from_workload",
           "infer_conv_geometry", "infer_pool", "reactnet_a_imagenet",
           "residual_net", "spec_to_workload"]


# ------------------------------------------------------------------ #
# geometry inference (moved here from models/layers.py)                #
# ------------------------------------------------------------------ #
def infer_conv_geometry(layer: ConvLayer) -> Tuple[int, int]:
    """Recover (stride, pad) from a workloads.ConvLayer's in/out dims —
    the paper's tables record only the feature-map sizes.  Searches
    small strides/pads for an exact match (BinaryNet: s=1 same-pad;
    AlexNet conv1: s=4 pad=0) and raises when the dims are not a
    realizable conv geometry."""
    for s in (1, 2, 4, 3):
        for p in range((layer.k + 1) // 2 + 1):
            ok_x = (layer.x1 + 2 * p - layer.k) % s == 0 and \
                (layer.x1 + 2 * p - layer.k) // s + 1 == layer.x2
            ok_y = (layer.y1 + 2 * p - layer.k) % s == 0 and \
                (layer.y1 + 2 * p - layer.k) // s + 1 == layer.y2
            if ok_x and ok_y:
                return s, p
    raise ValueError(f"no (stride, pad) realizes {layer.name}: "
                     f"{layer.x1}x{layer.y1} -> {layer.x2}x{layer.y2} "
                     f"with k={layer.k}")


def infer_pool(x_from: int, x_to: int) -> Optional[Tuple[int, int]]:
    """(window, stride) of the max-pool between two feature-map sizes,
    or None when none is needed.  Covers the workloads' 2x2/s2
    (BinaryNet) and 3x3/s2 (AlexNet) pools."""
    if x_from == x_to:
        return None
    for win, s in ((3, 2), (2, 2)):    # AlexNet's 3x3/s2 preferred;
        if (x_from - win) // s + 1 == x_to:   # BinaryNet only fits 2x2
            return win, s
    raise ValueError(f"no standard max-pool maps {x_from} -> {x_to}")


def fc_entry_size(last_conv: ConvLayer, fc0: FCLayer) -> int:
    """Spatial size the last conv's maps must pool down to so that
    z2 * s^2 == fc0.n_in (the flatten the paper's tables imply)."""
    s2 = fc0.n_in // last_conv.z2
    s = int(math.isqrt(s2))
    if last_conv.z2 * s * s != fc0.n_in:
        raise ValueError(f"{fc0.name}.n_in={fc0.n_in} is not "
                         f"z2 * s^2 for z2={last_conv.z2}")
    return s


# ------------------------------------------------------------------ #
# IR nodes                                                             #
# ------------------------------------------------------------------ #
@dataclass(frozen=True)
class IntegerEntry:
    """Float-input conv with alpha*sign(w) weights (paper "Integer")."""
    name: str
    kh: int
    kw: int
    c_in: int
    c_out: int
    h_in: int
    w_in: int
    h_out: int
    w_out: int
    stride: int = 1
    pad: int = 0
    parts: int = 1        # image buffer parts (paper Table III col 2)


@dataclass(frozen=True)
class Binarize:
    """sign+pack into the 1-bit domain; ``flatten`` collapses the
    spatial dims first (the all-integer-body -> FC boundary)."""
    name: str
    flatten: bool = False


@dataclass(frozen=True)
class BinaryConv:
    name: str
    kh: int
    kw: int
    c_in: int
    c_out: int
    h_in: int
    w_in: int
    h_out: int
    w_out: int
    stride: int = 1
    pad: int = 0
    parts: int = 1


@dataclass(frozen=True)
class MaxPool:
    name: str
    window: int
    stride: int


@dataclass(frozen=True)
class BinaryDense:
    name: str
    n_in: int
    n_out: int


@dataclass(frozen=True)
class BNThreshold:
    """Integer threshold (the folded-BN comparator, paper §IV-D).
    Structurally a node; in the compiled plan it is always FUSED into
    the producing conv/dense pack epilogue.  ``per_channel`` records
    whether the threshold is a [channels] vector (the folded-BN form;
    costs resident bytes in the megakernel) or a static scalar — the
    segmentation pass feeds it to the shared residency rule."""
    name: str
    channels: int
    per_channel: bool = True


@dataclass(frozen=True)
class Logits:
    """Terminal: the last dense's int32 dot as float32 logits."""
    name: str
    classes: int


@dataclass(frozen=True)
class ResidualEntry:
    """Opens the float residual stream after an IntegerEntry: adds the
    entry conv's per-channel BN bias (held with that conv's parameters
    as ``beta``; its ``alpha`` is the BN scale)."""
    name: str
    channels: int


@dataclass(frozen=True)
class ResidualBlock:
    """One ReActNet block over the float stream x [h_in, w_in, c_in]:

    a = sign(x + rsign1); u = RPReLU1(BN1(conv3x3_s(a)) + sc), sc = x
    or avgpool2x2(x) at stride 2; b = sign(u + rsign2); out =
    RPReLU2(BN2(conv1x1(b)) + u), the 1x1 conv c_in -> c_out with
    c_out = c_in, or 2 * c_in as two halves that each add u.  The 3x3
    conv pads with zeros (an exact integer correction of the kernels'
    -1 border, kernels/residual.py); RPReLU(v) = PReLU(v + move_in;
    slope) + move_out, per channel."""
    name: str
    c_in: int
    c_out: int
    h_in: int
    w_in: int
    stride: int = 1

    @property
    def h_out(self) -> int:
        return (self.h_in - 1) // self.stride + 1

    @property
    def w_out(self) -> int:
        return (self.w_in - 1) // self.stride + 1


@dataclass(frozen=True)
class PoolFC:
    """Terminal of the residual stream: global average pool, then a
    float32 dense n_in -> classes with a bias (the logits)."""
    name: str
    n_in: int
    classes: int


Node = Union[IntegerEntry, Binarize, BinaryConv, MaxPool, BinaryDense,
             BNThreshold, Logits, ResidualEntry, ResidualBlock, PoolFC]
ConvNode = (IntegerEntry, BinaryConv)


# ------------------------------------------------------------------ #
# the spec                                                             #
# ------------------------------------------------------------------ #
@dataclass(frozen=True)
class BNNSpec:
    """A declarative BNN: input shape + an ordered chain of nodes.

    ``input_shape`` is the logical per-sample shape: ``(H, W, C)`` for
    a conv network fed float NHWC images, ``(K,)`` for a dense stack
    fed an already-packed activation row."""
    name: str
    input_shape: Tuple[int, ...]
    nodes: Tuple[Node, ...]
    dataset: str = ""

    @property
    def conv_nodes(self) -> Tuple[Node, ...]:
        return tuple(n for n in self.nodes if isinstance(n, ConvNode))

    @property
    def dense_nodes(self) -> Tuple[BinaryDense, ...]:
        return tuple(n for n in self.nodes
                     if isinstance(n, BinaryDense))

    @property
    def residual_blocks(self) -> Tuple[ResidualBlock, ...]:
        return tuple(n for n in self.nodes
                     if isinstance(n, ResidualBlock))

    def thresholded(self, node: Union[BinaryConv, BinaryDense]) -> bool:
        """True when ``node`` is directly followed by a BNThreshold."""
        i = next((j for j, n in enumerate(self.nodes) if n is node),
                 None)
        if i is None:
            i = self.nodes.index(node)
        return i + 1 < len(self.nodes) and \
            isinstance(self.nodes[i + 1], BNThreshold)

    # -------------------------------------------------------------- #
    def validate(self) -> None:
        """Structural checks; raises ValueError with the offending
        node named.  See the module docstring for the rules."""
        if not self.nodes:
            raise ValueError(f"{self.name}: empty spec")
        first_dense = isinstance(self.nodes[0], BinaryDense)
        if first_dense and len(self.input_shape) != 1:
            raise ValueError(f"{self.name}: a dense-entry spec takes a "
                             f"packed (K,) input, got "
                             f"{self.input_shape}")
        domain = "packed_flat" if first_dense else "float"
        h, w, c = (0, 0, self.input_shape[0]) if first_dense else \
            self.input_shape
        width = self.input_shape[0] if first_dense else 0
        for i, nd in enumerate(self.nodes):
            prev = self.nodes[i - 1] if i else None
            if domain == "residual" and not isinstance(
                    nd, (ResidualBlock, PoolFC)):
                raise ValueError(
                    f"{nd.name}: the residual stream can only be read by "
                    f"ResidualBlock nodes and left through PoolFC")
            if isinstance(nd, ResidualEntry):
                if not isinstance(prev, IntegerEntry):
                    raise ValueError(
                        f"{nd.name}: the residual stream opens directly "
                        f"after an IntegerEntry")
                if nd.channels != c:
                    raise ValueError(f"{nd.name}: {nd.channels} channels "
                                     f"for a {c}-wide entry conv")
                domain = "residual"
            elif isinstance(nd, ResidualBlock):
                if domain != "residual":
                    raise ValueError(
                        f"{nd.name}: a residual block reads the float "
                        f"residual stream, not {domain} input (open the "
                        f"stream with ResidualEntry)")
                if (nd.c_in, nd.h_in, nd.w_in) != (c, h, w):
                    raise ValueError(
                        f"{nd.name}: expects {nd.h_in}x{nd.w_in}x"
                        f"{nd.c_in}, the stream is {h}x{w}x{c}")
                if nd.c_in % 32 or nd.c_out not in (nd.c_in, 2 * nd.c_in):
                    raise ValueError(
                        f"{nd.name}: {nd.c_in} -> {nd.c_out} channels; a "
                        f"block keeps a width that is a multiple of 32 "
                        f"or doubles it")
                if nd.stride not in (1, 2) or (
                        nd.stride == 2 and (h % 2 or w % 2)):
                    raise ValueError(
                        f"{nd.name}: stride {nd.stride} on a {h}x{w} map; "
                        f"the 2x2 average-pooled shortcut needs stride 1, "
                        f"or 2 on an even map")
                h, w, c = nd.h_out, nd.w_out, nd.c_out
            elif isinstance(nd, PoolFC):
                if domain != "residual":
                    raise ValueError(f"{nd.name}: PoolFC reads the float "
                                     f"residual stream, not {domain} "
                                     f"input")
                if nd.n_in != c:
                    raise ValueError(f"{nd.name}: n_in={nd.n_in} but the "
                                     f"stream is {c} wide")
                if i != len(self.nodes) - 1:
                    raise ValueError(f"{nd.name}: PoolFC must be the "
                                     f"terminal node")
                domain = "logits"
            elif isinstance(nd, IntegerEntry):
                if domain != "float":
                    raise ValueError(
                        f"{nd.name}: integer layer after a binary layer "
                        f"is not representable")
                if (nd.c_in, nd.h_in, nd.w_in) != (c, h, w):
                    raise ValueError(
                        f"{nd.name}: expects {nd.h_in}x{nd.w_in}x"
                        f"{nd.c_in}, incoming is {h}x{w}x{c}")
                h, w, c = nd.h_out, nd.w_out, nd.c_out
            elif isinstance(nd, Binarize):
                if domain != "float":
                    raise ValueError(f"{nd.name}: already packed")
                if nd.flatten:
                    domain, width = "packed_flat", h * w * c
                else:
                    domain = "packed_conv"
            elif isinstance(nd, BinaryConv):
                if domain != "packed_conv":
                    raise ValueError(f"{nd.name}: binary conv needs the "
                                     f"packed conv domain (insert a "
                                     f"Binarize node)")
                if (nd.c_in, nd.h_in, nd.w_in) != (c, h, w):
                    raise ValueError(
                        f"{nd.name}: expects {nd.h_in}x{nd.w_in}x"
                        f"{nd.c_in}, incoming is {h}x{w}x{c}")
                if not self.thresholded(nd):
                    raise ValueError(
                        f"{nd.name}: a binary conv must be followed by "
                        f"a BNThreshold (an int32 activation cannot "
                        f"stay packed)")
                h, w, c = nd.h_out, nd.w_out, nd.c_out
            elif isinstance(nd, MaxPool):
                if domain not in ("float", "packed_conv"):
                    raise ValueError(f"{nd.name}: pooling needs spatial "
                                     f"activations")
                h = (h - nd.window) // nd.stride + 1
                w = (w - nd.window) // nd.stride + 1
                if h <= 0 or w <= 0:
                    raise ValueError(f"{nd.name}: pool empties the map")
            elif isinstance(nd, BinaryDense):
                if domain == "packed_conv":
                    domain, width = "packed_flat", h * w * c
                elif domain == "float":
                    raise ValueError(f"{nd.name}: dense input must be "
                                     f"packed (insert a Binarize node)")
                if nd.n_in != width:
                    raise ValueError(f"{nd.name}: n_in={nd.n_in} but the "
                                     f"incoming width is {width}")
                nxt = self.nodes[i + 1] if i + 1 < len(self.nodes) \
                    else None
                if nxt is not None and \
                        not isinstance(nxt, (BNThreshold, Logits)):
                    raise ValueError(
                        f"{nd.name}: a dense layer must be followed by "
                        f"a BNThreshold or Logits (or terminate the "
                        f"spec with a packed output)")
                width = nd.n_out
            elif isinstance(nd, BNThreshold):
                if not isinstance(prev, (BinaryConv, BinaryDense)):
                    raise ValueError(f"{nd.name}: BNThreshold must "
                                     f"directly follow a binary conv "
                                     f"or dense node")
                out = prev.c_out if isinstance(prev, BinaryConv) \
                    else prev.n_out
                if nd.channels != out:
                    raise ValueError(f"{nd.name}: {nd.channels} channels "
                                     f"for a {out}-wide producer")
            elif isinstance(nd, Logits):
                if not isinstance(prev, BinaryDense):
                    raise ValueError(f"{nd.name}: Logits must follow an "
                                     f"un-thresholded BinaryDense")
                if nd.classes != prev.n_out:
                    raise ValueError(f"{nd.name}: {nd.classes} classes "
                                     f"vs {prev.n_out}-wide dense")
                if i != len(self.nodes) - 1:
                    raise ValueError(f"{nd.name}: Logits must be the "
                                     f"terminal node")
            else:
                raise ValueError(f"unknown node {nd!r}")
        if domain == "residual":
            raise ValueError(f"{self.name}: the residual stream must be "
                             f"left through a terminal PoolFC")


# ------------------------------------------------------------------ #
# lowering: workloads.py dataclasses -> IR                             #
# ------------------------------------------------------------------ #
def _conv_node(layer: ConvLayer, stride: int, pad: int) -> Node:
    cls = IntegerEntry if layer.integer else BinaryConv
    return cls(layer.name, layer.k, layer.k, layer.z1, layer.z2,
               layer.y1, layer.x1, layer.y2, layer.x2, stride, pad,
               layer.parts)


def from_workload(wl: Workload) -> BNNSpec:
    """Pass 1 of the compile pipeline: lower a paper Workload into the
    IR, inferring (stride, pad) and the inter-layer pools from the
    table dims exactly as the legacy builder did."""
    if not wl.fc:
        raise ValueError(f"{wl.name}: a workload needs an FC tail")
    nodes = []
    packed = False
    conv, fc = wl.conv, wl.fc
    for i, l in enumerate(conv):
        s, p = infer_conv_geometry(l)
        if l.integer:
            if packed:
                raise ValueError(f"{l.name}: integer layer after a "
                                 f"binary layer is not representable")
            nodes.append(_conv_node(l, s, p))
        else:
            if not packed:
                nodes.append(Binarize(f"binarize@{l.name}"))
                packed = True
            nodes.append(_conv_node(l, s, p))
            nodes.append(BNThreshold(f"{l.name}.bn", l.z2))
        nxt = conv[i + 1].x1 if i + 1 < len(conv) else \
            fc_entry_size(l, fc[0])
        pool = infer_pool(l.x2, nxt)
        if pool is not None:
            nodes.append(MaxPool(f"pool@{l.name}", *pool))
    if conv and not packed:            # all-integer conv body
        nodes.append(Binarize("binarize@flatten", flatten=True))
    for j, l in enumerate(fc):
        if l.integer:
            raise ValueError(f"{l.name}: integer FC layers are not "
                             f"representable on the packed datapath")
        nodes.append(BinaryDense(l.name, l.n_in, l.n_out))
        if j < len(fc) - 1:
            nodes.append(BNThreshold(f"{l.name}.bn", l.n_out))
        else:
            nodes.append(Logits("logits", l.n_out))
    shape = (conv[0].y1, conv[0].x1, conv[0].z1) if conv else \
        (fc[0].n_in,)
    spec = BNNSpec(wl.name, shape, tuple(nodes), dataset=wl.dataset)
    spec.validate()
    return spec


def from_dense_stack(k0: int, ns: Sequence[int],
                     thresholded: Optional[Sequence[bool]] = None,
                     name: str = "mlp", logits: bool = False,
                     per_channel: Optional[Sequence[bool]] = None
                     ) -> BNNSpec:
    """A fully-binary MLP stack as a spec: packed [.., k0] input
    through dense layers of widths ``ns``.  ``thresholded`` defaults
    to all-True (each layer's output stays packed); with ``logits``
    the last layer is un-thresholded and terminates in a Logits node.
    ``per_channel`` marks which thresholds are [N_l] vectors (default)
    vs static scalars — a residency-footprint input to the megakernel
    segmentation pass."""
    if not ns:
        raise ValueError("from_dense_stack needs at least one layer")
    if thresholded is None:
        thresholded = [True] * len(ns)
        if logits:
            thresholded[-1] = False
    if per_channel is None:
        per_channel = [True] * len(ns)
    nodes = []
    d = k0
    for idx, (n, thr, pc) in enumerate(zip(ns, thresholded,
                                           per_channel)):
        nodes.append(BinaryDense(f"dense{idx}", d, n))
        if thr:
            nodes.append(BNThreshold(f"dense{idx}.bn", n,
                                     per_channel=bool(pc)))
        d = n
    if logits:
        nodes.append(Logits("logits", ns[-1]))
    spec = BNNSpec(name, (k0,), tuple(nodes))
    spec.validate()
    return spec


def spec_to_workload(spec: BNNSpec) -> Workload:
    """The inverse bridge: IR conv/dense nodes back into the
    workloads.py dataclasses the TULIP mapping/energy model consumes.
    Guarantees ``compile(wl).tulip_mapping()`` sees exactly the layers
    ``core.mapping.table3_rows(wl)`` does."""
    conv, fc = [], []
    for nd in spec.nodes:
        if isinstance(nd, ConvNode):
            if nd.kh != nd.kw:
                raise ValueError(f"{nd.name}: the mapping model takes "
                                 f"square kernels, got "
                                 f"{nd.kh}x{nd.kw}")
            conv.append(ConvLayer(
                nd.name, nd.c_in, nd.c_out, nd.w_in, nd.h_in,
                nd.w_out, nd.h_out, nd.kh,
                integer=isinstance(nd, IntegerEntry), parts=nd.parts))
        elif isinstance(nd, ResidualBlock):
            conv.append(ConvLayer(
                f"{nd.name}.conv3x3", nd.c_in, nd.c_in, nd.w_in, nd.h_in,
                nd.w_out, nd.h_out, 3, integer=False))
            conv.append(ConvLayer(
                f"{nd.name}.conv1x1", nd.c_in, nd.c_out, nd.w_out,
                nd.h_out, nd.w_out, nd.h_out, 1, integer=False))
        elif isinstance(nd, BinaryDense):
            fc.append(FCLayer(nd.name, nd.n_in, nd.n_out))
        elif isinstance(nd, PoolFC):
            fc.append(FCLayer(nd.name, nd.n_in, nd.classes, integer=True))
    return Workload(spec.name, spec.dataset, tuple(conv), tuple(fc))


# ------------------------------------------------------------------ #
# residual networks                                                    #
# ------------------------------------------------------------------ #
# ReActNet-A (arXiv:2003.03488 §3-4; the authors' imagenet_training/
# reactnet.py): MobileNetV1's widths, every conv binary; (c_out, stride)
# of each of the 13 blocks after the 32-channel stem
REACTNET_A_BLOCKS = ((64, 1), (128, 2), (128, 1), (256, 2), (256, 1),
                     (512, 2), (512, 1), (512, 1), (512, 1), (512, 1),
                     (512, 1), (1024, 2), (1024, 1))


def residual_net(name: str, input_shape: Tuple[int, int, int],
                 stem: int, blocks: Sequence[Tuple[int, int]],
                 classes: int, dataset: str = "") -> BNNSpec:
    """A residual BNN as a spec: a 3x3 stride-2 IntegerEntry stem of
    ``stem`` channels (zero padding 1), the float stream it opens, one
    ResidualBlock per ``(c_out, stride)`` of ``blocks``, and PoolFC to
    ``classes`` logits.  Blocks are named ``block1`` ... and the stem
    ``stem``."""
    h, w, c = input_shape
    ho, wo = (h - 1) // 2 + 1, (w - 1) // 2 + 1
    nodes: list = [IntegerEntry("stem", 3, 3, c, stem, h, w, ho, wo,
                                stride=2, pad=1),
                   ResidualEntry("stem.bn", stem)]
    h, w, c = ho, wo, stem
    for i, (c_out, s) in enumerate(blocks, 1):
        blk = ResidualBlock(f"block{i}", c, c_out, h, w, s)
        nodes.append(blk)
        h, w, c = blk.h_out, blk.w_out, c_out
    nodes.append(PoolFC("fc", c, classes))
    spec = BNNSpec(name, tuple(input_shape), tuple(nodes), dataset=dataset)
    spec.validate()
    return spec


def reactnet_a_imagenet() -> BNNSpec:
    """ReActNet-A on ImageNet at its published widths: 224x224x3, a
    32-channel stem, 13 blocks (64 ... 1024), 1000 classes."""
    return residual_net("ReActNet-A", (224, 224, 3), 32,
                        REACTNET_A_BLOCKS, 1000, dataset="Imagenet")
