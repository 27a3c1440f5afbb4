"""Compile every Pallas kernel of the BinaryNet serving path for a TPU
v5e, at BinaryNet CIFAR-10 widths, and ReActNet-A's residual epilogue
at its widths, without a chip.

The TPU compiler ships with jaxlib and compiles for a described (not
attached) topology, so this refuses here what the chip's compiler would
refuse: blocks that are not whole (8, 128) tiles, operations Mosaic
cannot lower, kernels that overflow the scoped VMEM.  Interpret mode
checks none of that.  Nothing runs, so these tests say nothing about
results or speed; tests/test_fused.py, test_conv.py and test_graph.py
hold the kernels bit-exact in interpret mode.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and a worker of a parallel
test run that is not given this file must not try.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import graph
from repro.core.workloads import binarynet_cifar10
from repro.kernels import ops
from repro.kernels.fused_mlp import fused_binary_mlp
from repro.kernels.pack import pack
from repro.kernels.packed import PackedArray
from repro.kernels.packed_conv import packed_conv2d
from repro.graph.ir import residual_net
from repro.kernels.popcount_gemm import popcount_gemm
from repro.kernels.residual import border_classes, residual_epilogue
from repro.kernels.xnor_gemm import xnor_gemm

U32, I32, F32 = jnp.uint32, jnp.int32, jnp.float32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _kernels(hlo: str) -> int:
    return hlo.count('custom_call_target="tpu_custom_call"')


def _conv(hp, c32, f, ho):
    """A BinaryNet conv layer as ops.binary_conv2d launches it: a batch
    of 8 spatially padded images, tap-major filters, per-channel
    thresholds, fused threshold->pack epilogue."""
    def fn(x, w, t):
        return packed_conv2d(x, w, kh=3, kw=3, c=32 * c32, stride=1,
                             ho=ho, wo=ho, threshold_vec=t, pack_out=True,
                             valid_f=f)
    return fn, ((8, hp, hp, c32), U32), ((9 * c32, f), U32), ((f,), I32)


def _fc1_fc2(x, w1, w2, t1, t2):
    return fused_binary_mlp(
        PackedArray(x, 8192), [PackedArray(w1, 8192), PackedArray(w2, 1024)],
        [t1, t2], backend="pallas").words


def _epilogue(hw, c, groups, stride, border, pack):
    """A ReActNet-A block epilogue over 8 images: int32 sums of
    groups * c channels on the hw x hw output map, the c-wide float32
    shortcut, the [6, groups * c] rows and, after a 3x3 conv of stride
    ``stride``, the zero-border table."""
    rows = None
    n_rows = 1
    if border:
        rows, _, rsets, _ = border_classes(hw * stride, hw * stride, 3,
                                           stride, 1)
        n_rows = len(rsets)

    def fn(s, sc, vec, table):
        return residual_epilogue(s, sc, vec, table if border else None,
                                 rows, pack=pack)[0]
    cp = groups * c
    return fn, ((8, hw, hw, cp), I32), ((8, hw, hw, c), F32), \
        ((6, cp), F32), ((n_rows, hw, cp), I32)


def _sums_conv(hw, c, impl, k=3, stride=1, f=None):
    """A k x k conv of int32 sums (ReActNet-A's form) for 8 images,
    through binary_conv2d's dispatch with the given impl."""
    f = f or c

    def fn(x, w):
        return ops.binary_conv2d(PackedArray(x, c, -1),
                                 PackedArray(w, c, 2), stride=stride,
                                 padding=(k - 1) // 2, backend="pallas",
                                 impl=impl)
    return fn, ((8, hw, hw, c // 32), U32), ((k, k, c // 32, f), U32)


# name -> (function, operand (shape, dtype)s), at the padded shapes
# ops.py dispatch hands each kernel for a batch of 8 BinaryNet images
KERNELS = {
    # binarize conv1's output: 8 images x 32x32 pixels, 128 channels
    "pack": (lambda x: pack(x), ((8 * 32 * 32, 128), F32)),
    "packed_conv2d conv2": _conv(34, 4, 128, 32),
    "packed_conv2d conv6": _conv(10, 16, 512, 8),
    "fused_mlp fc1+fc2": (_fc1_fc2, ((128, 256), U32), ((1024, 256), U32),
                          ((1024, 32), U32), ((1024,), I32),
                          ((1024,), I32)),
    "popcount_gemm fc3": (lambda x, w: popcount_gemm(x, w, 1024),
                          ((128, 32), U32), ((128, 32), U32)),
    # fc1 as a chained launch: 8192 -> 1024 with the packed epilogue
    "popcount_gemm fc1 pack_out": (
        lambda x, w, t: popcount_gemm(x, w, 8192, threshold_vec=t,
                                      pack_out=True),
        ((128, 256), U32), ((1024, 256), U32), ((1024,), I32)),
    "xnor_gemm": (lambda x, w, a: xnor_gemm(x, w, a),
                  ((128, 1024), jnp.bfloat16), ((32, 1024), U32),
                  ((1024,), F32)),
    # ReActNet-A: block1's epilogues (32 channels, 112x112; the second
    # adds the stream to both halves of 64), block2's after a stride-2
    # conv, block13's last (no signs packed)
    "residual_epilogue block1.1": _epilogue(112, 32, 1, 1, True, True),
    "residual_epilogue block1.2": _epilogue(112, 32, 2, 1, False, True),
    "residual_epilogue block2.1": _epilogue(56, 64, 1, 2, True, True),
    "residual_epilogue block13.2": _epilogue(7, 1024, 1, 1, False, False),
    # ReActNet-A's stride-1 3x3 convs, int32 sums, forced off the MXU:
    # block3 and block13 as im2col (their direct kernels ask 48 MB of
    # VMEM), and direct a map the int32 estimate bounds below the budget
    "popcount_gemm block3.conv3x3": _sums_conv(56, 128, "im2col"),
    "popcount_gemm block13.conv3x3": _sums_conv(7, 1024, "im2col"),
    "packed_conv2d 8x8x1024 sums": _sums_conv(8, 1024, "direct"),
    "xnor_gemm pack_out": (
        lambda x, w, a, t: xnor_gemm(x, w, a, threshold_vec=t,
                                     pack_out=True),
        ((128, 1024), jnp.bfloat16), ((32, 1024), U32), ((1024,), F32),
        ((1024,), F32)),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, *shapes = KERNELS[name]
    assert _kernels(_compile(fn, one_chip, *shapes)) == 1


def test_binarynet_forward_compiles_for_v5e(one_chip):
    """The whole compiled forward at batch 8: every planned launch is a
    Pallas kernel in the program the chip would run."""
    cb = graph.compile(binarynet_cifar10(), backend="pallas", batch=8)
    params = jax.eval_shape(cb.init, jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        params)
    x = jax.ShapeDtypeStruct((8, 32, 32, 3), F32, sharding=one_chip)
    hlo = jax.jit(cb.apply).lower(params, x).compile().as_text()
    assert _kernels(hlo) == cb.launch_count() == 8


# ReActNet-A's int32-sum convs as auto plans them: XLA int8 convs
MXU_CONVS = {
    "block1.conv3x3": (112, 32, 3, 1, 32),
    "block2.conv3x3 stride 2": (112, 64, 3, 2, 64),
    "block7.conv3x3": (14, 512, 3, 1, 512),
    "block12.conv1x1": (7, 512, 1, 1, 1024),
}


@pytest.mark.parametrize("name", sorted(MXU_CONVS))
def test_mxu_conv_compiles_for_v5e(name, one_chip):
    """The MXU form is one convolution of int8 operands with int32
    sums (the 1x1's dot compiles to one too), and no Pallas kernel."""
    hw, c, k, stride, f = MXU_CONVS[name]
    fn, *shapes = _sums_conv(hw, c, "mxu", k=k, stride=stride, f=f)
    hlo = _compile(fn, one_chip, *shapes)
    assert _kernels(hlo) == 0
    convs = [ln for ln in hlo.splitlines() if " convolution(" in ln]
    assert len(convs) == 1 and "s32[" in convs[0], convs


def test_residual_forward_compiles_for_v5e(one_chip):
    """A small residual spec's whole forward at batch 8 on the kernels
    its plan picks (its convs on the MXU, the stem's pack and the
    epilogues as Pallas kernels): every planned launch is a Pallas
    kernel."""
    spec = residual_net("tiny-reactnet", (32, 32, 3), 32,
                        ((64, 1), (128, 2), (128, 1)), 10)
    cb = graph.compile(spec, backend="pallas", batch=8)
    params = jax.eval_shape(cb.init, jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        params)
    x = jax.ShapeDtypeStruct((8, 32, 32, 3), F32, sharding=one_chip)
    hlo = jax.jit(cb.apply).lower(params, x).compile().as_text()
    assert _kernels(hlo) == cb.launch_count() == 7
