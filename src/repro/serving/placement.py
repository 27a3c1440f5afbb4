"""Mesh placement for the serving engine (DESIGN.md §9).

Data-parallel serving: the request batch axis is sharded over the mesh
"data" axis, parameters are replicated.  The rules come from
runtime/sharding.py — ``fit_spec`` with the shared ``BATCH_AXES``
degrades to replication whenever the bucket does not divide the mesh
(a 1- or 2-row bucket on a 4-device mesh), so every bucket runs on
every mesh and the result is bit-identical to single-device execution
either way.

``PackedArray`` inputs shard on their ``words`` leaf: the pack axis is
the (trailing) feature axis, so row-sharding the leading word dim
partitions whole packed rows — no word ever straddles two devices, and
the packed output words come back bit-identical (tests/test_serving.py
asserts this with assert_array_equal).

The TPU compiler cannot partition a Pallas kernel on its own, so on a
mesh the forward runs under ``shard_map`` (``data_parallel``): every
device applies the whole plan to its own rows.  Rows are independent
throughout the datapath, so this is exact.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.launch.mesh import make_local_mesh
from repro.runtime.sharding import BATCH_AXES, fit_spec

__all__ = ["data_mesh", "data_parallel", "ensure_owned", "replicate",
           "shard_batch"]


def data_mesh(model: int = 1) -> Mesh:
    """A whole-host ("data", "model") mesh for data-parallel serving —
    the launch/mesh.py local-mesh shape, every device on "data" by
    default."""
    return make_local_mesh(model=model)


def _batch_spec(tree: Any, mesh: Mesh) -> PartitionSpec:
    """How ``shard_batch`` places a batch: its leading axis over the
    data axes, or replicated when the rows do not divide them."""
    rows = np.shape(jax.tree.leaves(tree)[0])[0]
    return fit_spec((rows,), (BATCH_AXES,), mesh)


def data_parallel(apply: Callable[..., Any], mesh: Optional[Mesh]
                  ) -> Callable[..., Any]:
    """``apply(params, x, valid_rows=None)`` as one program over
    ``mesh``: params replicated, each device running the plan on the
    rows ``shard_batch`` gave it.  A sharded batch runs every row of
    the bucket and is cut to ``valid_rows`` afterwards (a row count
    that does not divide the mesh cannot be split evenly); a batch too
    small to shard runs whole, masked, on every device."""
    if mesh is None:
        return apply

    def run(params: Any, x: Any, valid_rows: Optional[int] = None) -> Any:
        spec = _batch_spec(x, mesh)
        sharded = spec[0] is not None
        fn = jax.shard_map(
            lambda p, xs: apply(p, xs, None if sharded else valid_rows),
            mesh=mesh, in_specs=(PartitionSpec(), spec), out_specs=spec,
            check_vma=False)
        out = fn(params, x)
        if sharded and valid_rows is not None:
            out = jax.tree.map(lambda leaf: leaf[:valid_rows], out)
        return out

    return run


def shard_batch(tree: Any, mesh: Optional[Mesh]) -> Any:
    """device_put every array leaf with its leading (batch) axis over
    the mesh's data axes; a PackedArray flattens to its ``words`` leaf,
    so its leading word dim — whole packed rows — is what shards."""
    if mesh is None:
        return tree

    def put(leaf: Any) -> Any:
        return jax.device_put(leaf,
                              NamedSharding(mesh, _batch_spec(leaf, mesh)))

    return jax.tree.map(put, tree)


def ensure_owned(tree: Any) -> Any:
    """Deep-copy every array leaf so the result is safe to *donate*.

    The serving dispatch donates its input buffer (``CompiledBNN.
    serving_jit_kwargs``); on backends that honor donation the buffer
    is consumed and any other holder's view of it dies.  Padding and
    coalescing already produce fresh server-owned buffers, but an
    exact-bucket-sized single request would flow the CALLER'S array
    straight into the donated slot — this copy is what keeps the
    donation contract one-sided (the server only ever donates buffers
    it created; a caller-held PackedArray is never invalidated,
    tests/test_serving.py asserts it).  For host image rows, which the
    server stages as a flat ``[rows, H*W*C]`` view (DESIGN.md §10),
    the copy is the host-to-device transfer of that view."""
    return jax.tree.map(lambda leaf: jnp.array(leaf, copy=True), tree)


def replicate(tree: Any, mesh: Optional[Mesh]) -> Any:
    """device_put every leaf fully replicated — the parameter placement
    for data-parallel serving (weights are read-only and small in the
    packed layout; ZeRO-style parameter splits stay with the training
    path in runtime/sharding.py)."""
    if mesh is None:
        return tree

    def put(leaf: Any) -> Any:
        return jax.device_put(leaf, NamedSharding(mesh, PartitionSpec()))

    return jax.tree.map(put, tree)
