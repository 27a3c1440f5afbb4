"""The system under test, built through its normal path.

This is the one module of the benchmark that imports the program:
``graph.compile`` -> served parameters -> ``BNNServer``.  The served
parameters are the raw weights of bench/weights.py packed by the
program's own packer, drawn and packed in one jitted call.  Each
layer's kind (bench/layers/<kind>.py) says what the program's model
lists for it, where its weights go in the served tree, and which parts
the plan's steps run.
"""
from __future__ import annotations

import importlib
from typing import Any, Dict, List

import jax

import weights
from geometry import layer_shapes
from kinds import BenchError, kind


def _table_rows(cfg: Dict[str, Any]) -> Dict[str, List[tuple]]:
    """The rows the program's model must list for the layer table, by
    group, in table order."""
    rows: Dict[str, List[tuple]] = {}
    for sly in layer_shapes(cfg):
        for group, row in kind(sly["kind"]).rows(sly):
            rows.setdefault(group, []).append(row)
    return rows


def _listing(model) -> Dict[str, List[tuple]]:
    """The model's own listing of its layers, as ``core.workloads``
    rows: ``conv`` (name, z1, z2, x1, y1, x2, y2, k, integer) and ``fc``
    (name, n_in, n_out), a BNNSpec's through the program's
    ``spec_to_workload``."""
    from repro.core.workloads import Workload
    from repro.graph.ir import spec_to_workload

    wl = model if isinstance(model, Workload) else spec_to_workload(model)
    return {"conv": [(c.name, c.z1, c.z2, c.x1, c.y1, c.x2, c.y2, c.k, c.integer)
                     for c in wl.conv],
            "fc": [(f.name, f.n_in, f.n_out) for f in wl.fc]}


def workload(cfg: Dict[str, Any]):
    """The program's model of the configuration (a ``Workload`` or a
    ``BNNSpec``; ``graph.compile`` takes both): its ``builder``, a
    function of ``repro.core.workloads`` by name or any program path
    ``module:function``, checked against the rows of the layer table
    the reference runs; without a builder, a Workload built from the
    table's conv and fc rows."""
    from repro.core import workloads as W

    table = _table_rows(cfg)
    if set(table) - {"conv", "fc"}:
        raise BenchError(f"{cfg['name']}: the program lists no rows "
                         f"{sorted(set(table) - {'conv', 'fc'})}")
    builder = cfg.get("builder")
    if builder is None:
        return W.Workload(cfg["name"], cfg["name"],
                          tuple(W.ConvLayer(*c) for c in table.get("conv", ())),
                          tuple(W.FCLayer(*f) for f in table.get("fc", ())))
    mod, _, fn = builder.rpartition(":")
    model = getattr(importlib.import_module(mod) if mod else W, fn)()
    got = _listing(model)
    want = {g: table.get(g, []) for g in got}
    if got != want:
        raise BenchError(f"{builder}() does not match the layer table of "
                         f"{cfg['name']}: {got} != {want}")
    return model


def served_params(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The served parameter tree of ``seed``, drawn and packed on the
    device in one jitted call."""
    from repro.kernels.packed import PackedArray

    layers = cfg["layers"]
    draw = weights.draw_fn(layers)

    def build(key):
        params: Dict[str, List[Any]] = {}
        for ly, p in zip(layers, draw(key)):
            group, entry = kind(ly["kind"]).served(ly, p, PackedArray.pack)
            params.setdefault(group, []).append(entry)
        return params

    return jax.jit(build)(weights.weight_key(seed))


def build(cfg: Dict[str, Any], server_cfg: Dict[str, Any], chips: int,
          seed: int):
    """Compile the configuration and start a ``BNNServer`` over it, with
    the cell's ``max_batch`` and every other setting at its default
    (backend fallback off, so every answer comes from the compiled
    kernels).  Keeps JAX's compile cache where the program's entry
    points do.  Returns (compiled, server)."""
    from repro import graph
    from repro.launch.cache import use_compile_cache
    from repro.serving import BNNServer, data_mesh

    use_compile_cache()
    opts = cfg["compile"]
    cb = graph.compile(workload(cfg), backend=opts["backend"],
                       batch=server_cfg["max_batch"],
                       conv_impl=opts.get("conv_impl", "auto"))
    mesh = data_mesh() if chips > 1 else None
    srv = BNNServer(cb, served_params(cfg, seed),
                    max_batch=server_cfg["max_batch"], mesh=mesh,
                    fallback_backend=None)
    return cb, srv.start()


def plan_steps(cb, cfg: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The compiled plan's steps as plain dicts the kernel counts read:
    the step's kind and impl, with the parts it runs, matched by name
    over every layer's parts (a ``fused_stack`` runs the dense parts
    its ``fc_indices`` name)."""
    parts = [pt for sly in layer_shapes(cfg) for pt in kind(sly["kind"]).parts(sly)]
    by_name = {pt["name"]: pt for pt in parts}
    dense = [pt for pt in parts if pt["kind"] == "dense"]
    steps = []
    for s in cb.plan:
        if s.kind == "fused_stack":
            lys = [dense[j] for j in s.args["fc_indices"]]
        else:
            lys = [by_name[s.name]] if s.name in by_name else []
        steps.append({"kind": s.kind, "impl": s.args.get("impl"),
                      "layers": lys})
    return steps
