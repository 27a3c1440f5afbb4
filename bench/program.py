"""The system under test, built through its normal path.

This is the one module of the benchmark that imports the program:
``graph.compile`` -> served parameters -> ``BNNServer``.  The served
parameters are the raw weights of bench/weights.py packed by the
program's own packer, drawn and packed in one jitted call.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax

import weights
from geometry import layer_shapes


def _geometry(cfg: Dict[str, Any]) -> Tuple[tuple, tuple]:
    """(conv rows, fc rows) of the layer table in the program's
    ``core.workloads`` terms: (name, z1, z2, x1, y1, x2, y2, k, integer)
    and (name, n_in, n_out)."""
    conv, fc = [], []
    for ly in layer_shapes(cfg):
        if ly["kind"] == "dense":
            fc.append((ly["name"], ly["n_in"], ly["n_out"]))
        else:
            conv.append((ly["name"], ly["c_in"], ly["c_out"], ly["w_in"],
                         ly["h_in"], ly["w_out"], ly["h_out"], ly["k"],
                         ly["kind"] == "entry_conv"))
    return tuple(conv), tuple(fc)


def workload(cfg: Dict[str, Any]):
    """The program's Workload for the configuration: its named builder
    in ``repro.core.workloads``, checked against the layer table the
    reference runs, or, without a builder, built from that table."""
    from repro.core import workloads as W

    conv, fc = _geometry(cfg)
    builder = cfg.get("builder")
    if builder is None:
        return W.Workload(cfg["name"], cfg["name"],
                          tuple(W.ConvLayer(*c) for c in conv),
                          tuple(W.FCLayer(*f) for f in fc))
    wl = getattr(W, builder)()
    got = (tuple((c.name, c.z1, c.z2, c.x1, c.y1, c.x2, c.y2, c.k,
                  c.integer) for c in wl.conv),
           tuple((f.name, f.n_in, f.n_out) for f in wl.fc))
    if got != (conv, fc):
        raise ValueError(f"{builder}() does not match the layer table of "
                         f"{cfg['name']}: {got} != {(conv, fc)}")
    return wl


def served_params(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The served parameter tree of ``seed``, drawn and packed on the
    device in one jitted call."""
    from repro.kernels.packed import PackedArray

    layers = cfg["layers"]
    draw = weights.draw_fn(layers)

    def build(key):
        params: Dict[str, List[Any]] = {"conv": [], "fc": []}
        for ly, p in zip(layers, draw(key)):
            if ly["kind"] == "entry_conv":
                params["conv"].append({"w": p["w"], "alpha": p["alpha"]})
            elif ly["kind"] == "binary_conv":
                params["conv"].append({"wf": PackedArray.pack(p["w"], axis=2),
                                       "t": p["t"]})
            else:
                q = {"wp": PackedArray.pack(p["w"], axis=-1)}
                if "t" in p:
                    q["t"] = p["t"]
                params["fc"].append(q)
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
    the step's kind and impl, with the shapes of the layers it runs."""
    by_name = {ly["name"]: ly for ly in layer_shapes(cfg)}
    dense = [ly for ly in layer_shapes(cfg) if ly["kind"] == "dense"]
    steps = []
    for s in cb.plan:
        if s.kind == "fused_stack":
            lys = [dense[j] for j in s.args["fc_indices"]]
        else:
            lys = [by_name[s.name]] if s.name in by_name else []
        steps.append({"kind": s.kind, "impl": s.args.get("impl"),
                      "layers": lys})
    return steps
