"""Layer kinds found by name (bench/kinds.py, bench/layers/).

* golden values: the table's shapes and MACs, the seeded weights, the
  reference logits, the compiled plan's steps and the kernel counts of
  each configuration, as the three kinds gave them before they moved
  into modules of their own;
* a kind that exists only as a new file (a copy of ``dense`` under
  another name) is found and drives a whole run to ``correct``; an
  unknown kind is a BenchError;
* the program's model named as ``module:function``, a Workload or a
  BNNSpec, is checked against the table; rows the program does not
  list are a BenchError;
* a dense part counts as a popcount_gemm call only in a dense step;
* ``compare.row_gap``: a row differs only where its widest gap exceeds
  it.
"""
import hashlib
import json
import os
import shutil
import types

import numpy as np
import pytest

import geometry
import kinds
import program
import reference
import run
import weights

DATA = os.path.join(os.path.dirname(__file__), "data")
SEED = 2**31 + 4242

# name: (configuration file, max_batch the plan is compiled for, images)
CONFIGS = {
    "tiny-bnn": (os.path.join(DATA, "tiny-bnn.json"), 8, 4),
    "binarynet-cifar10": (os.path.join(run.BENCH, "configs", "binarynet-cifar10.json"), 128, 4),
    "xnor-alexnet-imagenet": (os.path.join(run.BENCH, "configs", "xnor-alexnet-imagenet.json"),
                              64, 2),
}

# sha256 digests (see _digest) and MACs, taken on the CPU before the
# kinds moved into bench/layers/
GOLDEN = {
    "tiny-bnn": {
        "layer_shapes": "f0a03c30723df0180ddc47f2c4ed31a6e9b0cb9d7e65cc30a7ce96528b30cc85",
        "macs_per_image": 682624,
        "make_raw": "74b368994f4a20701cd189fa0cdabc9fa7c50685f5f3df40d0b11cf12d76288f",
        "logits": "b9cb834af241208d3bcb757bfb3d68cbc983fad36cd0bcbd5a461a9611702669",
        "plan_steps": "bccbb319ccffa2f8a90165784e74e07500bf055142a87d557a64d8af0be177fe",
        "kernel_costs": "c58707632347e7286d5ecd373aa243f473bfec6320c917c58e01383f05b43137",
    },
    "binarynet-cifar10": {
        "layer_shapes": "123cd305bd517e496640a6c81d9bfa9bb4f5436e576722d94dbab02cc5327e83",
        "macs_per_image": 616966144,
        "make_raw": "89d1b8c2778986a79cdbfc99ca91e301816caec85c20e1c16a10b6f3e03f9638",
        "logits": "7091ac7bb30f59b125b70f862fc9365bf65875cccc1af9b95e67a1e6bb036a32",
        "plan_steps": "05fc7a7d6f09015a8133e0c35e8a1cd18674665b94989a8d7a70c17015f923fa",
        "kernel_costs": "ce83b92fd91cd19179dd6d6f46ad218439275be3bd1e730bda7c1d2c58337a45",
    },
    "xnor-alexnet-imagenet": {
        "layer_shapes": "fe3c524939b829e5ff6b589b1f7652c3d3f656d4fb349e19b7eb6df5b2247e4d",
        "macs_per_image": 1135256096,
        "make_raw": "a1fb8a4c90aec3f0f54273a9f6d3348425d954a841896fcd4af195f310ce26da",
        "logits": "2e26cdf65cb85b4b9d4bc10d1189cdbd4921d91907e185679198ce51b77d372b",
        "plan_steps": "f6ab17b291986ff8ea15e4a6c55f877722d19f02bdcc5c30530770927923175d",
        "kernel_costs": "027550346318426feb48fc066d2e38bddef4c63c22c7b52ff5db160f8851b799",
    },
}
# the served parameter tree of tiny-bnn, leaf by leaf
GOLDEN_SERVED_TINY = "7fd0877cb81ff2abcaeee425fb53d9ce2ddce733d900957ecc7efce74a350f1c"
KERNEL_ROWS = 100.37   # a mean of valid rows per flight that is not whole


def _digest(*parts):
    h = hashlib.sha256()
    for p in parts:
        h.update(p if isinstance(p, bytes) else str(p).encode())
    return h.hexdigest()


def _raw_digest(raw):
    return _digest(*(b for i, p in enumerate(raw) for k in sorted(p)
                     for a in [np.asarray(p[k])]
                     for b in (f"{i}/{k}/{a.dtype}/{a.shape}", a.tobytes())))


def _plan(cfg, batch):
    from repro import graph

    return graph.compile(program.workload(cfg), backend=cfg["compile"]["backend"],
                         batch=batch, conv_impl=cfg["compile"].get("conv_impl", "auto"))


def _kernel_costs(steps):
    return {k: [run.load_module(os.path.join(run.BENCH, "kernels", k + ".py")).cost(s, KERNEL_ROWS)
                for s in steps]
            for k in ("packed_conv2d", "popcount_gemm", "fused_mlp")}


@pytest.mark.parametrize("name", list(CONFIGS))
def test_golden_values(name):
    path, batch, n = CONFIGS[name]
    cfg = run._load_json(path)
    want = GOLDEN[name]
    assert _digest(json.dumps(geometry.layer_shapes(cfg), sort_keys=True)) == want["layer_shapes"]
    assert geometry.macs_per_image(cfg) == want["macs_per_image"]
    raw = weights.make_raw(cfg["layers"], SEED)
    assert _raw_digest(raw) == want["make_raw"]
    got = reference.logits(cfg["layers"], raw, weights.make_images(cfg["input"], n, SEED), n)
    assert got.shape == (n, *geometry.final_shape(cfg))
    assert _digest(np.asarray(got, np.float32).tobytes()) == want["logits"]
    steps = program.plan_steps(_plan(cfg, batch), cfg)
    assert _digest(json.dumps(steps, sort_keys=True)) == want["plan_steps"]
    assert _digest(json.dumps(_kernel_costs(steps))) == want["kernel_costs"]


def test_golden_served_tree():
    import jax

    cfg = run._load_json(CONFIGS["tiny-bnn"][0])
    leaves = jax.tree_util.tree_flatten_with_path(program.served_params(cfg, SEED))[0]
    assert _digest(*(b for pth, v in leaves for a in [np.asarray(v)]
                     for b in (jax.tree_util.keystr(pth), str(a.dtype), a.tobytes()))
                   ) == GOLDEN_SERVED_TINY


def _copy_cell(tmp_path, monkeypatch, cfg, cell="tiny-copy.bulk"):
    """A benchmark file of one cell over ``cfg``, written to
    ``tmp_path``, with the traffic and server settings of tiny.bulk."""
    (tmp_path / "cfg.json").write_text(json.dumps(cfg))
    (tmp_path / "workloads").mkdir()
    shutil.copy(os.path.join(DATA, "workloads", "tiny.bulk.json"),
                tmp_path / "workloads" / f"{cell}.json")
    spec = run._load_json(os.path.join(DATA, "bench.json"))
    spec["configs"] = [{"name": cfg["name"], "file": str(tmp_path / "cfg.json")}]
    spec["workloads"] = [{"name": cell, "config": cfg["name"], "traffic": "closed-2x8",
                          "chips": 1}]
    (tmp_path / "bench.json").write_text(json.dumps(spec))
    monkeypatch.setattr(run, "BENCHMARK_JSON", str(tmp_path / "bench.json"))
    monkeypatch.setattr(run, "WORKLOADS_DIR", str(tmp_path / "workloads"))
    return cell


def test_kind_from_a_new_file_alone(on_cpu, tmp_path):
    """dense.py copied as dense_copy.py into a directory of the search
    path, and nothing else added, serves tiny-bnn's head."""
    layers = tmp_path / "layers"
    layers.mkdir()
    shutil.copy(os.path.join(run.BENCH, "layers", "dense.py"), layers / "dense_copy.py")
    on_cpu.setattr(kinds, "LAYER_DIRS", [*kinds.LAYER_DIRS, str(layers)])
    tiny = run._load_json(CONFIGS["tiny-bnn"][0])
    cfg = json.loads(json.dumps(tiny))
    cfg["name"] = "tiny-copy"
    cfg["layers"][-1]["kind"] = "dense_copy"

    assert kinds.kind("dense_copy").__file__ == str(layers / "dense_copy.py")
    assert program.plan_steps(_plan(cfg, 8), cfg) == program.plan_steps(_plan(tiny, 8), tiny)
    out = run.run(_copy_cell(tmp_path, on_cpu, cfg), SEED, 1.0, False)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0


def test_kind_loads_once():
    assert kinds.kind("dense") is kinds.kind("dense")


def test_rows_the_program_does_not_list(monkeypatch):
    cfg = run._load_json(CONFIGS["tiny-bnn"][0])
    dense = kinds.kind("dense")
    monkeypatch.setattr(dense, "rows", lambda sly: [("pool", (sly["name"],))])
    with pytest.raises(run.BenchError, match=r"lists no rows \['pool'\]"):
        program.workload(cfg)


@pytest.mark.parametrize("step_kind", ["logits", "binarize", "flatten", "float_pool"])
def test_dense_count_needs_a_dense_step(step_kind):
    """A step of another kind that shares the head's name is no
    popcount_gemm call."""
    gemm = run.load_module(os.path.join(run.BENCH, "kernels", "popcount_gemm.py"))
    head = [dict(sly) for sly in geometry.layer_shapes(run._load_json(CONFIGS["tiny-bnn"][0]))
            if sly["kind"] == "dense"][-1]
    assert gemm.cost({"kind": "dense", "impl": None, "layers": [head]}, 8) is not None
    assert gemm.cost({"kind": step_kind, "impl": None, "layers": [head]}, 8) is None


@pytest.mark.parametrize("name", ["no_such_kind", "../run"])
def test_unknown_kind_is_a_bench_error(name):
    with pytest.raises(run.BenchError, match=r"no layer kind .*layers/"):
        kinds.kind(name)
    cfg = run._load_json(CONFIGS["tiny-bnn"][0])
    cfg["layers"][1]["kind"] = name
    with pytest.raises(run.BenchError, match=name):
        geometry.layer_shapes(cfg)
    with pytest.raises(run.BenchError, match=name):
        weights.draw_fn(cfg["layers"])


_TINY_SPEC = '''
from repro.core import workloads as W
from repro.graph.ir import from_workload


def tiny_spec():
    return from_workload(W.Workload(
        "tiny-bnn", "tiny-bnn",
        (W.ConvLayer("conv1", 3, 32, 8, 8, 8, 8, 3, True),
         W.ConvLayer("conv2", 32, 32, 8, 8, 8, 8, 3, False)),
        (W.FCLayer("fc1", 512, 64), W.FCLayer("fc2", 64, 64), W.FCLayer("fc3", 64, 10))))
'''


def test_builder_program_path_bnnspec(on_cpu, tmp_path):
    """A builder named ``module:function`` that returns a BNNSpec is
    checked against the table through the program's listing and serves
    a correct run."""
    from repro.graph.ir import BNNSpec

    (tmp_path / "tiny_model.py").write_text(_TINY_SPEC)
    on_cpu.syspath_prepend(str(tmp_path))
    cfg = run._load_json(CONFIGS["tiny-bnn"][0])
    cfg["builder"] = "tiny_model:tiny_spec"
    model = program.workload(cfg)
    assert isinstance(model, BNNSpec)
    out = run.run(_copy_cell(tmp_path, on_cpu, cfg), SEED, 1.0, False)
    assert out["correct"], out["checks"]


def test_builder_program_path_workload():
    cfg = run._load_json(CONFIGS["binarynet-cifar10"][0])
    bare = program.workload(cfg)
    cfg["builder"] = "repro.core.workloads:binarynet_cifar10"
    assert program.workload(cfg) == bare
    cfg["builder"] = "repro.core.workloads:alexnet_imagenet"
    with pytest.raises(run.BenchError, match="does not match the layer table"):
        program.workload(cfg)


@pytest.mark.parametrize("row_gap,delta,differ", [
    (None, 0.5, 1),           # no row_gap: any gap differs
    (0.5, 0.5, 0),            # a gap equal to row_gap agrees
    (0.5, 0.5 + 2**-10, 1),   # a gap just above it differs
])
def test_row_gap(row_gap, delta, differ):
    cfg = run._load_json(CONFIGS["tiny-bnn"][0])
    if row_gap is not None:
        cfg["compare"]["row_gap"] = row_gap
    pool = weights.make_images(cfg["input"], 8, SEED)
    want = reference.logits(cfg["layers"], weights.make_raw(cfg["layers"], SEED), pool, 8)
    got = want.copy()
    got[5, 3] += delta
    rec = types.SimpleNamespace(out=[got[:4], got[4:]], off=[0, 4], rows=[4, 4])
    checks = run.compare(cfg, SEED, rec, pool)
    assert checks["logit_gap_max"]["value"] == delta
    assert checks["rows_differ_pct"]["value"] == 100.0 * differ / 8
    assert checks["requests_failed"]["value"] == 0
