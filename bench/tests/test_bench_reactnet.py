"""ReActNet-A (bench/configs/reactnet-a-imagenet.json) and its kinds
``reactnet_stem``, ``reactnet_block`` and ``pool_fc``.

* golden shapes and MACs of the published configuration: 4,828,758,016
  a 224x224 image, of which 4,816,896,000 binary, 10,838,016 in the
  stem and 1,024,000 in the head;
* the parts a block's plan steps run, and the residual_epilogue byte
  counts of each, against numbers worked by hand and against the
  program's own byte model (``CompiledBNN.traffic``) on the tiny spec;
* the program's builder lists the layer table's rows;
* a tiny ReActNet (32x32 input, widths 32 -> 64 -> 128 -> 128: a
  stride-1 doubling block, a stride-2 doubling block, a stride-1 block
  of one width) driven through bench/run.py on the CPU to ``correct``,
  and its control (the stream rounded to bfloat16) failing.
"""
import json
import os
import shutil

import numpy as np
import pytest

import geometry
import program
import reference
import run
import weights

DATA = os.path.join(os.path.dirname(__file__), "data")
CONFIG = os.path.join(run.BENCH, "configs", "reactnet-a-imagenet.json")
TINY = os.path.join(DATA, "tiny-reactnet.json")
SEED = 2**31 + 4242

# (h_in, w_in, c_in, c_out, stride, h_out) of the 13 blocks
BLOCKS = [(112, 112, 32, 64, 1, 112), (112, 112, 64, 128, 2, 56),
          (56, 56, 128, 128, 1, 56), (56, 56, 128, 256, 2, 28),
          (28, 28, 256, 256, 1, 28), (28, 28, 256, 512, 2, 14)] + \
    [(14, 14, 512, 512, 1, 14)] * 5 + \
    [(14, 14, 512, 1024, 2, 7), (7, 7, 1024, 1024, 1, 7)]

_TINY_MODEL = '''
from repro.graph.ir import residual_net


def tiny_reactnet():
    return residual_net("tiny-reactnet", (32, 32, 3), 32,
                        ((64, 1), (128, 2), (128, 1)), 10)
'''


def _tiny(tmp_path, monkeypatch):
    """The tiny configuration with its builder, a module of the test's
    own."""
    (tmp_path / "tiny_reactnet_model.py").write_text(_TINY_MODEL)
    monkeypatch.syspath_prepend(str(tmp_path))
    cfg = run._load_json(TINY)
    cfg["builder"] = "tiny_reactnet_model:tiny_reactnet"
    return cfg


def test_golden_shapes_and_macs():
    cfg = run._load_json(CONFIG)
    shapes = geometry.layer_shapes(cfg)
    stem, blocks, head = shapes[0], shapes[1:-1], shapes[-1]
    assert (stem["h_out"], stem["w_out"], stem["c_out"]) == (112, 112, 32)
    assert [(b["h_in"], b["w_in"], b["c_in"], b["c_out"], b["stride"], b["h_out"])
            for b in blocks] == BLOCKS
    assert (head["h_in"], head["n_in"], head["classes"]) == (7, 1024, 1000)
    assert geometry.final_shape(cfg) == (1000,)
    binary = sum(geometry.macs(b) for b in blocks)
    assert binary == 4_816_896_000
    assert sum(geometry.macs(pt) for b in blocks for pt in program.kind(b["kind"]).parts(b)
               ) == binary
    assert geometry.macs(stem) == 10_838_016
    assert geometry.macs(head) == 1_024_000
    assert geometry.macs_per_image(cfg) == 4_828_758_016
    w_bits = sum(9 * b[2] ** 2 + b[2] * b[3] for b in BLOCKS)
    assert w_bits == 28_253_184


def test_parts_and_epilogue_bytes():
    cfg = run._load_json(CONFIG)
    blocks = geometry.layer_shapes(cfg)[1:-1]
    gemm = run.load_module(os.path.join(run.BENCH, "kernels", "popcount_gemm.py"))
    ep = run.load_module(os.path.join(run.BENCH, "kernels", "residual_epilogue.py"))
    assert ep.NAMES == ("residual_epilogue",)
    b2 = program.kind("reactnet_block").parts(blocks[1])
    assert [(p["name"], p["kind"]) for p in b2] == [
        ("block2.conv3x3", "binary_conv"), ("block2.epilogue1", "reactnet_block"),
        ("block2.conv1x1", "binary_conv"), ("block2.epilogue2", "reactnet_block")]
    rows = 3
    steps = [{"kind": k, "impl": i, "layers": [p]} for p, (k, i) in zip(b2, [
        ("residual_conv", "im2col"), ("residual_epilogue", None),
        ("residual_conv", "im2col"), ("residual_epilogue", None)])]
    px = rows * 56 * 56
    # 64 -> 64 at stride 2 from 112x112: the patch matrix, weights and int32 sums
    assert gemm.cost(steps[0], rows) == (2.0 * 9 * 64 * 64 * px,
                                         px * 576 / 8 + 576 * 64 / 8 + 4 * px * 64)
    assert gemm.cost(steps[1], rows) is None
    # epilogue1: sums, pooled shortcut and stream of 64 channels, rows,
    # a 2-class table (even map at stride 2: no last-row class), signs
    assert ep.cost(steps[1], rows) == (12.0 * px * 64, 4 * px * 64 * 3 + 4 * 6 * 64
                                       + 4 * 2 * 56 * 64 + px * 64 / 8)
    # epilogue2: 128 channels of sums and stream, the 64-wide u twice
    assert ep.cost(steps[3], rows) == (12.0 * px * 128, 4 * px * 128 * 2 + 4 * px * 64
                                       + 4 * 6 * 128 + px * 128 / 8)
    assert ep.cost(steps[0], rows) is None
    last = program.kind("reactnet_block").parts(blocks[-1])
    assert [p["pack"] for p in last[1::2]] == [True, False]
    assert last[1]["table_rows"] == 3          # 7x7 at stride 1: first, interior, last


def test_epilogue_bytes_match_the_program_traffic(tmp_path, monkeypatch):
    """CompiledBNN.traffic() counts each epilogue as the harness does."""
    cfg = _tiny(tmp_path, monkeypatch)
    cb = _plan(cfg, 8)
    ep = run.load_module(os.path.join(run.BENCH, "kernels", "residual_epilogue.py"))
    rows = 5
    by_name = {d["name"]: d["packed_bytes"] for d in cb.traffic(rows)["layers"]}
    steps = [s for s in program.plan_steps(cb, cfg) if s["kind"] == "residual_epilogue"]
    assert len(steps) == 6
    for s in steps:
        assert ep.cost(s, rows)[1] == by_name[s["layers"][0]["name"]]


def test_builder_lists_the_table():
    from repro.graph.ir import BNNSpec, reactnet_a_imagenet

    cfg = run._load_json(CONFIG)
    model = program.workload(cfg)
    assert isinstance(model, BNNSpec) and model == reactnet_a_imagenet()
    conv = program._listing(model)["conv"]
    assert len(conv) == 1 + 2 * 13 and conv[0][-1] is True
    assert program._listing(model)["fc"] == [("fc", 1024, 1000)]


def _plan(cfg, batch):
    from repro import graph

    return graph.compile(program.workload(cfg), backend=cfg["compile"]["backend"],
                         batch=batch, conv_impl=cfg["compile"].get("conv_impl", "auto"))


def _cell(tmp_path, monkeypatch, cfg, cell="tiny-reactnet.bulk"):
    (tmp_path / "cfg.json").write_text(json.dumps(cfg))
    (tmp_path / "workloads").mkdir()
    shutil.copy(os.path.join(DATA, "workloads", "tiny.bulk.json"),
                tmp_path / "workloads" / f"{cell}.json")
    spec = run._load_json(os.path.join(DATA, "bench.json"))
    spec["configs"] = [{"name": cfg["name"], "file": str(tmp_path / "cfg.json")}]
    spec["workloads"] = [{"name": cell, "config": cfg["name"], "traffic": "closed-2x8",
                          "chips": 1}]
    monkeypatch.setattr(run, "BENCHMARK_JSON", str(tmp_path / "bench.json"))
    monkeypatch.setattr(run, "WORKLOADS_DIR", str(tmp_path / "workloads"))
    (tmp_path / "bench.json").write_text(json.dumps(spec))
    return cell


def test_tiny_run_is_correct(on_cpu, tmp_path):
    cfg = _tiny(tmp_path, on_cpu)
    out = run.run(_cell(tmp_path, on_cpu, cfg), SEED, 1.0, False)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["checks"]["logit_gap_max"]["value"] == 0.0


def test_tiny_control_fails():
    cfg = run._load_json(TINY)
    pool = weights.make_images(cfg["input"], 64, SEED)
    raw = weights.make_raw(cfg["layers"], SEED)
    want = reference.logits(cfg["layers"], raw, pool, 16)
    got = reference.logits(cfg["layers"], raw, pool, 16, precision="control")
    gap, bad = run.logit_gaps(got, want, cfg["compare"]["row_gap"])
    checks = run.checks_of(gap, bad, len(pool), 0, cfg["compare"])
    assert checks["rows_differ_pct"]["value"] > 50, checks
    assert checks["logit_gap_max"]["value"] > checks["logit_gap_max"]["limit"]


@pytest.mark.parametrize("block,call", [("block1", 1), ("block3", 3)])
def test_a_sign_flip_exceeds_row_gap(monkeypatch, block, call):
    """One sign flipped in the reference, at one position of image 0
    (the 3x3 conv's input of the first block, the 1x1 conv's input of
    the last), moves that image's widest logit gap past row_gap."""
    cfg = run._load_json(TINY)
    pool = weights.make_images(cfg["input"], 8, SEED)
    raw = weights.make_raw(cfg["layers"], SEED)
    want = reference.logits(cfg["layers"], raw, pool, 8)
    kind = program.kind("reactnet_block")
    forward, sign = kind.forward, reference.sign

    def flipped_forward(ly, p, x, precision):
        if ly["name"] != block:
            return forward(ly, p, x, precision)
        calls = []

        def flipped_sign(v):
            calls.append(v)
            out = sign(v)
            return out.at[0, 0, 0, 0].multiply(-1.0) if len(calls) == call else out

        monkeypatch.setattr(reference, "sign", flipped_sign)
        try:
            return forward(ly, p, x, precision)
        finally:
            monkeypatch.setattr(reference, "sign", sign)

    monkeypatch.setattr(kind, "forward", flipped_forward)
    got = reference.logits(cfg["layers"], raw, pool, 8)
    assert np.abs(got[0] - want[0]).max() > cfg["compare"]["row_gap"]
    np.testing.assert_array_equal(got[1:], want[1:])
