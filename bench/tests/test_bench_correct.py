"""The comparison that decides ``correct``, driven through a whole run
of a small cell on the CPU (the harness's look for a chip skipped).

* a sound run comes out correct;
* the control (the reference with its entry conv input one precision
  step below the configuration's) fails the comparison;
* an answer altered where the program produces it, and on the 4-device
  data mesh one device's rows left out of the answer, come out not
  correct.
"""
import os

import pytest

import run

DATA = os.path.join(os.path.dirname(__file__), "data")
SEED = 2**31 + 4242


def _broken(monkeypatch, fault):
    """Build the server as the run does, with ``fault`` applied to every
    answer where the compiled program produces it."""
    import program

    build = program.build

    def broken_build(*a, **kw):
        cb, srv = build(*a, **kw)
        inner = srv._apply_jit

        def apply(params, x, valid_rows=None):
            return fault(inner(params, x, valid_rows=valid_rows))

        srv._apply_jit = apply
        return cb, srv

    monkeypatch.setattr(program, "build", broken_build)


@pytest.mark.parametrize("cell", ["tiny.bulk", "tiny.online", "tiny.bulk-4dev"])
def test_sound_run_is_correct(cell, on_cpu):
    out = run.run(cell, SEED, 1.0, False)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"images_per_s", "setup_s"}
    assert list(out)[-1] == "checks"


def test_control_fails():
    import reference
    import weights

    cfg = run._load_json(os.path.join(DATA, "tiny-bnn.json"))
    pool = weights.make_images(cfg["input"], 256, SEED)
    raw = weights.make_raw(cfg["layers"], SEED)
    want = reference.logits(cfg["layers"], raw, pool, 64)
    got = reference.logits(cfg["layers"], raw, pool, 64, precision="control")
    gap, bad = run.logit_gaps(got, want)
    checks = run.checks_of(gap, bad, len(pool), 0, cfg["compare"])
    assert any(c["value"] > c["limit"] for c in checks.values()), checks


def test_altered_answer_is_not_correct(on_cpu):
    _broken(on_cpu, lambda y: y.at[0, 3].add(2.0))
    out = run.run("tiny.bulk", SEED, 1.0, False)
    assert not out["correct"]
    assert out["checks"]["logit_gap_max"]["value"] == 2.0


def test_mesh_rows_left_out_is_not_correct(on_cpu):
    def drop_last_device(y):
        # the last device's quarter of the rows never reaches the answer
        q = y.shape[0] // 4
        return y.at[3 * q:].set(y[:q])

    _broken(on_cpu, drop_last_device)
    out = run.run("tiny.bulk-4dev", SEED, 1.0, False)
    assert not out["correct"]
    assert out["checks"]["rows_differ_pct"]["value"] > 0


def test_no_tpu_no_result(monkeypatch):
    monkeypatch.setattr(run, "BENCHMARK_JSON", os.path.join(DATA, "bench.json"))
    monkeypatch.setattr(run, "TRAFFIC_DIR", os.path.join(DATA, "traffic"))
    monkeypatch.setattr(run, "WORKLOADS_DIR", os.path.join(DATA, "workloads"))
    with pytest.raises(run.BenchError, match="no TPU"):
        run.run("tiny.bulk", SEED, 1.0, False)


def test_traced_run_reports_per_layer_metrics(on_cpu):
    """The --trace 1 path end to end on the CPU, with the recorded TPU
    trace standing in for the CPU's (which has no device ops)."""
    import devtrace

    recorded = devtrace.load(os.path.join(DATA, "small.xplane.pb.gz"))
    on_cpu.setattr(devtrace, "load", lambda path: recorded)
    out = run.run("tiny.bulk", SEED, 2.0, True)
    assert out["correct"]
    assert set(out["metrics"]) == {"step_mfu", "packed_conv2d_roofline"}
    assert 0 < out["metrics"]["packed_conv2d_roofline"]["value"]
    assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
    assert out["breakdown"]["device_ops"][0][0] == "packed_conv2d"
    assert len(out["breakdown"]["idle_gaps"]) == 10
    assert out["window"]["images_per_s_traced"] > 0
