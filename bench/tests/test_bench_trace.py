"""The trace reduction (bench/devtrace.py): interval arithmetic and gap
labels on hand-made events, and the whole reduction on a small trace
recorded on a TPU v5e (data/small.xplane.pb.gz, gzipped: a 0.25-s window of the
binarynet-cifar10.bulk cell)."""
import os

import pytest

import devtrace as T

DATA = os.path.join(os.path.dirname(__file__), "data")


def test_union_clip_and_gaps():
    busy = T.union([(5, 7), (0, 2), (1, 3), (7, 8), (10, 12)])
    assert busy == [(0, 3), (5, 8), (10, 12)]
    assert T.clip(busy, 1, 11) == [(1, 3), (5, 8), (10, 11)]
    assert T.gaps(T.clip(busy, 1, 11), 1, 11) == [(3, 5), (8, 10)]
    assert T.gaps([], 0, 4) == [(0, 4)]


def test_op_names():
    ev = '%packed_conv2d.5 = u32[128,1024,4]{2,1,0} custom-call(u32[1] %pad.0)'
    assert T.op_name(ev) == "packed_conv2d.5"
    assert T.base_name(ev) == "packed_conv2d"
    assert T.base_name("copy-start.13") == "copy-start"
    assert T.base_name("jit_apply") == "jit_apply"


def test_reduce_on_made_events():
    ms = 1_000_000
    t_start = 1_792_000_000 * 10**9     # the session's start on time.time_ns()
    tr = {"devices": {"/device:TPU:0": [("%a.1 = x", 0, 2 * ms), ("%b.2 = y", 1 * ms, 3.5 * ms),
                                        ("%a.3 = x", 6 * ms, 7 * ms), ("%c = z", 9 * ms, 12 * ms)]},
          "start_ns": t_start}
    r = T.reduce(tr, {"a": ("a",), "nothing": ("zz",)}, window=(1 * ms, 10 * ms))
    assert r["window_s"] == pytest.approx(9e-3)
    # busy inside [1, 10] ms: [1, 3.5] + [6, 7] + [9, 10]
    assert r["busy_s"] == [pytest.approx(4.5e-3)]
    # a: only [6, 7] ran wholly inside the window
    assert r["kernel_s"] == {"a": pytest.approx(1e-3), "nothing": 0.0}
    assert r["kernel_calls"] == {"a": {"a.3": 1}, "nothing": {}}
    assert [n for n, _ in r["device_ops"]] == ["b", "a", "c"]
    # without a window, the device timeline: first op's start to last
    # op's end, busy but for the gaps [3.5, 6] and [7, 9]
    d = T.reduce(tr, {})
    assert T.device_window(tr) == (0, 12 * ms)
    assert d["window_s"] == pytest.approx(12e-3)
    assert d["busy_s"] == [pytest.approx(7.5e-3)]
    # the gaps, labelled by the host spans (on time.time_ns()) over them
    spans = [("bench.wait", t_start + 2 * ms, t_start + 10 * ms),
             ("bench.wait", t_start + 3 * ms, t_start + 6.5 * ms),
             ("bench.submit", t_start + 7.5 * ms, t_start + 8 * ms)]
    (g1, d1), (g2, d2) = T.idle_gaps(tr, spans)
    assert d1 == pytest.approx(2.5e-3) and d2 == pytest.approx(2e-3)
    assert g1 == "bench.submit 0 | bench.wait 2"
    assert g2 == "bench.submit 1 | bench.wait 1"
    assert T.idle_gaps(tr, [])[0][0] == "none"


def test_reduce_recorded_trace():
    tr = T.load(os.path.join(DATA, "small.xplane.pb.gz"))
    assert list(tr["devices"]) == ["/device:TPU:0"]
    assert tr["start_ns"] == 1792272472066393792
    kernels = {"packed_conv2d": ("packed_conv2d",), "fused_mlp": ("fused_mlp", "_lambda_")}
    t0, t1 = 57499005.0, 308535757.0   # the recorded run's window
    r = T.reduce(tr, kernels, window=(t0, t1))
    assert r["window_s"] == pytest.approx((t1 - t0) * 1e-9)
    assert 0 < r["busy_s"][0] <= r["window_s"]
    # every kernel's time is a part of the busy time, and is the sum
    # of its calls that ran wholly inside the window
    calls = [(a, b) for n, a, b in tr["devices"]["/device:TPU:0"]
             if T.base_name(n) == "packed_conv2d" and t0 <= a and b <= t1]
    assert r["kernel_s"]["packed_conv2d"] == pytest.approx(sum(b - a for a, b in calls) * 1e-9)
    # five packed convs a flight: five ops of the program, called alike
    by_op = r["kernel_calls"]["packed_conv2d"]
    assert sum(by_op.values()) == len(calls) > 5 * 40
    assert len(by_op) == 5 and max(by_op.values()) - min(by_op.values()) <= 1
    assert 0 < r["kernel_s"]["fused_mlp"] < r["kernel_s"]["packed_conv2d"] <= r["busy_s"][0]
    assert r["device_ops"][0][0] == "packed_conv2d"
    # the device timeline: every op of the trace, from the first to the last
    d0, d1 = T.device_window(tr)
    whole = T.reduce(tr, kernels)
    assert whole["window_s"] == pytest.approx((d1 - d0) * 1e-9)
    assert 0 < whole["busy_s"][0] <= whole["window_s"]
    assert sum(whole["kernel_calls"]["packed_conv2d"].values()) >= sum(by_op.values())
    idle = T.idle_gaps(tr, [("bench.wait", tr["start_ns"], tr["start_ns"] + int(d1))])
    assert len(idle) == 10
    assert sum(d for _, d in idle) <= whole["window_s"] - whole["busy_s"][0] + 1e-9
    assert all(g == "bench.wait 1" for g, _ in idle)
