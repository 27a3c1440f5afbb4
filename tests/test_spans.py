"""The span recorder (runtime/spans.py) and its sites in BNNServer and
graph.compile (DESIGN.md §10): off it records nothing; on, one request
leaves its chain of spans with matching ids, in order, on the clock of
``time.time_ns()``; first touches, recoveries, garbage collections and
the cap are recorded as such."""
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import graph
from repro.kernels.ops import binarize_pack
from repro.robustness import ChaosMonkey
from repro.runtime import spans
from repro.serving import BackendFault, BNNServer

CHAIN = ("serve.queue", "serve.admit", "serve.ahead_wait", "serve.stage",
         "serve.enqueue", "serve.device_wait", "serve.resolve")


@pytest.fixture(autouse=True)
def recorder_off():
    spans.disable()
    yield
    spans.disable()


def _server(**kw):
    spec = graph.from_dense_stack(256, [128, 64], name="span_mlp")
    cb = graph.compile(spec, backend="xla", batch=4)
    params = cb.init(jax.random.PRNGKey(0))
    kw.setdefault("retry_backoff_s", 0.0)
    return cb, BNNServer(cb, params, max_batch=8, **kw)


def _packed(rng, rows):
    x = jnp.asarray(rng.normal(size=(rows, 256)).astype(np.float32))
    return binarize_pack(x, backend="xla")


def _serve(srv, xs):
    """Submit each payload in turn through the worker threads and wait
    for its answer."""
    srv.start()
    try:
        for x in xs:
            srv.submit(x).result(timeout=60)
    finally:
        srv.stop()


def _named(recorded, prefix):
    return [s for s in recorded if s[0].startswith(prefix)]


def test_off_records_nothing():
    spans.enable()
    spans.disable()
    _, srv = _server()
    _serve(srv, [_packed(np.random.default_rng(0), 3)])
    assert spans.collect() == []
    assert spans.dropped() == 0


def test_one_request_leaves_its_chain():
    t_before = time.time_ns()
    spans.enable()
    cb, srv = _server()
    _serve(srv, [_packed(np.random.default_rng(1), 3)])
    t_after = time.time_ns()
    got = spans.collect()
    (compile_span,) = _named(got, "setup.compile")
    assert compile_span[5] == {"steps": len(cb.plan)}
    serve = _named(got, "serve.")
    assert sorted(s[0] for s in serve) == sorted(CHAIN)
    by = {s[0]: s for s in serve}
    queue, admit, wait = by["serve.queue"], by["serve.admit"], by["serve.ahead_wait"]
    stage, enqueue = by["serve.stage"], by["serve.enqueue"]
    flight = admit[3]
    assert flight > 0 and queue[3] not in (0, flight)
    assert queue[4] == flight and admit[4] == 0
    for name in ("serve.ahead_wait", "serve.device_wait", "serve.resolve"):
        assert by[name][3:5] == (flight, 0)
    # a chunk's two spans share its id, under the flight
    assert stage[3] == enqueue[3] and stage[3] not in (0, flight, queue[3])
    assert stage[4] == enqueue[4] == flight
    assert queue[5] == {"rows": 3}
    assert admit[5] == {"requests": 1, "rows": 3}
    assert stage[5]["bucket"] == 4 and stage[5]["valid"] == 3
    assert stage[5]["bytes"] == 4 * 8 * 4       # 4 rows of 8 uint32 words
    assert enqueue[5] == {"first": 1}
    starts = [by[n][1] for n in CHAIN]
    assert starts == sorted(starts)
    assert all(by[n][1] <= by[n][2] for n in CHAIN)
    assert wait[1] >= admit[2] and by["serve.device_wait"][2] == by["serve.resolve"][1]
    # on time.time_ns(): inside the calls that made them
    slack = 5_000_000
    assert all(t_before - slack <= s[1] <= s[2] <= t_after + slack for s in got)


def test_first_marks_a_levels_first_touch_only():
    rng = np.random.default_rng(2)
    spans.enable()
    _, srv = _server()
    # 3 rows: level (4, 3) twice, then 8 rows: level (8, 8)
    _serve(srv, [_packed(rng, 3), _packed(rng, 3), _packed(rng, 8)])
    firsts = [s[5]["first"] for s in _named(spans.collect(), "serve.enqueue")]
    assert firsts == [1, 0, 1]


def test_stage_carries_flat():
    """``serve.stage`` says whether the chunk's rows moved flat: host
    NHWC image rows do, a PackedArray request does not."""
    from repro.core.workloads import ConvLayer, FCLayer, Workload

    wl = Workload("span_conv", "tiny", (
        ConvLayer("conv1", 3, 32, 8, 8, 8, 8, 3, integer=True),
        ConvLayer("conv2", 32, 32, 8, 8, 4, 4, 3, integer=False),
    ), (FCLayer("fc1", 512, 64), FCLayer("fc2", 64, 10)))
    cb = graph.compile(wl, backend="xla", batch=4)
    conv = BNNServer(cb, cb.init(jax.random.PRNGKey(0)), max_batch=8)
    _, mlp = _server()
    rng = np.random.default_rng(4)
    spans.enable()
    _serve(conv, [rng.integers(0, 256, size=(3, 8, 8, 3)).astype(np.float32)])
    (stage,) = _named(spans.collect(), "serve.stage")
    assert stage[5]["flat"] == 1 and stage[5]["bytes"] == 4 * 8 * 8 * 3 * 4
    spans.enable()
    _serve(mlp, [_packed(rng, 3)])
    (stage,) = _named(spans.collect(), "serve.stage")
    assert stage[5]["flat"] == 0


def test_backend_fault_records_recovery():
    rng = np.random.default_rng(3)
    chaos = ChaosMonkey()
    _, srv = _server(chaos=chaos)
    chaos.fail_next(BackendFault("kernel launch failed"))
    spans.enable()
    fut = srv.submit(_packed(rng, 5))
    srv.flush()
    fut.result(timeout=60)
    got = spans.collect()
    (recover,) = _named(got, "serve.recover")
    (queue,) = _named(got, "serve.queue")
    assert recover[3] == queue[4] > 0
    assert recover[5] == {"fallback": 1, "retries": 0, "bisections": 0}
    assert queue[2] <= recover[1] <= recover[2]


def test_garbage_collection_is_a_span():
    spans.enable()
    gc.collect()
    spans.disable()
    gc.collect()
    full = [s for s in _named(spans.collect(), "proc.gc") if s[5]["generation"] == 2]
    assert len(full) == 1 and full[0][1] <= full[0][2]


def test_cap_counts_drops(monkeypatch):
    monkeypatch.setattr(spans, "CAP", 3)
    spans.enable()
    for k in range(5):
        t = time.perf_counter()
        spans.record("x", t, t, k)
    assert [s[3] for s in spans.collect()] == [0, 1, 2]
    assert spans.dropped() == 2
    spans.enable()                              # a fresh recording
    assert spans.collect() == [] and spans.dropped() == 0


def test_collect_is_on_the_wall_clock():
    spans.enable()
    t = time.perf_counter()
    wall = time.time_ns()
    spans.record("x", t, t + 0.5, spans.new_id())
    (span,) = spans.collect()
    assert abs(span[1] - wall) < 2_000_000
    assert span[2] - span[1] == pytest.approx(5e8, abs=1)
