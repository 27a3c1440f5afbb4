"""The serving engine: bucketing + ragged-mask policy, trace bounds,
the continuously-batched queue (admission window, dispatch-ahead,
donation safety), and sharded-vs-single-device bit-identity
(DESIGN.md §9/§10).

Whole-net dispatch runs on backend="xla" (interpret mode is far too
slow for full networks — see tests/test_graph.py); the mesh tests need
the 4 virtual CPU devices conftest.py forces, and skip on hosts where
the flag could not land.
"""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import graph
from repro.core.workloads import ConvLayer, FCLayer, Workload
from repro.kernels.autotune import get_table
from repro.kernels.ops import binarize_pack
from repro.robustness import ChaosMonkey
from repro.serving import (BackendFault, BNNServer, bucket_for, bucket_sizes,
                           data_mesh, dispatch_grid, ensure_owned,
                           mask_levels, mask_step, pow2_ceil,
                           ragged_valid, split_rows, trace_bound)

MULTIDEV = len(jax.devices()) >= 4
needs_mesh = pytest.mark.skipif(
    not MULTIDEV, reason="needs >= 4 devices (conftest XLA flag)")


def _mlp_server(max_batch=8, mesh=None, d0=256, hidden=(128, 64),
                batch=4, **kw):
    spec = graph.from_dense_stack(d0, list(hidden), name="srv_mlp")
    cb = graph.compile(spec, backend="xla", batch=batch)
    params = cb.init(jax.random.PRNGKey(0))
    return cb, params, BNNServer(cb, params, max_batch=max_batch,
                                 mesh=mesh, **kw)


def _packed(rng, rows, d0=256):
    x = jnp.asarray(rng.normal(size=(rows, d0)).astype(np.float32))
    return binarize_pack(x, backend="xla")


def _conv_server(max_batch=8, mesh=None, **kw):
    """A small image spec (8x8x3 integer entry conv, one binary conv
    with pooling, two dense layers) behind a server."""
    wl = Workload("tiny_conv", "tiny", (
        ConvLayer("conv1", 3, 32, 8, 8, 8, 8, 3, integer=True),
        ConvLayer("conv2", 32, 32, 8, 8, 4, 4, 3, integer=False),
    ), (FCLayer("fc1", 512, 64), FCLayer("fc2", 64, 10)))
    cb = graph.compile(wl, backend="xla", batch=4)
    params = cb.init(jax.random.PRNGKey(0))
    return cb, params, BNNServer(cb, params, max_batch=max_batch,
                                 mesh=mesh, **kw)


def _images(rng, rows):
    """Host NHWC rows of 8-bit pixel values, as a client sends them."""
    return rng.integers(0, 256, size=(rows, 8, 8, 3)).astype(np.float32)


def _spy(fn, seen):
    """``fn`` with the jit's call form, recording each input's rank."""
    def apply(params, x, valid_rows=None):
        seen.append(np.ndim(x))
        return fn(params, x, valid_rows=valid_rows)
    return apply


# ------------------------------------------------------------------ #
# the audited serving contract (repro.analysis.jaxpr_audit)            #
# ------------------------------------------------------------------ #
def test_served_artifact_passes_audit():
    """The exact CompiledBNN the server wraps must satisfy the audited
    contracts: donation only on the server-owned batch input, static
    valid_rows, and a prewarm key set bounded by the dispatch grid the
    server actually uses (DESIGN.md §13)."""
    cb, _, srv = _mlp_server(max_batch=8)
    try:
        report = cb.audit(max_batch=8)
    finally:
        srv.stop()
    assert report.ok
    by_name = {c.name: c for c in report.checks}
    assert not by_name["donation"].skipped
    assert not by_name["trace-bound"].skipped
    # xla serving backend: the HBM check defers to the kernel backends
    assert by_name["int32-escape"].skipped


# ------------------------------------------------------------------ #
# bucketing + ragged-mask policy                                       #
# ------------------------------------------------------------------ #
def test_bucket_edges():
    assert bucket_for(1, 32) == 1                   # batch of one
    assert bucket_for(32, 32) == 32                 # exact pow2: itself
    assert bucket_for(8, 32) == 8
    assert bucket_for(5, 32) == 8                   # pow2 ceiling
    assert bucket_for(17, 32) == 32
    with pytest.raises(ValueError):                 # > max bucket
        bucket_for(33, 32)
    with pytest.raises(ValueError):
        pow2_ceil(0)


def test_bucket_sizes_and_trace_bound():
    assert bucket_sizes(8) == (1, 2, 4, 8)
    assert trace_bound(8) == 4
    assert trace_bound(1) == 1
    with pytest.raises(ValueError):                 # non-pow2 ceiling
        bucket_sizes(12)


def test_ragged_valid_levels():
    # eighth-bucket rounding: small buckets mask at row granularity,
    # big buckets at bucket//8 — <= 4 mask levels per bucket
    assert mask_step(8) == 1 and mask_step(64) == 8
    assert ragged_valid(3, 4) == 3
    assert ragged_valid(33, 64) == 40               # not 64
    assert ragged_valid(64, 64) == 64
    assert mask_levels(8) == (5, 6, 7, 8)
    assert mask_levels(64) == (40, 48, 56, 64)
    # a bucket only ever sees rows in (bucket/2, bucket]
    assert all(b // 2 < v <= b for b, v in dispatch_grid(64))
    assert trace_bound(8, ragged=True) == 8         # 1 + 1 + 2 + 4
    assert trace_bound(64, ragged=True) == len(dispatch_grid(64)) == 20
    with pytest.raises(ValueError):
        ragged_valid(0, 4)
    with pytest.raises(ValueError):
        ragged_valid(5, 4)


def test_split_rows_oversized():
    assert split_rows(70, 32) == [32, 32, 6]
    assert split_rows(32, 32) == [32]
    assert split_rows(3, 32) == [3]
    with pytest.raises(ValueError):
        split_rows(0, 32)


# ------------------------------------------------------------------ #
# ragged masking: bit-identity of the masked forward                   #
# ------------------------------------------------------------------ #
def test_masked_apply_bit_identical_on_valid_rows():
    """apply(params, x, valid_rows=r) == apply(params, x)[:r] exactly —
    the masked launch computes the SAME bits on valid rows and simply
    never touches the dead ones."""
    spec = graph.from_dense_stack(256, [128, 64], name="mask_mlp")
    cb = graph.compile(spec, backend="xla", batch=8)
    params = cb.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(9)
    xp = _packed(rng, 8)
    full = cb.apply(params, xp)
    for r in (1, 3, 5, 8):
        got = cb.apply(params, xp, valid_rows=r)
        np.testing.assert_array_equal(np.asarray(got.words),
                                      np.asarray(full.words)[:r])


def test_masked_apply_conv_logits_bit_identical():
    from repro.core.workloads import binarynet_cifar10
    cb = graph.compile(binarynet_cifar10(), backend="xla", batch=4)
    params = cb.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 32, 32, 3),
                          jnp.float32)
    ref = np.asarray(cb.apply(params, x))
    got = np.asarray(cb.apply(params, x, valid_rows=3))
    np.testing.assert_array_equal(got, ref[:3])


# ------------------------------------------------------------------ #
# bucketed dispatch: bit-identity + trace bound                        #
# ------------------------------------------------------------------ #
def test_ragged_batches_bit_identical_to_direct_apply():
    cb, params, srv = _mlp_server(max_batch=8)
    rng = np.random.default_rng(0)
    for rows in (1, 3, 8, 5):
        xp = _packed(rng, rows)
        ref = cb.apply(params, xp)
        got = srv.apply_batch(xp)
        assert got.length == ref.length and got.axis == ref.axis
        np.testing.assert_array_equal(np.asarray(got.words),
                                      np.asarray(ref.words))


def test_trace_count_bounded_by_dispatch_grid():
    cb, params, srv = _mlp_server(max_batch=8)
    rng = np.random.default_rng(1)
    for rows in (1, 2, 3, 4, 5, 6, 7, 8, 1, 5, 8):
        srv.apply_batch(_packed(rng, rows))
    st = srv.stats()
    assert st["buckets_traced"] == [1, 2, 4, 8]
    # ground truth from the jit cache itself, not just our bookkeeping
    assert srv.jit_traces() <= srv.trace_bound() == trace_bound(
        8, ragged=True)
    # re-dispatching every size again adds no traces, only hits
    before = srv.jit_traces()
    for rows in (1, 2, 3, 4, 5, 6, 7, 8):
        srv.apply_batch(_packed(rng, rows))
    assert srv.jit_traces() == before
    assert srv.stats()["bucket_hits"] >= 8


def test_oversized_request_chunks_through_max_batch():
    cb, params, srv = _mlp_server(max_batch=4)
    rng = np.random.default_rng(2)
    xp = _packed(rng, 11)                           # 4 + 4 + 3
    ref = cb.apply(params, xp)
    got = srv.apply_batch(xp)
    np.testing.assert_array_equal(np.asarray(got.words),
                                  np.asarray(ref.words))
    st = srv.stats()
    assert st["batches"] == 3 and st["rows"] == 11
    assert srv.jit_traces() <= trace_bound(4, ragged=True)


def test_stats_occupancy_and_traffic_accounting():
    cb, params, srv = _mlp_server(max_batch=8)
    rng = np.random.default_rng(3)
    srv.apply_batch(_packed(rng, 3))                # bucket 4, valid 3
    st = srv.stats()
    assert st["padded_rows"] == 4 and st["real_rows"] == 3
    assert st["valid_rows"] == 3                    # masked launch size
    assert st["occupancy"] == pytest.approx(0.75)
    assert st["compute_occupancy"] == pytest.approx(1.0)
    # HBM is charged at the MASKED row count, not the bucket
    assert st["hbm_bytes"] == cb.traffic(batch=3)["packed_bytes"]
    assert st["hbm_bytes_per_request"] == st["hbm_bytes"]
    assert st["latency_s"]["max"] > 0


def test_bucket_warm_prefetches_tuning_keys():
    cb, params, srv = _mlp_server(max_batch=8)
    rng = np.random.default_rng(4)
    srv.apply_batch(_packed(rng, 5))                # bucket 8, valid 5
    for key in cb.tuning_keys_for_batch(5):
        assert get_table().get(key) is not None


def test_prewarm_resolves_all_dispatch_levels():
    cb, params, srv = _mlp_server(max_batch=8, prewarm=True)
    for _, valid in dispatch_grid(8):
        for key in cb.tuning_keys_for_batch(valid):
            assert get_table().get(key) is not None


# ------------------------------------------------------------------ #
# plan reuse across buckets (no recompile)                             #
# ------------------------------------------------------------------ #
def test_tuning_keys_for_batch_matches_fresh_compile():
    """The rescaled keys must be exactly what a fresh compile at that
    batch would prefetch — the no-drift guarantee that lets the server
    reuse ONE plan across every bucket."""
    spec = graph.from_dense_stack(256, [128, 128, 64], name="kchk")
    cb = graph.compile(spec, backend="xla", batch=8)
    for b in (1, 2, 4, 8, 16):
        fresh = graph.compile(spec, backend="xla", batch=b).tuning_keys
        assert cb.tuning_keys_for_batch(b) == fresh
    assert cb.tuning_keys_for_batch(8) is cb.tuning_keys


def test_tuning_keys_for_batch_conv_spec():
    from repro.core.workloads import binarynet_cifar10
    wl = binarynet_cifar10()
    cb = graph.compile(wl, backend="xla", batch=4)
    for b in (1, 2, 8):
        fresh = graph.compile(wl, backend="xla", batch=b).tuning_keys
        assert cb.tuning_keys_for_batch(b) == fresh


def test_tuning_keys_for_batches_dedups():
    spec = graph.from_dense_stack(256, [128, 64], name="tkb")
    cb = graph.compile(spec, backend="xla", batch=8)
    keys = cb.tuning_keys_for_batches((4, 8, 8, 4))
    assert len(keys) == len(set(keys))
    want = set(cb.tuning_keys_for_batch(4)) | set(
        cb.tuning_keys_for_batch(8))
    assert set(keys) == want


# ------------------------------------------------------------------ #
# buffer donation never bites the caller                               #
# ------------------------------------------------------------------ #
def test_donation_never_invalidates_caller_buffer():
    """An exact-bucket request is the one case where the caller's own
    array would reach the donated jit slot; the server must copy it
    first (placement.ensure_owned), so the caller's PackedArray stays
    alive, unchanged, and reusable."""
    cb, params, srv = _mlp_server(max_batch=8)      # donate=True default
    rng = np.random.default_rng(10)
    xp = _packed(rng, 8)                            # rows == bucket
    before = np.asarray(xp.words).copy()
    ref = cb.apply(params, xp)
    srv.apply_batch(xp)
    np.testing.assert_array_equal(np.asarray(xp.words), before)
    got = srv.apply_batch(xp)                       # reuse is safe too
    np.testing.assert_array_equal(np.asarray(got.words),
                                  np.asarray(ref.words))


def test_ensure_owned_copies_every_leaf():
    x = jnp.arange(8, dtype=jnp.uint32)
    cp = ensure_owned({"a": x})
    assert cp["a"] is not x
    np.testing.assert_array_equal(np.asarray(cp["a"]), np.asarray(x))


# ------------------------------------------------------------------ #
# host image rows move flat; the forward restores NHWC                 #
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("path", ["full", "ragged", "coalesced",
                                  "chunked", "fallback"])
def test_host_image_rows_stage_flat(path):
    """Host NHWC requests reach the jit as [rows, H*W*C] on every path
    (an exact bucket, a padded one, several requests in one flight, an
    oversized request cut in chunks, the fallback backend), one chunk
    counted each; the answers equal ``compiled.apply`` bit for bit and
    the caller's arrays are untouched."""
    chaos = ChaosMonkey()
    cb, params, srv = _conv_server(chaos=chaos)
    seen = []
    srv._apply_jit = _spy(srv._apply_jit, seen)
    srv._fallback_fn()
    srv._fallback_jit = _spy(srv._fallback_jit, seen)
    rng = np.random.default_rng(14)
    rows = {"full": [8], "ragged": [3], "coalesced": [2, 3, 3],
            "chunked": [11], "fallback": [5]}[path]
    xs = [_images(rng, r) for r in rows]
    before = [x.copy() for x in xs]
    if path == "fallback":
        chaos.fail_next(BackendFault("kernel launch failed"))
    futs = [srv.submit(x) for x in xs]
    assert srv.flush() == 1
    for fut, x, x0 in zip(futs, xs, before):
        ref = np.asarray(cb.apply(params, jnp.asarray(x0)))
        np.testing.assert_array_equal(np.asarray(fut.result(timeout=5)), ref)
        np.testing.assert_array_equal(x, x0)
    chunks = 2 if path == "chunked" else 1
    assert seen == [2] * chunks
    assert srv.stats()["flat_staged"] == chunks
    if path == "fallback":
        assert srv.stats()["faults"]["backend_fallbacks"] == 1


def test_device_and_packed_requests_keep_their_shape():
    """A device-resident jax.Array is not reshaped outside the jit (that
    would add a device op), a flight that mixes it with host rows keeps
    NHWC, and a PackedArray is already 2-D words: none is staged flat."""
    cb, params, srv = _conv_server()
    seen = []
    srv._apply_jit = _spy(srv._apply_jit, seen)
    rng = np.random.default_rng(15)
    dev = jnp.asarray(_images(rng, 3))
    host = _images(rng, 2)
    np.testing.assert_array_equal(np.asarray(srv.apply_batch(dev)),
                                  np.asarray(cb.apply(params, dev)))
    futs = [srv.submit(dev), srv.submit(host)]
    srv.flush()
    np.testing.assert_array_equal(np.asarray(futs[1].result(timeout=5)),
                                  np.asarray(cb.apply(params, host)))
    assert seen == [4, 4]
    assert srv.stats()["flat_staged"] == 0
    mcb, mparams, msrv = _mlp_server()
    xp = _packed(rng, 3)
    np.testing.assert_array_equal(np.asarray(msrv.apply_batch(xp).words),
                                  np.asarray(mcb.apply(mparams, xp).words))
    assert msrv.stats()["flat_staged"] == 0


def test_flat_staging_keeps_one_trace_per_level():
    """Host rows staged flat take one jit trace per (bucket, valid)
    level, as device-resident requests of the same rows do."""
    rng = np.random.default_rng(16)
    sizes = (8, 3, 5, 8, 3, 1)                      # levels (8,8) (4,3) (8,5) (1,1)
    xs = [_images(rng, r) for r in sizes]
    _, _, host_srv = _conv_server()
    _, _, dev_srv = _conv_server()
    for x in xs:
        host_srv.apply_batch(x)
        dev_srv.apply_batch(jnp.asarray(x))
    assert host_srv.stats()["flat_staged"] == len(sizes)
    assert dev_srv.stats()["flat_staged"] == 0
    assert host_srv.jit_traces() == dev_srv.jit_traces() == 4
    assert host_srv.stats()["buckets_traced"] == [1, 4, 8]
    assert host_srv.stats()["bucket_hits"] == 2


# ------------------------------------------------------------------ #
# the continuously-batched queue                                       #
# ------------------------------------------------------------------ #
def test_queue_drain_bursty_arrival():
    cb, params, srv = _mlp_server(max_batch=8)
    rng = np.random.default_rng(5)
    sizes = (2, 2, 2, 2, 5, 3, 8, 1)
    xs = [_packed(rng, r) for r in sizes]
    refs = [cb.apply(params, x) for x in xs]
    futs = [srv.submit(x) for x in xs]              # burst, no worker
    assert srv.queue_depth() == len(sizes)
    n_micro = srv.flush()
    assert srv.queue_depth() == 0
    # FIFO coalescing packed the burst into fewer dispatches
    assert n_micro < len(sizes)
    for fut, ref in zip(futs, refs):
        got = fut.result(timeout=5)
        np.testing.assert_array_equal(np.asarray(got.words),
                                      np.asarray(ref.words))
    st = srv.stats()
    assert st["requests"] == len(sizes)
    assert st["latency_s"]["mean"] > 0
    assert st["queue_wait_s"]["p50"] >= 0


def test_mismatched_request_does_not_fail_neighbors():
    """Only same-kind payloads coalesce: a malformed request (wrong
    input width for the spec) fails alone; the valid requests around
    it still resolve."""
    cb, params, srv = _mlp_server(max_batch=8)
    rng = np.random.default_rng(8)
    good1, bad, good2 = _packed(rng, 2), _packed(rng, 2, d0=64), \
        _packed(rng, 2)
    refs = [cb.apply(params, good1), cb.apply(params, good2)]
    f1, fb, f2 = srv.submit(good1), srv.submit(bad), srv.submit(good2)
    srv.flush()
    for fut, ref in zip((f1, f2), refs):
        np.testing.assert_array_equal(np.asarray(fut.result(timeout=5).words),
                                      np.asarray(ref.words))
    with pytest.raises(Exception):
        fb.result(timeout=5)


def test_admission_joins_open_batch_only_while_device_busy():
    """The continuous-batching policy: a partial batch launches
    immediately when nothing is in flight (waiting would serialize),
    but while the device is busy the not-yet-launched batch stays open
    and a late-arriving request joins it instead of starting fresh."""
    cb, params, srv = _mlp_server(max_batch=8)
    srv.admit_window_s = 0.5
    rng = np.random.default_rng(11)
    # device idle: partial batch comes back at once, window unpaid
    srv.submit(_packed(rng, 2))
    t0 = time.perf_counter()
    taken = srv._admit()
    assert len(taken) == 1 and taken[0].rows == 2
    assert time.perf_counter() - t0 < 0.25
    # device busy: a row submitted mid-window joins the open batch
    srv._inflight_n = 1
    try:
        srv.submit(_packed(rng, 2))
        late = threading.Thread(
            target=lambda: (time.sleep(0.05),
                            srv.submit(_packed(rng, 3))))
        late.start()
        taken = srv._admit()
        late.join()
    finally:
        srv._inflight_n = 0
    assert len(taken) == 2
    assert sum(r.rows for r in taken) == 5
    assert srv.queue_depth() == 0


def test_worker_thread_async_dispatch():
    cb, params, srv = _mlp_server(max_batch=8)
    rng = np.random.default_rng(6)
    srv.start()
    try:
        sizes = (1, 4, 3, 8, 2)
        xs = [_packed(rng, r) for r in sizes]
        refs = [cb.apply(params, x) for x in xs]
        futs = [srv.submit(x) for x in xs]
        for fut, ref in zip(futs, refs):
            got = fut.result(timeout=60)
            np.testing.assert_array_equal(np.asarray(got.words),
                                          np.asarray(ref.words))
    finally:
        srv.stop()
    assert srv.queue_depth() == 0
    assert srv.jit_traces() <= srv.trace_bound()


def test_queue_wait_counts_the_wait_for_a_dispatch_slot():
    """A request's queue wait runs until its flight holds a
    dispatch-ahead slot: with the one slot held, the wait shows."""
    cb, params, srv = _mlp_server(max_batch=8, dispatch_ahead=1)
    rng = np.random.default_rng(13)
    srv.start()
    try:
        srv._ahead_sem.acquire()
        fut = srv.submit(_packed(rng, 3))
        time.sleep(0.2)
        srv._ahead_sem.release()
        fut.result(timeout=60)
    finally:
        srv.stop()
    assert srv.stats()["queue_wait_s"]["max"] >= 0.2


def test_stop_resolves_batches_in_flight():
    """stop() with work queued and batches in flight: every future
    resolves before stop returns, the in-flight gauge drops to zero,
    and the server restarts cleanly."""
    cb, params, srv = _mlp_server(max_batch=4)
    rng = np.random.default_rng(12)
    xs = [_packed(rng, 3) for _ in range(6)]
    refs = [cb.apply(params, x) for x in xs]
    srv.start()
    futs = [srv.submit(x) for x in xs]
    srv.stop()
    for fut, ref in zip(futs, refs):
        assert fut.done()
        np.testing.assert_array_equal(np.asarray(fut.result().words),
                                      np.asarray(ref.words))
    st = srv.stats()
    assert st["inflight_batches"] == 0
    assert st["inflight_peak"] >= 1
    assert st["queue_depth"] == 0
    assert {"p50", "p95", "p99"} <= set(st["latency_s"])
    assert {"p50", "p95", "p99"} <= set(st["queue_wait_s"])
    srv.start()                                     # restart after stop
    fut = srv.submit(xs[0])
    np.testing.assert_array_equal(np.asarray(fut.result(timeout=60).words),
                                  np.asarray(refs[0].words))
    srv.stop()


# ------------------------------------------------------------------ #
# sharded vs single-device bit-identity                                #
# ------------------------------------------------------------------ #
@needs_mesh
def test_sharded_packed_words_bit_identical():
    mesh = data_mesh()
    cb, params, _ = _mlp_server()
    srv_mesh = BNNServer(cb, params, max_batch=8, mesh=mesh)
    srv_one = BNNServer(cb, params, max_batch=8, mesh=None)
    rng = np.random.default_rng(7)
    for rows in (1, 2, 3, 4, 8, 11):                # incl. non-divisible
        xp = _packed(rng, rows)
        a = srv_mesh.apply_batch(xp)
        b = srv_one.apply_batch(xp)
        np.testing.assert_array_equal(np.asarray(a.words),
                                      np.asarray(b.words))
    assert srv_mesh.stats()["devices"] == mesh.size


@needs_mesh
def test_sharded_binarynet_logits_bit_identical():
    """The acceptance gate: BinaryNet through a 4-virtual-device data
    mesh equals the single-device compiled apply EXACTLY, with the
    trace count pinned to one per (bucket, valid) level."""
    from repro.core.workloads import binarynet_cifar10
    cb = graph.compile(binarynet_cifar10(), backend="xla", batch=4)
    params = cb.init(jax.random.PRNGKey(0))
    srv = BNNServer(cb, params, max_batch=4, mesh=data_mesh())
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 32, 32, 3),
                          jnp.float32)
    ref = cb.apply(params, x)
    got = srv.apply_batch(x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    assert srv.jit_traces() <= 1


@needs_mesh
def test_sharded_host_image_rows_bit_identical():
    """Host NHWC rows staged flat through a 4-virtual-device data mesh:
    each device restores its own rows inside the sharded forward, and
    the logits equal the single-device compiled apply exactly."""
    cb, params, srv = _conv_server(mesh=data_mesh())
    rng = np.random.default_rng(17)
    for rows in (1, 3, 4, 8, 11):                   # incl. non-divisible
        x = _images(rng, rows)
        np.testing.assert_array_equal(np.asarray(srv.apply_batch(x)),
                                      np.asarray(cb.apply(params, x)))
    assert srv.stats()["flat_staged"] == 6          # 11 rows: two chunks
