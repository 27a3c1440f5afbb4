"""BNNServer: continuously-batched, sharded, fault-tolerant serving
over compile() (DESIGN.md §9 bucketing/sharding, §10 continuous
batching, §11 failure handling).

The server wraps one :class:`~repro.graph.compile.CompiledBNN` + its
bound parameters with the things a deployment needs that the compiler
does not provide:

* **bucketed jit reuse with ragged masking** — request batches are
  right-padded to pow2 buckets (serving/bucketing.py) but dispatched
  with a *static row-validity count* (``CompiledBNN.apply(...,
  valid_rows=)``), so a 33-row batch on the 64 bucket launches a
  40-row GEMM grid, not a 64-row one; the jit trace count stays
  bounded by ``trace_bound(max_batch, ragged=True)`` and the compiled
  *plan* is reused across every (bucket, valid) level (autotune keys
  prefetched through ``CompiledBNN.tuning_keys_for_batch``);
* **data-parallel sharding** — inputs are placed with their batch axis
  over the mesh "data" axis (PackedArray ``words`` leaf included) and
  parameters replicated (serving/placement.py); results are
  bit-identical to single-device execution;
* **continuous batching with dispatch-ahead** — ``submit`` returns a
  future; the background dispatcher admits queued rows into a
  not-yet-launched in-flight batch, holds the batch open for a short
  admission window ONLY while the device is already busy (so the wait
  is overlapped, never added to latency), and enqueues batch ``k+1``'s
  device computation while batch ``k`` is still executing — jax
  dispatch is asynchronous, and only the completer thread ever calls
  ``block_until_ready``, at future-resolution time.  Up to
  ``dispatch_ahead`` launched batches may be in flight at once;
* **buffer donation** — the dispatch jit donates its input buffer
  (``CompiledBNN.serving_jit_kwargs``), letting XLA reuse the
  allocation on backends that honor donation; the server only ever
  donates buffers it owns (padding/coalescing create them; an
  exact-bucket caller array is defensively copied first —
  ``placement.ensure_owned``), so a caller-held array is never
  invalidated;
* **fault tolerance** (serving/errors.py taxonomy) — the queue is
  bounded (``max_queue_rows``, rejecting with ``ServerOverloaded``);
  requests carry optional deadlines and are shed with
  ``RequestTimeout`` *before* launch; a failed flight climbs a
  recovery ladder — re-execute on the bit-identical fallback backend
  for backend faults, bounded retry with exponential backoff for
  transients, then bisect-and-retry halves so exactly the poison
  request(s) fail with ``PoisonRequest`` while healthy co-batched
  neighbors still resolve.  A supervisor thread restarts a dispatcher
  or completer loop that dies before its clean exit point, and
  ``health()`` is the readiness probe.  The invariant: every submitted
  Future resolves with a value or a typed error — never strands;
* **observability** — ``stats()`` reports request/row/batch counters,
  bucket reuse, trace counts vs the policy bound, padded-vs-valid-vs-
  real occupancy, HBM bytes from ``CompiledBNN.traffic``, an
  ``inflight_batches`` gauge, p50/p95/p99 queue-wait and end-to-end
  latency percentiles, the fault/recovery counters, and the straggler
  watchdog's flags (runtime/straggler.py fed per-flight wall times);
  with the span recorder on (runtime/spans.py), each boundary a
  request and its flight cross is a span (DESIGN.md §10).

Inputs are float ``[B, H, W, C]`` arrays for image specs or
``PackedArray [B, K]`` (packed on the last axis) for dense-entry
specs; outputs keep the compiled pipeline's type (float logits or a
PackedArray), always sliced back to the request's true row count.
Host (numpy) image rows move to the device flat, as ``[B, H*W*C]``,
and the jitted forward restores NHWC (DESIGN.md §10, "Staging host
image rows").
"""

from __future__ import annotations

import threading
import time
import warnings
from collections import deque
from concurrent.futures import Future
from queue import Empty, Queue
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import autotune
from repro.kernels.packed import PackedArray
from repro.runtime import spans
from repro.runtime.straggler import StepWatchdog, WatchdogConfig
from repro.serving.bucketing import (
    bucket_for,
    dispatch_grid,
    pow2_ceil,
    ragged_valid,
    split_rows,
    trace_bound,
)
from repro.serving.errors import (
    BackendFault,
    PoisonRequest,
    RequestTimeout,
    ServerOverloaded,
    ServingError,
)
from repro.serving.placement import (
    data_parallel,
    ensure_owned,
    replicate,
    shard_batch,
)

__all__ = ["BNNServer"]


def _filter_donation_warning() -> None:
    """Donation is best-effort: backends that cannot alias a donated
    buffer (CPU, or shape-mismatched outputs) ignore it with a
    UserWarning per dispatch — pure noise at serving rates.  Filtered
    at server construction (not import, and not once-per-process: test
    harnesses reset the global filter list between tests)."""
    warnings.filterwarnings("ignore", message="Some donated buffers were not usable")


def _rows_of(x: Any) -> int:
    """Leading-axis row count of a request payload."""
    if isinstance(x, PackedArray):
        return int(x.words.shape[0])
    return int(np.shape(x)[0])


def _pad_rows(x: Any, rows: int) -> Any:
    """Right-pad the batch axis to ``rows`` with zeros (zero words are
    all-(-1) under pm1; pad rows are masked off by ``valid_rows`` and
    never reach a kernel).  Returns ``x`` itself when already sized."""
    n = _rows_of(x)
    if n == rows:
        return x
    if isinstance(x, PackedArray):
        pads = [(0, rows - n)] + [(0, 0)] * (x.words.ndim - 1)
        return x.with_words(jnp.pad(x.words, pads))
    pads = [(0, rows - n)] + [(0, 0)] * (np.ndim(x) - 1)
    return jnp.pad(jnp.asarray(x), pads)


def _slice_rows(x: Any, start: int, stop: int) -> Any:
    if isinstance(x, PackedArray):
        return x.with_words(x.words[start:stop])
    return x[start:stop]


def _concat_rows(xs: Sequence[Any]) -> Any:
    """Concatenate request payloads along the batch axis (PackedArray
    metadata must agree — same spec, so it always does)."""
    if len(xs) == 1:
        return xs[0]
    first = xs[0]
    if isinstance(first, PackedArray):
        meta = (first.length, first.axis, first.values)
        for x in xs[1:]:
            if (x.length, x.axis, x.values) != meta:
                raise ValueError("cannot coalesce differently-laid-out rows")
        return first.with_words(jnp.concatenate([x.words for x in xs], axis=0))
    return jnp.concatenate([jnp.asarray(x) for x in xs], axis=0)


def _flat_rows(x: Any, row_shape: Tuple[int, ...]) -> Optional[np.ndarray]:
    """A host image batch as ``[rows, H*W*C]`` (a free view of a
    contiguous array), or None where ``x`` is not one: a device-resident
    ``jax.Array`` (reshaping it outside the jit would add a device op),
    a PackedArray (already 2-D words), or rows of another shape.  A 4-D
    host array's transfer pays for the chip's tiled layout of its minor
    dims (W x C); the same bytes move flat in well under half the time
    (DESIGN.md §10)."""
    if isinstance(x, np.ndarray) and x.ndim > 2 and x.shape[1:] == row_shape:
        return x.reshape(x.shape[0], -1)
    return None


def _restore_rows(apply: Any, row_shape: Tuple[int, ...]) -> Any:
    """``apply(params, x, valid_rows)`` that first reshapes rows staged
    flat back to ``row_shape``: an exact reshape, the forward's first op
    (inside ``data_parallel``, each device restores its own rows)."""
    if len(row_shape) < 2:
        return apply

    def run(params: Any, x: Any, valid_rows: Optional[int] = None) -> Any:
        if not isinstance(x, PackedArray) and x.ndim == 2:
            x = x.reshape((x.shape[0],) + row_shape)
        return apply(params, x, valid_rows)

    return run


def _kind_of(x: Any) -> Tuple:
    """The shape-minus-batch signature a jit trace is keyed on."""
    if isinstance(x, PackedArray):
        return ("packed", x.words.shape[1:], x.length, x.axis, x.values)
    dt = getattr(x, "dtype", None)
    if dt is None:
        dt = jnp.asarray(x).dtype
    return ("dense", tuple(np.shape(x)[1:]), str(dt))


def _nbytes(x: Any) -> int:
    """Bytes of a staged payload's array leaves."""
    return sum(int(leaf.nbytes) for leaf in jax.tree.leaves(x))


def _pcts(samples: List[float]) -> Dict[str, float]:
    """mean/p50/p95/p99/max of a non-empty pre-sorted sample list."""
    n = len(samples)

    def pct(q: float) -> float:
        return float(samples[min(n - 1, int(q * n))])

    return {
        "mean": float(np.mean(samples)),
        "p50": pct(0.50),
        "p95": pct(0.95),
        "p99": pct(0.99),
        "max": float(samples[-1]),
    }


def _is_kill(e: BaseException) -> bool:
    """A chaos-injected thread kill.  robustness/chaos.py raises it as
    a BaseException precisely so the ordinary ``except Exception``
    recovery paths cannot swallow it; matched by name so the server
    never imports the chaos layer (no serving -> robustness cycle)."""
    return type(e).__name__ == "ThreadKill"


def _is_backend_fault(e: BaseException) -> bool:
    """Classify a flight failure as the *backend* failing (kernel
    launch / runtime fault) rather than the payload: these re-execute
    on the fallback backend.  Matched narrowly — payload errors
    (shape/value problems) must reach bisection instead."""
    if isinstance(e, BackendFault):
        return True
    mod = type(e).__module__ or ""
    return "XlaRuntimeError" in type(e).__name__ or mod.startswith("jaxlib")


def _is_retryable(e: BaseException) -> bool:
    """Deterministic payload errors re-raise identically — retrying
    them wastes device time; anything else may be transient."""
    return not isinstance(e, (ValueError, TypeError))


class _Request:
    __slots__ = ("x", "rows", "kind", "future", "t_enqueue", "deadline",
                 "flight")

    def __init__(
        self,
        x: Any,
        rows: int,
        kind: Tuple,
        future: Future,
        t_enqueue: float,
        deadline: Optional[float] = None,
    ):
        self.x = x
        self.rows = rows
        self.kind = kind
        self.future = future
        self.t_enqueue = t_enqueue
        self.deadline = deadline
        self.flight = 0         # the span id of its flight (0: none)

    def expired(self, now: float) -> bool:
        return self.deadline is not None and now >= self.deadline


class _Flight:
    """One launched-but-unresolved micro-batch: its admitted requests
    and the (async, not yet block_until_ready'd) chunk outputs."""

    __slots__ = ("reqs", "outs", "t_launch")

    def __init__(
        self, reqs: List[_Request], outs: List[Tuple[Any, int]], t_launch: float
    ):
        self.reqs = reqs
        self.outs = outs
        self.t_launch = t_launch


class BNNServer:
    """Serving front door over a compiled BNN (see module docstring).

    compiled: the CompiledBNN to serve; params: its bound parameter
    tree (replicated onto ``mesh`` at construction); max_batch: bucket
    ceiling, rounded up to a power of two; mesh: a jax Mesh with a
    "data" axis for data-parallel dispatch, or None for single-device;
    donate: donate the per-dispatch input buffer to XLA (safe — the
    server never donates caller-held arrays); dispatch_ahead: max
    launched-but-unresolved batches the dispatcher may run ahead of the
    completer; admit_window_s: how long a partial batch may be held
    open for late-arriving rows WHILE the device is busy (a partial
    batch launches immediately when the device is idle); prewarm:
    resolve the autotune keys for every (bucket, valid) dispatch level
    at construction instead of on first touch.

    Robustness knobs (DESIGN.md §11): max_queue_rows bounds the queue
    (None: unbounded; ``submit`` raises ServerOverloaded past it);
    fallback_backend names the backend a backend-faulted flight
    re-executes on (None disables fallback); max_retries/
    retry_backoff_s bound the transient-fault retry ladder (backoff
    doubles per attempt); chaos is a fault-injection hook (duck-typed:
    ``on_flight(payloads, fallback=)`` before every execution and
    ``maybe_kill(role)`` in the worker loops — see
    repro.robustness.chaos.ChaosMonkey); watchdog_cfg configures the
    straggler StepWatchdog fed per-flight wall times;
    supervise_interval_s is the supervisor's liveness-check period.
    """

    def __init__(
        self,
        compiled: Any,
        params: Dict[str, Any],
        max_batch: int = 32,
        mesh: Optional[Any] = None,
        donate: bool = True,
        dispatch_ahead: int = 2,
        admit_window_s: float = 0.002,
        prewarm: bool = False,
        max_queue_rows: Optional[int] = 65536,
        fallback_backend: Optional[str] = "xla",
        max_retries: int = 2,
        retry_backoff_s: float = 0.05,
        chaos: Any = None,
        watchdog_cfg: Optional[WatchdogConfig] = None,
        supervise_interval_s: float = 0.05,
    ):
        if dispatch_ahead < 1:
            raise ValueError(f"dispatch_ahead must be >= 1, got {dispatch_ahead}")
        if max_queue_rows is not None and max_queue_rows < 1:
            raise ValueError(f"max_queue_rows must be >= 1, got {max_queue_rows}")
        if max_retries < 0:
            raise ValueError(f"max_retries must be >= 0, got {max_retries}")
        self.compiled = compiled
        self.mesh = mesh
        self.max_batch = pow2_ceil(max_batch)
        self.donate = donate
        self.dispatch_ahead = dispatch_ahead
        self.admit_window_s = admit_window_s
        self.max_queue_rows = max_queue_rows
        self.fallback_backend = fallback_backend
        self.max_retries = max_retries
        self.retry_backoff_s = retry_backoff_s
        self.supervise_interval_s = supervise_interval_s
        self.params = replicate(params, mesh)
        if donate:
            _filter_donation_warning()
        self._row_shape = tuple(compiled.spec.input_shape)
        self._apply_jit = jax.jit(
            data_parallel(_restore_rows(compiled.apply, self._row_shape), mesh),
            **compiled.serving_jit_kwargs(donate),
        )
        self._chaos = chaos
        self._watchdog = StepWatchdog(watchdog_cfg or WatchdogConfig())
        self._fallback_jit = None
        self._fallback_lock = threading.Lock()
        self._traced: set = set()
        self._queue: deque = deque()
        self._qlock = threading.Lock()
        self._trace_lock = threading.Lock()
        self._stats_lock = threading.Lock()
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._worker: Optional[threading.Thread] = None
        self._completer: Optional[threading.Thread] = None
        self._supervisor: Optional[threading.Thread] = None
        self._sup_stop = threading.Event()
        self._dispatcher_exited = False
        self._completer_done = False
        self._launched: Queue = Queue()
        self._ahead_sem = threading.Semaphore(dispatch_ahead)
        self._latencies: deque = deque(maxlen=2048)
        self._queue_waits: deque = deque(maxlen=2048)
        self._traffic_cache: Dict[int, int] = {}
        self._queued_rows = 0
        self._n_requests = 0
        self._n_rows = 0
        self._n_batches = 0
        self._bucket_hits = 0
        self._bucket_misses = 0
        self._padded_rows = 0
        self._valid_rows = 0
        self._real_rows = 0
        self._hbm_bytes = 0
        self._flat_staged = 0
        self._inflight_n = 0
        self._inflight_peak = 0
        self._flight_faults = 0
        self._backend_fallbacks = 0
        self._retries = 0
        self._bisections = 0
        self._poisoned = 0
        self._timeouts = 0
        self._rejected = 0
        self._thread_restarts = 0
        if prewarm:
            levels = sorted({v for _, v in dispatch_grid(self.max_batch)})
            autotune.warm(compiled.tuning_keys_for_batches(levels))

    # -- the bucketed, masked, sharded dispatch core ----------------- #
    def trace_bound(self) -> int:
        """Max jit traces this server can ever take per input kind:
        one per (bucket, ragged-valid) level."""
        return trace_bound(self.max_batch, ragged=True)

    def jit_traces(self) -> int:
        """Ground-truth trace count of the single jitted apply (falls
        back to the server's own bookkeeping off-jax)."""
        cache_size = getattr(self._apply_jit, "_cache_size", None)
        if cache_size is not None:
            return int(cache_size())
        return len(self._traced)

    def _warm(self, valid: int) -> None:
        """First touch of a (bucket, valid) level: prefetch every
        launch's autotune key at the masked row count — same plan, M
        rescaled (no recompile of the plan)."""
        autotune.warm(self.compiled.tuning_keys_for_batch(valid))

    def _inflight(self) -> int:
        with self._stats_lock:
            return self._inflight_n

    def _fallback_fn(self):
        """The degraded-path jit, built lazily on first backend fault:
        the same spec recompiled for ``fallback_backend``
        (``CompiledBNN.with_backend`` — bit-identical by the backend
        registry contract), jitted WITHOUT donation so a re-execution
        can never consume a buffer twice."""
        with self._fallback_lock:
            if self._fallback_jit is None:
                fb = self.compiled.with_backend(self.fallback_backend)
                self._fallback_jit = jax.jit(
                    data_parallel(_restore_rows(fb.apply, self._row_shape), self.mesh),
                    **fb.serving_jit_kwargs(donate=False),
                )
            return self._fallback_jit

    def _run(
        self,
        x: Any,
        bucket: int,
        valid: int,
        owned: bool,
        flat: bool,
        fallback: bool = False,
        flight: int = 0,
        first: bool = False,
    ) -> Any:
        """Pad to the bucket, place on the mesh, and ENQUEUE the masked
        forward — asynchronous: the caller decides when (and on which
        thread) to block.  The donated input slot only ever sees a
        server-owned buffer: padding and placement create fresh ones,
        and the one aliasing case (exact-bucket rows arriving in a
        caller-held array) is defensively copied.  The fallback path
        never donates at all (its jit has no donate_argnums).  ``flat``:
        the rows are host image rows staged as ``[rows, H*W*C]``
        (``_stage``), which the jitted forward reshapes back.

        With the span recorder on, the chunk's host preparation and
        host-to-device copy is a ``serve.stage`` span (``flat``) and the
        jit call a ``serve.enqueue`` span (``first``: the level's first
        touch, so the call traces and compiles or loads from the cache)."""
        t0 = time.perf_counter() if spans.on else 0.0
        xp = _pad_rows(x, bucket)
        if fallback:
            fn = self._fallback_fn()
        else:
            fn = self._apply_jit
            if self.donate and xp is x and not owned:
                xp = ensure_owned(xp)
        xs = shard_batch(xp, self.mesh)
        if not t0:
            return fn(self.params, xs, valid_rows=valid)
        t1 = time.perf_counter()
        out = fn(self.params, xs, valid_rows=valid)
        chunk = spans.new_id()
        spans.record("serve.stage", t0, t1, chunk, flight, bucket=bucket,
                     valid=valid, bytes=_nbytes(xs), flat=int(flat))
        spans.record("serve.enqueue", t1, time.perf_counter(), chunk, flight,
                     first=int(first))
        return out

    def _launch(
        self,
        x: Any,
        rows: int,
        owned: bool,
        kind: Tuple,
        flat: bool,
        fallback: bool = False,
        flight: int = 0,
    ) -> Any:
        """Async-dispatch one micro-batch at its (bucket, valid) level;
        returns the UNRESOLVED output (``valid`` >= ``rows`` rows).
        A level is keyed on the caller's payload ``kind`` and on
        ``flat`` (rows staged flat are a program of their own).

        Only a level's FIRST dispatch holds the trace lock across the
        jit call (tracing happens inside the call, so concurrent first
        touches cannot double-trace and the per-level bound holds);
        warm levels dispatch lock-free — jax dispatch is thread-safe —
        so one slow batch never head-of-line blocks unrelated callers.
        Fallback dispatches skip the trace-set bookkeeping: they are a
        different jit whose trace count the bucketing bound does not
        govern (same bounded level set, though)."""
        bucket = bucket_for(rows, self.max_batch)
        valid = ragged_valid(rows, bucket)
        hit: Optional[bool] = None
        if fallback:
            out = self._run(x, bucket, valid, owned, flat, fallback=True, flight=flight)
        else:
            key = (bucket, valid, kind, flat)
            with self._trace_lock:
                hit = key in self._traced
                if not hit:
                    self._warm(valid)
                    out = self._run(x, bucket, valid, owned, flat, flight=flight,
                                    first=True)
                    self._traced.add(key)
            if hit:
                out = self._run(x, bucket, valid, owned, flat, flight=flight)
        with self._stats_lock:
            if hit is True:
                self._bucket_hits += 1
            elif hit is False:
                self._bucket_misses += 1
            self._n_batches += 1
            self._padded_rows += bucket
            self._valid_rows += valid
            self._real_rows += rows
            self._hbm_bytes += self._level_traffic(valid)
            self._flat_staged += flat
        return out

    def _launch_chunks(
        self,
        x: Any,
        rows: int,
        multi: bool,
        kind: Tuple,
        flat: bool,
        fallback: bool = False,
        flight: int = 0,
    ) -> List[Tuple[Any, int]]:
        """Async-launch a staged payload (``_stage``) as max_batch
        chunks + remainder; returns [(unresolved out, chunk rows)].
        ``multi``: the payload was coalesced from several requests
        (already server-owned); ``kind``: the callers' payload kind;
        ``flight``: the span id the chunks' spans name as parent."""
        outs: List[Tuple[Any, int]] = []
        chunks = split_rows(rows, self.max_batch)
        off = 0
        for chunk in chunks:
            piece = x if len(chunks) == 1 else _slice_rows(x, off, off + chunk)
            owned = multi or len(chunks) > 1
            out = self._launch(piece, chunk, owned, kind, flat, fallback, flight)
            outs.append((out, chunk))
            off += chunk
        return outs

    def _stage(self, xs: Sequence[Any]) -> Tuple[Any, bool]:
        """Coalesce request payloads into one batch; returns (batch,
        flat).  Host image rows (``_flat_rows``) are staged flat, so
        padding, coalescing and the host-to-device copy all move 2-D
        rows, and the jitted forward restores NHWC (``_restore_rows``);
        any other payload, or a mix, keeps its shape."""
        flat = [_flat_rows(x, self._row_shape) for x in xs]
        if all(f is not None for f in flat):
            return _concat_rows(flat), True
        return _concat_rows(xs), False

    def _finish_chunks(self, outs: List[Tuple[Any, int]]) -> Any:
        """Resolve launched chunks (block_until_ready) and reassemble
        the true-row-count result."""
        parts = []
        for out, chunk in outs:
            jax.block_until_ready(out)
            parts.append(_slice_rows(out, 0, chunk))
        return parts[0] if len(parts) == 1 else _concat_rows(parts)

    def _level_traffic(self, valid: int) -> int:
        b = self._traffic_cache.get(valid)
        if b is None:
            b = int(self.compiled.traffic(batch=valid)["packed_bytes"])
            self._traffic_cache[valid] = b
        return b

    def apply_batch(self, x: Any) -> Any:
        """Synchronous bucketed+masked+sharded forward of one request
        batch (chunked through ``max_batch`` when larger);
        bit-identical to ``compiled.apply(params, x)``."""
        rows = _rows_of(x)
        t0 = time.perf_counter()
        xs, flat = self._stage([x])
        out = self._finish_chunks(
            self._launch_chunks(xs, rows, False, _kind_of(x), flat))
        with self._stats_lock:
            self._n_requests += 1
            self._n_rows += rows
            self._latencies.append(time.perf_counter() - t0)
        return out

    # -- the continuous-batching request queue ----------------------- #
    def submit(self, x: Any, deadline_s: Optional[float] = None) -> Future:
        """Enqueue one request batch; the returned future resolves to
        the sliced result once a micro-batch containing it completes.
        The row count and kind signature are computed HERE so a payload
        the server cannot even inspect fails fast in the caller, never
        in the worker loop.

        deadline_s bounds how long the request may wait: a request
        whose deadline passes before its flight launches is shed
        without touching the device and its future resolves with
        RequestTimeout.  Raises ServerOverloaded (without enqueueing)
        when admission would push the queue past max_queue_rows."""
        now = time.perf_counter()
        deadline = None if deadline_s is None else now + deadline_s
        req = _Request(x, _rows_of(x), _kind_of(x), Future(), now, deadline)
        with self._qlock:
            full = (
                self.max_queue_rows is not None
                and self._queued_rows + req.rows > self.max_queue_rows
            )
            if not full:
                self._queue.append(req)
                self._queued_rows += req.rows
        if full:
            with self._stats_lock:
                self._rejected += 1
            raise ServerOverloaded(
                f"admitting {req.rows} rows would exceed "
                f"max_queue_rows={self.max_queue_rows}"
            )
        self._wake.set()
        return req.future

    def queue_depth(self) -> int:
        with self._qlock:
            return len(self._queue)

    def _take_microbatch(self) -> List[_Request]:
        """Pop a FIFO run of requests whose rows coalesce under
        ``max_batch`` (an oversized head request comes back alone and
        is chunked by ``_launch_chunks``)."""
        taken: List[_Request] = []
        self._pop_run(taken, 0, None)
        return taken

    def _pop_run(
        self, taken: List[_Request], total: int, kind: Optional[Tuple]
    ) -> Tuple[int, Optional[Tuple], bool]:
        """Move the queue's FIFO head run onto ``taken`` (holding
        ``total`` rows of ``kind``) while it stays within ``max_batch``
        rows; returns (total, kind, backlog: requests still queued).
        Only same-kind payloads coalesce: a request whose trailing
        shape/dtype differs from the head's starts its own micro-batch,
        so one malformed request can never fail its neighbors' futures.
        With the span recorder on, each request taken ends its
        ``serve.queue`` span and joins its flight's span id."""
        n0 = len(taken)
        with self._qlock:
            while self._queue:
                nxt = self._queue[0]
                if taken and total + nxt.rows > self.max_batch:
                    break
                if taken and nxt.kind != kind:
                    break
                if not taken:
                    kind = nxt.kind
                taken.append(self._queue.popleft())
                self._queued_rows -= nxt.rows
                total += nxt.rows
                if total >= self.max_batch:
                    break
            backlog = bool(self._queue)
        if spans.on and len(taken) > n0:
            t = time.perf_counter()
            flight = taken[0].flight or spans.new_id()
            for r in taken[n0:]:
                r.flight = flight
                spans.record("serve.queue", r.t_enqueue, t, spans.new_id(), flight,
                             rows=r.rows)
        return total, kind, backlog

    def _admit(self) -> List[_Request]:
        """Continuous-batching admission: build the next micro-batch,
        holding it open (the admission window) so rows arriving while
        the device is busy join the not-yet-launched batch instead of
        starting their own.  The window is keyed on queue state and
        never delays latency-bound traffic — a partial batch launches
        IMMEDIATELY when

        * it is full (``max_batch`` rows), or
        * other requests are already queued behind it (backlog: a
          different-kind head, or rows that did not fit), or
        * no batch is in flight (the device is idle — holding the
          batch would serialize, not overlap).

        Only while at least one batch is in flight does the batch stay
        open, for at most ``admit_window_s`` — time that is fully
        overlapped with device compute."""
        taken: List[_Request] = []
        total = 0
        kind: Optional[Tuple] = None
        deadline: Optional[float] = None
        t_first = 0.0
        while not self._stop.is_set():
            self._chaos_kill("dispatcher")
            total, kind, backlog = self._pop_run(taken, total, kind)
            if taken and not t_first and spans.on:
                t_first = time.perf_counter()
            if taken and (total >= self.max_batch or backlog):
                break
            if taken:
                if self._inflight() == 0:
                    break
                now = time.perf_counter()
                if deadline is None:
                    deadline = now + self.admit_window_s
                if now >= deadline:
                    break
                timeout = min(deadline - now, 0.0005)
            else:
                timeout = 0.05
            self._wake.wait(timeout=timeout)
            self._wake.clear()
        if t_first:
            spans.record("serve.admit", t_first, time.perf_counter(),
                         taken[0].flight, requests=len(taken), rows=total)
        return taken

    # -- fault handling (DESIGN.md §11) ------------------------------ #
    def _chaos_flight(self, reqs: List[_Request], fallback: bool) -> None:
        if self._chaos is not None:
            self._chaos.on_flight([r.x for r in reqs], fallback=fallback)

    def _chaos_kill(self, role: str) -> None:
        if self._chaos is not None:
            self._chaos.maybe_kill(role)

    def _shed_expired(self, reqs: List[_Request]) -> List[_Request]:
        """Resolve requests whose deadline already passed with
        RequestTimeout — BEFORE any device work — and return the
        still-live remainder."""
        now = time.perf_counter()
        live: List[_Request] = []
        for r in reqs:
            if r.expired(now):
                late = now - r.deadline
                r.future.set_exception(
                    RequestTimeout(f"deadline expired {late:.3f}s before launch")
                )
                with self._stats_lock:
                    self._timeouts += 1
            else:
                live.append(r)
        return live

    def _execute(self, reqs: List[_Request], fallback: bool = False) -> Any:
        """Synchronously run one coalesced flight end to end (launch +
        block) and return the concatenated result — the re-execution
        primitive the recovery ladder is built from.  Safe to call
        repeatedly for the same requests: payloads are never donated
        (padding/coalescing stage into fresh server-owned buffers, and
        the fallback jit does not donate at all)."""
        self._chaos_flight(reqs, fallback)
        x, flat = self._stage([r.x for r in reqs])
        rows = sum(r.rows for r in reqs)
        outs = self._launch_chunks(
            x, rows, len(reqs) > 1, reqs[0].kind, flat, fallback, reqs[0].flight
        )
        return self._finish_chunks(outs)

    def _recover(self, reqs: List[_Request], exc: BaseException) -> None:
        """Run the recovery ladder (``_climb``) for one failed flight and
        count it; with the span recorder on, the ladder's run is a
        ``serve.recover`` span carrying the fallbacks, retries and
        bisections the server counted meanwhile."""
        with self._stats_lock:
            self._flight_faults += 1
        t0 = time.perf_counter() if spans.on else 0.0
        if t0:
            before = self._ladder_counts()
        self._climb(reqs, exc)
        if t0:
            moved = [b - a for a, b in zip(before, self._ladder_counts())]
            spans.record("serve.recover", t0, time.perf_counter(), reqs[0].flight,
                         fallback=moved[0], retries=moved[1], bisections=moved[2])

    def _ladder_counts(self) -> Tuple[int, int, int]:
        with self._stats_lock:
            return self._backend_fallbacks, self._retries, self._bisections

    def _climb(self, reqs: List[_Request], exc: BaseException) -> None:
        """The recovery ladder for a failed flight: backend fallback ->
        bounded retry with backoff -> bisection -> typed singleton
        failure.  Every future in ``reqs`` is resolved (value or typed
        error) by the time this returns — the zero-lost-futures
        invariant.

        * A *backend* fault (kernel launch / runtime failure) first
          re-executes the flight on the bit-identical fallback backend
          — graceful degradation, counted in stats().
        * A transient fault retries up to ``max_retries`` times with
          exponential backoff.  Deterministic payload errors
          (ValueError/TypeError) skip straight past the retries.
        * A multi-request flight that still fails is bisected: each
          half re-executes independently, recursing until exactly the
          poison request(s) hold the exception (wrapped as
          PoisonRequest with the original chained as ``__cause__``)
          and every healthy neighbor has resolved normally.  The full
          ladder applies at every bisection level — a backend fault
          landing on a half mid-bisection still degrades to the
          fallback path instead of failing healthy requests.
        """
        if self.fallback_backend is not None and _is_backend_fault(exc):
            try:
                out = self._execute(reqs, fallback=True)
            except Exception as e:
                exc = e
            else:
                with self._stats_lock:
                    self._backend_fallbacks += 1
                self._resolve(reqs, out)
                return
        if _is_retryable(exc):
            for attempt in range(self.max_retries):
                time.sleep(self.retry_backoff_s * (2**attempt))
                with self._stats_lock:
                    self._retries += 1
                try:
                    out = self._execute(reqs)
                except Exception as e:
                    exc = e
                else:
                    self._resolve(reqs, out)
                    return
        if len(reqs) > 1:
            with self._stats_lock:
                self._bisections += 1
            mid = len(reqs) // 2
            for half in (reqs[:mid], reqs[mid:]):
                try:
                    out = self._execute(half)
                except Exception as e:
                    self._climb(half, e)
                else:
                    self._resolve(half, out)
            return
        if isinstance(exc, ServingError):
            err: BaseException = exc
        else:
            err = PoisonRequest(f"request payload makes the forward raise: {exc!r}")
            err.__cause__ = exc
            with self._stats_lock:
                self._poisoned += 1
        reqs[0].future.set_exception(err)

    def _observe_wall(self, wall: float) -> None:
        """Feed one flight's wall time to the straggler watchdog
        (runtime/straggler.py): a flight slower than ``slow_factor`` x
        the trailing-window median is flagged in
        ``stats()["straggler_flags"]``."""
        with self._stats_lock:
            self._watchdog.observe(wall)

    def _launch_flight(self, taken: List[_Request]) -> None:
        """Coalesce one admitted micro-batch and ENQUEUE its device
        computation without waiting (dispatch-ahead): the completer
        thread blocks on results in launch order while this thread
        returns to admission for the next batch.  The dispatch-ahead
        semaphore bounds launched-but-unresolved flights.  A launch
        failure runs the recovery ladder here, synchronously — rare by
        construction, and recovery must not race admission."""
        taken = self._shed_expired(taken)
        if not taken:
            return
        flight = taken[0].flight
        acquired = False
        t_launch = time.perf_counter()
        try:
            self._chaos_flight(taken, False)
            x, flat = self._stage([r.x for r in taken])
            rows = sum(r.rows for r in taken)
            t_wait = time.perf_counter() if spans.on else 0.0
            self._ahead_sem.acquire()
            acquired = True
            # a request's queue wait ends here, once its flight holds a
            # dispatch-ahead slot
            t_slot = time.perf_counter()
            if t_wait:
                if len(taken) > 1:
                    bucket = bucket_for(rows, self.max_batch)
                    spans.record("serve.stage", t_launch, t_wait, spans.new_id(),
                                 flight, bucket=bucket,
                                 valid=ragged_valid(rows, bucket), bytes=_nbytes(x),
                                 flat=int(flat))
                spans.record("serve.ahead_wait", t_wait, t_slot, flight)
            outs = self._launch_chunks(x, rows, len(taken) > 1, taken[0].kind, flat,
                                       flight=flight)
        except Exception as e:
            if acquired:
                self._ahead_sem.release()
            self._recover(taken, e)
            self._observe_wall(time.perf_counter() - t_launch)
            return
        with self._stats_lock:
            self._inflight_n += 1
            self._inflight_peak = max(self._inflight_peak, self._inflight_n)
            for r in taken:
                self._queue_waits.append(t_slot - r.t_enqueue)
        self._launched.put(_Flight(taken, outs, t_launch))

    def _serve_one(self, taken: List[_Request]) -> None:
        """Run one coalesced micro-batch synchronously and resolve its
        futures (the ``flush`` path — no dispatch-ahead); failures run
        the recovery ladder."""
        taken = self._shed_expired(taken)
        if not taken:
            return
        t_start = time.perf_counter()
        with self._stats_lock:
            for r in taken:
                self._queue_waits.append(t_start - r.t_enqueue)
        try:
            out = self._execute(taken)
        except Exception as e:
            self._recover(taken, e)
        else:
            self._resolve(taken, out)
        self._observe_wall(time.perf_counter() - t_start)

    def _resolve(self, taken: List[_Request], out: Any) -> float:
        """Slice a completed micro-batch result back to its requests;
        returns the time it started (a ``serve.resolve`` span's start
        with the span recorder on)."""
        t_done = time.perf_counter()
        off = 0
        for r in taken:
            r.future.set_result(_slice_rows(out, off, off + r.rows))
            off += r.rows
            with self._stats_lock:
                self._n_requests += 1
                self._n_rows += r.rows
                self._latencies.append(t_done - r.t_enqueue)
        if spans.on:
            spans.record("serve.resolve", t_done, time.perf_counter(), taken[0].flight)
        return t_done

    def flush(self) -> int:
        """Drain the queue synchronously; returns micro-batches run.
        Terminates even under backpressure: every iteration removes
        the requests it takes from the bounded queue, and concurrent
        ``submit`` calls cannot grow it past ``max_queue_rows``."""
        n = 0
        while True:
            taken = self._take_microbatch()
            if not taken:
                return n
            self._serve_one(taken)
            n += 1

    # -- async dispatcher + completer + supervisor ------------------- #
    def start(self) -> "BNNServer":
        """Spawn the dispatcher, completer, and supervisor threads
        (idempotent)."""
        if self._worker is not None and self._worker.is_alive():
            return self
        self._stop.clear()
        self._sup_stop.clear()
        self._dispatcher_exited = False
        self._completer_done = False
        self._launched = Queue()
        self._ahead_sem = threading.Semaphore(self.dispatch_ahead)
        self._completer = threading.Thread(target=self._complete_loop, daemon=True)
        self._worker = threading.Thread(target=self._dispatch_loop, daemon=True)
        self._supervisor = threading.Thread(target=self._supervise_loop, daemon=True)
        self._completer.start()
        self._worker.start()
        self._supervisor.start()
        return self

    def _dispatch_loop(self) -> None:
        while not self._stop.is_set():
            try:
                self._chaos_kill("dispatcher")
                taken = self._admit()
                if taken:
                    self._launch_flight(taken)
            except Exception:
                # per-request failures already resolve their own
                # futures through the recovery ladder; anything that
                # still escapes must not kill the dispatcher and strand
                # the queue
                continue
            except BaseException as e:
                if _is_kill(e):
                    # simulated thread death: exit WITHOUT the clean-
                    # exit flag, so the supervisor restarts the loop
                    return
                raise
        self._dispatcher_exited = True

    def _complete_loop(self) -> None:
        while True:
            try:
                self._chaos_kill("completer")
                fl = self._launched.get(timeout=0.05)
            except Empty:
                continue
            except BaseException as e:
                if _is_kill(e):
                    return  # dead without _completer_done: restarted
                raise
            if fl is None:
                self._completer_done = True
                return
            self._complete_one(fl)

    def _complete_one(self, fl: _Flight) -> None:
        """Resolve one launched flight (failures climb the recovery
        ladder); ALWAYS releases its dispatch-ahead slot.  With the span
        recorder on, the wait for the device is a ``serve.device_wait``
        span."""
        t_wait = time.perf_counter() if spans.on else 0.0
        try:
            try:
                out = self._finish_chunks(fl.outs)
            except Exception as e:
                self._recover(fl.reqs, e)
            else:
                t_done = self._resolve(fl.reqs, out)
                if t_wait:
                    spans.record("serve.device_wait", t_wait, t_done, fl.reqs[0].flight)
        finally:
            self._observe_wall(time.perf_counter() - fl.t_launch)
            with self._stats_lock:
                self._inflight_n -= 1
            self._ahead_sem.release()

    def _supervise_loop(self) -> None:
        """Thread watchdog: a dispatcher or completer that died without
        reaching its clean exit point (a chaos kill, an unexpected
        BaseException) is restarted, so a dead loop can never strand
        the queue or the in-flight batches.  Clean exits set their exit
        flag before returning and are never restarted."""
        while not self._sup_stop.is_set():
            w, c = self._worker, self._completer
            if w is not None and not w.is_alive() and not self._dispatcher_exited:
                self._worker = threading.Thread(
                    target=self._dispatch_loop, daemon=True
                )
                self._worker.start()
                with self._stats_lock:
                    self._thread_restarts += 1
            if c is not None and not c.is_alive() and not self._completer_done:
                self._completer = threading.Thread(
                    target=self._complete_loop, daemon=True
                )
                self._completer.start()
                with self._stats_lock:
                    self._thread_restarts += 1
            self._sup_stop.wait(timeout=self.supervise_interval_s)

    def stop(self) -> None:
        """Stop the worker threads, drain what is already queued, and
        resolve every launched batch before returning — even with
        chaos-killed loops mid-flight: the supervisor stays up until
        both loops reach their clean exit points, restarting dead ones,
        so stop() cannot deadlock on a dead completer's unreleased
        dispatch-ahead slot."""
        if self._worker is None:
            return
        self._stop.set()
        self._wake.set()
        while not self._dispatcher_exited:
            w = self._worker
            if w is None:
                break
            w.join(timeout=0.05)
        # the dispatcher is gone for good: launch everything still
        # queued (no admission window), then hand the completer its
        # stop sentinel — batches in flight resolve before we return
        while True:
            taken = self._take_microbatch()
            if not taken:
                break
            self._launch_flight(taken)
        self._launched.put(None)
        while not self._completer_done:
            c = self._completer
            if c is None:
                break
            c.join(timeout=0.05)
        self._sup_stop.set()
        if self._supervisor is not None:
            self._supervisor.join()
            self._supervisor = None
        self._worker = None
        self._completer = None
        self.flush()  # anything submitted after the drain began

    # -- observability ----------------------------------------------- #
    def health(self) -> Dict[str, Any]:
        """Readiness probe: thread liveness, queue pressure, restart
        count.  ``healthy`` is True when the server can make progress —
        worker loops alive (or not started: flush-mode serving) and
        admission not saturated.  A loop the chaos layer just killed
        reads unhealthy until the supervisor restarts it."""
        w, c = self._worker, self._completer
        running = w is not None
        d_alive = bool(w is not None and w.is_alive())
        c_alive = bool(c is not None and c.is_alive())
        with self._qlock:
            depth = len(self._queue)
            qrows = self._queued_rows
        with self._stats_lock:
            inflight = self._inflight_n
            restarts = self._thread_restarts
        overloaded = self.max_queue_rows is not None and qrows >= self.max_queue_rows
        return {
            "healthy": (not running or (d_alive and c_alive)) and not overloaded,
            "running": running,
            "dispatcher_alive": d_alive,
            "completer_alive": c_alive,
            "queue_depth": depth,
            "queued_rows": qrows,
            "overloaded": overloaded,
            "inflight_batches": inflight,
            "thread_restarts": restarts,
        }

    def stats(self) -> Dict[str, Any]:
        """The serving counters (DESIGN.md §9/§10/§11 schema): request/
        row totals, dispatch and bucket-reuse counts, jit trace count
        vs the policy bound, padded-vs-valid-vs-real occupancy, HBM
        bytes/request from the compiled traffic model, the chunks whose
        host image rows were staged flat, the in-flight gauge,
        queue-wait / end-to-end latency percentiles, the fault-recovery
        counters, and the straggler watchdog flags."""
        with self._stats_lock:  # snapshot: writers hold the same locks
            lat = sorted(self._latencies)
            waits = sorted(self._queue_waits)
            requests, rows = self._n_requests, self._n_rows
            batches = self._n_batches
            hits, misses = self._bucket_hits, self._bucket_misses
            padded, valid = self._padded_rows, self._valid_rows
            real = self._real_rows
            hbm = self._hbm_bytes
            flat_staged = self._flat_staged
            inflight, inflight_peak = self._inflight_n, self._inflight_peak
            faults = {
                "flights": self._flight_faults,
                "backend_fallbacks": self._backend_fallbacks,
                "retries": self._retries,
                "bisections": self._bisections,
                "poisoned_requests": self._poisoned,
                "timeouts": self._timeouts,
                "rejected": self._rejected,
                "thread_restarts": self._thread_restarts,
            }
            straggler_flags = list(self._watchdog.flags)
            straggler_median = self._watchdog.median
        with self._trace_lock:
            buckets = sorted({key[0] for key in self._traced})
        dispatches = hits + misses
        stats = {
            "requests": requests,
            "rows": rows,
            "batches": batches,
            "queue_depth": self.queue_depth(),
            "inflight_batches": inflight,
            "inflight_peak": inflight_peak,
            "buckets_traced": buckets,
            "bucket_hits": hits,
            "bucket_misses": misses,
            "bucket_hit_rate": hits / dispatches if dispatches else 0.0,
            "jit_traces": self.jit_traces(),
            "trace_bound": self.trace_bound(),
            "padded_rows": padded,
            "valid_rows": valid,
            "real_rows": real,
            "occupancy": real / padded if padded else 0.0,
            "compute_occupancy": real / valid if valid else 0.0,
            "hbm_bytes": hbm,
            "hbm_bytes_per_request": hbm / max(requests, 1),
            "flat_staged": flat_staged,
            "devices": 1 if self.mesh is None else self.mesh.size,
            "faults": faults,
            "straggler_flags": straggler_flags,
            "straggler_median_s": straggler_median,
        }
        if lat:
            stats["latency_s"] = _pcts(lat)
        if waits:
            stats["queue_wait_s"] = _pcts(waits)
        return stats
