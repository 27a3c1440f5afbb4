"""The one load generator: reads a traffic mix (bench/traffic/<mix>.json)
and drives a server with it for the measured window.

A mix is a list of ``streams``, each one of

* ``{"arrival": "closed", "clients": n, "rows": dist}``: n clients,
  each sending a request and waiting for its answer before the next;
* ``{"arrival": "poisson", "rate_per_s": r, "rows": dist}``: open
  loop, requests due at Poisson times whatever the server does;

where ``rows`` is ``{"dist": "fixed", "n": k}`` or ``{"dist": "zipf",
"s": s, "min": a, "max": b}`` (P(k) proportional to k**-s on [a, b]).
Every request is a run of consecutive rows of the image pool
(``pool_images`` images made in set-up) at a seeded offset.

Open-loop schedules give every seed the same sizes and gaps in another
order: both are drawn from the mix's fixed ``shape_seed`` and then
shuffled with the run's seed, so seeds change the order of the work
and not its amount.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from weights import seed_words

RESULT_GRACE_S = 60.0


def _rows_sampler(dist: Dict[str, Any]):
    if dist["dist"] == "fixed":
        n = int(dist["n"])
        return (lambda rng, size: np.full(size, n, np.int64)), n
    if dist["dist"] == "zipf":
        ks = np.arange(int(dist["min"]), int(dist["max"]) + 1)
        p = ks.astype(np.float64) ** -float(dist["s"])
        p /= p.sum()
        return (lambda rng, size: rng.choice(ks, size=size, p=p)), int(ks[-1])
    raise ValueError(f"unknown rows dist {dist['dist']!r}")


def _gaps(stream: Dict[str, Any], rng, seconds: float) -> np.ndarray:
    """The inter-arrival gaps that fit in ``seconds`` (as drawn from
    ``rng``): their sum stays within the window in any order."""
    rate = float(stream["rate_per_s"])
    g = rng.exponential(1.0 / rate, size=int(rate * seconds * 1.2) + 64)
    while g.sum() < seconds:
        g = np.concatenate([g, g])
    return g[:int(np.searchsorted(np.cumsum(g), seconds))]


def schedule(stream: Dict[str, Any], mix: Dict[str, Any], seed: int,
             seconds: float, pool_n: int) -> Dict[str, np.ndarray]:
    """Due offsets (s from window start), rows and pool offsets of an
    open-loop stream's requests in the window."""
    shape_rng = np.random.default_rng(int(mix.get("shape_seed", 0)))
    sample, _ = _rows_sampler(stream["rows"])
    gaps = _gaps(stream, shape_rng, seconds)
    rows = sample(shape_rng, gaps.size)
    rng = np.random.default_rng(seed_words(seed, salt=3))
    gaps = rng.permutation(gaps)
    rows = rng.permutation(rows)
    due = np.cumsum(gaps) - gaps       # the first request is due at once
    offs = rng.integers(0, pool_n - rows + 1)
    return {"due": due, "rows": rows, "off": offs}


class Records:
    """What the window saw, one entry per request attempted."""

    def __init__(self):
        self.lock = threading.Lock()
        self.rows: List[int] = []
        self.off: List[int] = []
        self.due: List[float] = []
        self.sent: List[float] = []
        self.submitted: List[float] = []
        self.done: List[float] = []
        self.out: List[Any] = []
        self.error: List[Optional[str]] = []

    def add(self, rows, off, due, sent) -> int:
        with self.lock:
            self.rows.append(rows)
            self.off.append(off)
            self.due.append(due)
            self.sent.append(sent)
            self.submitted.append(float("nan"))
            self.done.append(float("nan"))
            self.out.append(None)
            self.error.append(None)
            return len(self.rows) - 1

    def finish(self, i: int, fut) -> None:
        t = time.perf_counter()
        try:
            out = fut.result(timeout=0)
        except Exception as e:  # the request failed: recorded, not raised
            self.error[i] = repr(e)
        else:
            self.out[i] = out
        self.done[i] = t


def _closed_client(srv, pool, stream, rng, t_end, rec: Records):
    sample, _ = _rows_sampler(stream["rows"])
    while True:
        now = time.perf_counter()
        if now >= t_end:
            return
        rows = int(sample(rng, 1)[0])
        off = int(rng.integers(0, len(pool) - rows + 1))
        i = rec.add(rows, off, now, now)
        try:
            fut = srv.submit(pool[off:off + rows])
            rec.submitted[i] = time.perf_counter()
            fut.result(timeout=RESULT_GRACE_S + t_end - now)
        except Exception as e:  # overload or a lost answer: a failure
            rec.error[i] = repr(e)
            rec.done[i] = time.perf_counter()
            continue
        rec.finish(i, fut)


def _open_sender(srv, pool, sched, t0, rec: Records, futs):
    for due_off, rows, off in zip(sched["due"], sched["rows"], sched["off"]):
        due = t0 + float(due_off)
        while True:
            now = time.perf_counter()
            if now >= due:
                break
            time.sleep(min(due - now, 0.002))
        i = rec.add(int(rows), int(off), due, now)
        try:
            fut = srv.submit(pool[off:off + rows])
            rec.submitted[i] = time.perf_counter()
        except Exception as e:  # refused: a failure
            rec.error[i] = repr(e)
            rec.done[i] = time.perf_counter()
            continue
        fut.add_done_callback(lambda f, i=i: rec.finish(i, f))
        futs.append(fut)


def drive(srv, pool: np.ndarray, mix: Dict[str, Any], seed: int,
          seconds: float, on_start: Optional[Callable] = None,
          on_close: Optional[Callable] = None,
          marks: Sequence[Tuple[float, Callable]] = ()) -> Dict[str, Any]:
    """Run the mix for ``seconds``; returns the records and the window
    [t0, t1] on the host clock.  ``on_start`` runs just before the
    first request is due, each of ``marks`` (seconds into the window,
    callback) in turn at its time, and ``on_close`` as the window
    closes.  Waits up to RESULT_GRACE_S past the close for answers
    still owed."""
    rec = Records()
    futs: List[Any] = []
    plans = []
    for k, st in enumerate(mix["streams"]):
        if st["arrival"] == "poisson":
            plans.append(schedule(st, mix, seed + 7919 * k, seconds, len(pool)))
        elif st["arrival"] != "closed":
            raise ValueError(f"unknown arrival {st['arrival']!r}")
        else:
            plans.append(None)
    if on_start is not None:
        on_start()
    t0 = time.perf_counter() + 0.01
    t1 = t0 + seconds
    threads = []
    for k, (st, sched) in enumerate(zip(mix["streams"], plans)):
        if sched is None:
            for c in range(int(st["clients"])):
                rng = np.random.default_rng(seed_words(seed, salt=100 + 64 * k + c))
                threads.append(threading.Thread(
                    target=_closed_client,
                    args=(srv, pool, st, rng, t1, rec)))
        else:
            threads.append(threading.Thread(
                target=_open_sender, args=(srv, pool, sched, t0, rec, futs)))
    while time.perf_counter() < t0:
        pass
    for th in threads:
        th.start()
    for at, fn in marks:
        time.sleep(max(0.0, t0 + at - time.perf_counter()))
        fn()
    time.sleep(max(0.0, t1 - time.perf_counter()))
    if on_close is not None:
        on_close()
    for th in threads:
        th.join(timeout=RESULT_GRACE_S + 1)
    deadline = t1 + RESULT_GRACE_S
    for f in futs:
        try:
            f.result(timeout=max(0.0, deadline - time.perf_counter()))
        except Exception:  # recorded by the done callback
            pass
    # a callback may still be running for the last futures
    time.sleep(0.01)
    return {"records": rec, "t0": t0, "t1": t1}
