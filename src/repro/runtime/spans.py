"""The process-wide span recorder (DESIGN.md §10): where the host time
of serving and set-up goes, on the clock a device trace uses.

Off by default.  ``enable()`` starts a fresh recording; every
instrumented site then appends ``(name, start, end, id, parent,
attrs)`` to one in-memory list, with ``start``/``end`` the
``time.perf_counter()`` readings the site takes anyway.  ``collect()``
hands the spans out on ``time.time_ns()``, which is the clock of a JAX
profiler session's ``profile_start_time``, so each idle gap of a
device trace can be laid against what the host was doing in it.

While the recorder is off a site costs one check of the module flag
``on``: no clock read, no allocation.  Appends rely on the interpreter
lock (``list.append`` and ``next`` on a counter are atomic), so the hot
path takes no lock.  The list holds at most ``CAP`` spans; what does
not fit is counted by ``dropped()``.  While recording, garbage
collections are spans too (``proc.gc``).

Standard library only, so graph/, serving/ and the benchmark import it
without a cycle.
"""
from __future__ import annotations

import gc
import itertools
import threading
import time
from typing import Any, Dict, List, Tuple

__all__ = ["CAP", "collect", "disable", "dropped", "enable", "new_id",
           "record"]

CAP = 1 << 20

on = False
_spans: List[tuple] = []
_dropped = 0
_drop_lock = threading.Lock()
_ids = itertools.count(1)
_offset_ns = 0
_gc_start = 0.0


def enable() -> None:
    """Start a fresh recording: clears what was kept, fixes the offset
    from ``perf_counter`` to ``time.time_ns``, and records garbage
    collections."""
    global on, _spans, _dropped, _offset_ns
    _spans, _dropped = [], 0
    _offset_ns = time.time_ns() - time.perf_counter_ns()
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
    on = True


def disable() -> None:
    """Stop recording; what was kept stays for ``collect()``."""
    global on
    on = False
    if _on_gc in gc.callbacks:
        gc.callbacks.remove(_on_gc)


def new_id() -> int:
    """A span id unique in the process (0 means none)."""
    return next(_ids)


def record(name: str, start: float, end: float, id: int = 0,
           parent: int = 0, **attrs: Any) -> None:
    """Keep one span; ``start`` and ``end`` are ``time.perf_counter()``
    readings.  Callers check ``on`` first."""
    global _dropped
    if len(_spans) >= CAP:
        with _drop_lock:
            _dropped += 1
        return
    _spans.append((name, start, end, id, parent, attrs))


def collect() -> List[Tuple[str, int, int, int, int, Dict[str, Any]]]:
    """Every span kept since ``enable()``, start and end in ns on
    ``time.time_ns()``."""
    off = _offset_ns
    return [(n, round(a * 1e9) + off, round(b * 1e9) + off, i, p, at)
            for n, a, b, i, p, at in list(_spans)]


def dropped() -> int:
    """Spans refused because the list was full."""
    return _dropped


def _on_gc(phase: str, info: Dict[str, int]) -> None:
    global _gc_start
    if phase == "start":
        _gc_start = time.perf_counter()
    elif _gc_start:
        record("proc.gc", _gc_start, time.perf_counter(),
               generation=info["generation"])
