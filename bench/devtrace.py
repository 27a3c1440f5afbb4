"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device
numbers.

A traced run takes one trace, without the host tracer, whose cost
would slow the host it measures (and a second trace in one process
records nothing).  ``load`` reads it with nothing but JAX into plain
tuples: device op events per chip on the trace's clock, and the
session's start on the host's wall clock (``time.time_ns``), which
puts the benchmark's own records of each request on the same clock.
``reduce`` takes its window from the device timeline
(``device_window``: first op's start to last op's end on any chip)
and computes within it:

* busy seconds per chip: the union of the intervals in which an op ran;
* per kernel, the calls that ran wholly inside the window, counted by
  op name (``packed_conv2d.5``: one op of the compiled program), and
  their summed device seconds: the ops whose name, without its
  ``.N`` suffix, is one of the kernel's names;
* the ten device ops (by name without suffix) that took most time.

``idle_gaps`` gives the ten longest idle gaps on the first chip, each
labelled by how many requests were in each of the benchmark's host
spans (``bench.submit``, ``bench.wait``) during it.
"""
from __future__ import annotations

import glob
import gzip
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

Interval = Tuple[float, float]
_SUFFIX = re.compile(r"\.\d+$")
OPS_LINE = "XLA Ops"
SESSION_PLANE = "Task Environment"


def op_name(event_name: str) -> str:
    """The HLO instruction name of a device op event: the trace names
    an op by its HLO text, ``%packed_conv2d.5 = u32[...] custom-call(...)``."""
    if event_name.startswith("%"):
        return event_name[1:].split(" ", 1)[0]
    return event_name


def base_name(name: str) -> str:
    """The op name without its ``.N`` uniquifier."""
    return _SUFFIX.sub("", op_name(name))


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> Dict[str, object]:
    """{"devices": {plane: [(name, start_ns, end_ns)]}, "start_ns": the
    session's start as ``time.time_ns()`` reads it, which the event
    times count from}; a ``.gz`` path is read through gzip."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    devices: Dict[str, List[tuple]] = {}
    start_ns = None
    for plane in pd.planes:
        pname = plane.name
        if pname == SESSION_PLANE:
            start_ns = int(dict(plane.stats)["profile_start_time"])
        elif pname.startswith("/device:") and "SparseCore" not in pname:
            evs = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    evs.append((ev.name, float(ev.start_ns), float(ev.end_ns)))
            if evs:
                devices[pname] = sorted(evs, key=lambda e: e[1])
    if start_ns is None:
        raise ValueError(f"{path} has no {SESSION_PLANE!r} plane")
    return {"devices": devices, "start_ns": start_ns}


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Iterable[Interval], t0: float, t1: float) -> List[Interval]:
    return [(max(a, t0), min(b, t1)) for a, b in intervals if b > t0 and a < t1]


def gaps(busy: Sequence[Interval], t0: float, t1: float) -> List[Interval]:
    out, t = [], t0
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < t1:
        out.append((t, t1))
    return out


def device_window(tr: Dict[str, object]) -> Interval:
    """First op's start to last op's end over every chip of the trace."""
    evs = [e for plane in tr["devices"].values() for e in plane]
    if not evs:
        raise ValueError("the trace has no device ops")
    return min(e[1] for e in evs), max(e[2] for e in evs)


def reduce(tr: Dict[str, object], kernels: Dict[str, Sequence[str]],
           window: Optional[Interval] = None, top: int = 10) -> Dict[str, object]:
    """Device numbers of the traced window (seconds); see the module
    docstring.  ``kernels`` maps a kernel to the op names it runs as;
    the window is the device timeline unless given."""
    t0, t1 = window if window is not None else device_window(tr)
    devices = tr["devices"]
    busy_s, kernel_s, per_op = [], {k: 0.0 for k in kernels}, {}
    kernel_calls: Dict[str, Dict[str, int]] = {k: {} for k in kernels}
    name_to_kernel = {n: k for k, names in kernels.items() for n in names}
    for plane in sorted(devices):
        inside = [(n, a, b) for n, a, b in devices[plane] if b > t0 and a < t1]
        busy = union(clip(((a, b) for _, a, b in inside), t0, t1))
        busy_s.append(sum(b - a for a, b in busy) * 1e-9)
        for n, a, b in inside:
            bn = base_name(n)
            per_op[bn] = per_op.get(bn, 0.0) + (min(b, t1) - max(a, t0)) * 1e-9
            k = name_to_kernel.get(bn)
            if k is not None and t0 <= a and b <= t1:
                kernel_s[k] += (b - a) * 1e-9
                on = op_name(n)
                kernel_calls[k][on] = kernel_calls[k].get(on, 0) + 1
    return {
        "window_s": (t1 - t0) * 1e-9,
        "busy_s": busy_s,
        "kernel_s": kernel_s,
        "kernel_calls": kernel_calls,
        "device_ops": sorted(per_op.items(), key=lambda kv: -kv[1])[:top],
    }


def idle_gaps(tr: Dict[str, object], spans: Sequence[Tuple[str, int, int]],
              top: int = 10) -> List[list]:
    """The ``top`` longest idle gaps [label, seconds] on the first chip
    within the device window, longest first.  ``spans`` are the
    benchmark's (name, start, end) on ``time.time_ns()``; a gap's label
    counts the spans of each name that overlap it:
    ``"bench.submit 0 | bench.wait 4"``."""
    t0, t1 = device_window(tr)
    evs = tr["devices"][sorted(tr["devices"])[0]]
    busy = union(clip(((a, b) for _, a, b in evs), t0, t1))
    idle = sorted(gaps(busy, t0, t1), key=lambda g: g[0] - g[1])[:top]
    names = sorted({n for n, _, _ in spans})
    by_name = {n: (np.array([a - tr["start_ns"] for m, a, _ in spans if m == n], np.float64),
                   np.array([b - tr["start_ns"] for m, _, b in spans if m == n], np.float64))
               for n in names}

    def label(g: Interval) -> str:
        parts = [f"{n} {int(np.count_nonzero((a < g[1]) & (b > g[0])))}"
                 for n, (a, b) in by_name.items()]
        return " | ".join(parts) or "none"

    return [[label(g), (g[1] - g[0]) * 1e-9] for g in idle]
