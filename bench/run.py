"""Run one cell of the benchmark on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  One process: it builds the cell's
configuration through the program's normal path (``graph.compile`` ->
``BNNServer``), warms the dispatch levels the cell's traffic uses,
drives the traffic for ``--seconds`` (bench/load.py), checks every
answer against the plain reference (bench/reference.py), and prints
the numbers compared, each beside its limit, as the last lines of
standard error, and one JSON line as the last line of standard output.
With ``--trace 0`` that line carries the cell's end-to-end metrics;
with ``--trace 1`` it carries the per-layer metrics: the last
TRACE_S seconds of the window are traced without the host tracer (busy
time, kernels, device ops, idle gaps), and rates are taken over the
untraced part before.

Everything a cell is made of is found by name: the cell in
BENCHMARK.json, its configuration file, each layer kind of that file's
table in bench/layers/<kind>.py, its traffic mix in
bench/traffic/<traffic>.json, its server settings in
bench/workloads/<cell>.json, each metric's reader in
bench/metrics/<metric>.py and each kernel's operation and byte count
in bench/kernels/<kernel>.py.

It exits nonzero, printing no result, without a TPU or with fewer
chips than the cell asks for.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

from kinds import BenchError, load_module  # noqa: E402

BENCHMARK_JSON = os.path.join(ROOT, "BENCHMARK.json")
TRAFFIC_DIR = os.path.join(BENCH, "traffic")
WORKLOADS_DIR = os.path.join(BENCH, "workloads")
TRACE_S = 3.0
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


def _load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def reader_name(metric: str) -> str:
    """The reader of a metric: ``images_per_s.imagenet`` is read by
    bench/metrics/images_per_s.py; the part after the first dot only
    splits a quantity over cells that report different end-to-end
    metrics."""
    return metric.split(".", 1)[0]


def applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


class Context:
    """What a metric reader sees of one run."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    # -- the window, from the load generator's records --------------- #
    def completed_rows(self, a: Optional[float] = None,
                       b: Optional[float] = None) -> int:
        """Rows answered in (a, b], by default in the whole window."""
        a = self.t0 if a is None else a
        b = self.t1 if b is None else b
        r = self.records
        return int(sum(n for n, d, o in zip(r.rows, r.done, r.out)
                       if o is not None and a < d <= b))

    def untraced(self):
        """The part of the window before tracing started: (a, b); the
        whole window where that part is under a second."""
        if self.t_trace is None or self.t_trace - self.t0 < 1.0:
            return self.t0, self.t1
        return self.t0, self.t_trace

    def stats_delta(self, key: str, traced: bool = False) -> float:
        """A server counter's growth over the window, or over its traced
        part."""
        start = self.stats_trace if traced else self.stats0
        return float(self.stats1[key] - start[key])

    # -- kernels ------------------------------------------------------ #
    def kernel_least_s(self, name: str) -> float:
        """Least time the chips could take for the kernel's calls that
        the trace holds whole: per call the larger of its operations
        over the int8 peak and its bytes over the HBM bandwidth, at the
        traced part's mean valid rows per flight on each chip.  The
        kernel's ops in the compiled program, in the order of their
        ``.N`` suffixes, are its plan steps in plan order; where their
        counts differ, calls are taken in the plan's mix of steps."""
        mod = load_module(os.path.join(BENCH, "kernels", name + ".py"))
        flights = self.stats_delta("batches", traced=True)
        calls = self.trace["kernel_calls"].get(name, {})
        if flights <= 0 or not calls:
            return 0.0
        rows = self.stats_delta("valid_rows", traced=True) / flights / self.chips
        least = []
        for step in self.steps:
            c = mod.cost(step, rows)
            if c is not None:
                ops, nbytes = c
                least.append(max(ops / self.peaks["int8_ops_per_s"],
                                 nbytes / self.peaks["hbm_bytes_per_s"]))
        if not least:
            return 0.0
        names = sorted(calls, key=op_index)
        if len(names) == len(least):
            return sum(calls[n] * t for n, t in zip(names, least))
        return sum(least) / len(least) * sum(calls.values())

    def ops_per_image(self) -> float:
        """2 x the multiply-accumulates of one image through every
        layer of the configuration."""
        from geometry import macs_per_image

        return 2.0 * macs_per_image(self.config)


def op_index(op: str) -> int:
    """The ``.N`` uniquifier of an HLO op name (0 for none)."""
    head, _, tail = op.rpartition(".")
    return int(tail) if head and tail.isdigit() else 0


def peaks_for(kind: str) -> Optional[Dict[str, float]]:
    """The published peaks of a device kind (bench/peaks.json), or None."""
    return _load_json(os.path.join(BENCH, "peaks.json"))["kinds"].get(kind)


def chips_for(n: int):
    """The first ``n`` TPU chips JAX sees and their peaks; a BenchError
    without a TPU, with fewer chips, or for a kind with no peaks."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < n:
        raise BenchError(f"the cell asks for {n} chips, JAX sees {len(devs)}")
    kind = devs[0].device_kind
    peaks = peaks_for(kind)
    if peaks is None:
        raise BenchError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return devs[:n], peaks


def host_spans(rec, a: float, b: float, to_ns: float) -> List[tuple]:
    """The benchmark's host spans of the requests active in (a, b):
    (``bench.submit`` or ``bench.wait``, start, end) on
    ``time.time_ns()``, ``to_ns`` being its reading minus the host
    clock's in ns."""
    out = []
    for sent, sub, done in zip(rec.sent, rec.submitted, rec.done):
        end = done if np.isfinite(done) else b
        if sent < b and end > a:
            mid = sub if np.isfinite(sub) else end
            out.append(("bench.submit", int(sent * 1e9 + to_ns), int(mid * 1e9 + to_ns)))
            out.append(("bench.wait", int(mid * 1e9 + to_ns), int(end * 1e9 + to_ns)))
    return out


def warm_rows(mix: Dict[str, Any], max_batch: int) -> List[int]:
    """Request sizes that touch every dispatch level (and pad shape)
    the mix can produce: only the full bucket where every request is a
    whole number of full buckets, else every size up to max_batch."""
    fixed = all(s["rows"]["dist"] == "fixed" and int(s["rows"]["n"]) % max_batch == 0
                for s in mix["streams"])
    return [max_batch] if fixed else list(range(1, max_batch + 1))


def logit_gaps(got: np.ndarray, want: np.ndarray, row_gap: float = 0.0):
    """(widest |got - want|, rows whose widest gap exceeds ``row_gap``)
    of one block of logits."""
    d = np.abs(got.astype(np.float64) - want)
    d[~np.isfinite(d)] = np.inf
    return float(d.max(initial=0.0)), int(np.count_nonzero(d.max(axis=1) > row_gap))


def checks_of(gap: float, rows_bad: int, rows_all: int, failed: int,
              limits: Dict[str, float]) -> Dict[str, Dict[str, float]]:
    return {
        "logit_gap_max": {"value": gap, "limit": limits["logit_gap_max"]},
        "rows_differ_pct": {"value": 100.0 * rows_bad / max(rows_all, 1),
                            "limit": limits["rows_differ_pct"]},
        "requests_failed": {"value": failed, "limit": 0},
    }


def compare(cfg, seed: int, rec, pool: np.ndarray) -> Dict[str, Dict[str, float]]:
    """The numbers compared and their limits: the widest gap between a
    served logit and the reference's, and the share of answered rows
    whose widest gap exceeds the configuration's ``compare.row_gap``
    (default 0: any gap), over every answer in the run; and the
    requests that failed or never answered."""
    import reference
    import weights
    from geometry import final_shape

    raw = weights.make_raw(cfg["layers"], seed)
    used = np.zeros(len(pool), bool)
    for o, off, n in zip(rec.out, rec.off, rec.rows):
        if o is not None:
            used[off:off + n] = True
    idx = np.flatnonzero(used)
    want = np.zeros((len(pool), *final_shape(cfg)), np.float32)
    if idx.size:
        want[idx] = reference.logits(cfg["layers"], raw, pool[idx],
                                     cfg["reference_block_rows"])
    gap, rows_bad, rows_all, failed = 0.0, 0, 0, 0
    for o, off, n in zip(rec.out, rec.off, rec.rows):
        if o is None or o.shape != (n, *want.shape[1:]):
            failed += 1
            continue
        g, b = logit_gaps(o, want[off:off + n], cfg["compare"].get("row_gap", 0.0))
        gap, rows_bad, rows_all = max(gap, g), rows_bad + b, rows_all + n
    return checks_of(gap, rows_bad, rows_all, failed, cfg["compare"])


def run(cell_name: str, seed: int, seconds: float, trace: bool) -> Dict[str, Any]:
    """One run; returns the result object."""
    spec = _load_json(BENCHMARK_JSON)
    cells = {w["name"]: w for w in spec["workloads"]}
    if cell_name not in cells:
        raise BenchError(f"no workload {cell_name!r} in {BENCHMARK_JSON}")
    cell = cells[cell_name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg = _load_json(os.path.join(ROOT, cfg_entry["file"]))
    mix = _load_json(os.path.join(TRAFFIC_DIR, cell["traffic"] + ".json"))
    server_cfg = _load_json(os.path.join(WORKLOADS_DIR, cell_name + ".json"))
    chips = int(cell["chips"])

    import jax

    # cache every program, so that a second run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    used, peaks = chips_for(chips)

    import load
    import program
    import weights

    cb, srv = program.build(cfg, server_cfg, chips, seed)
    pool = weights.make_images(cfg["input"], int(mix["pool_images"]), seed)
    for n in warm_rows(mix, server_cfg["max_batch"]):
        srv.submit(pool[:n]).result()

    compiles = []

    def on_compile(event, duration, **_kw):
        if event in COMPILE_EVENTS and window_open[0]:
            compiles.append((event, duration))

    window_open = [False]
    jax.monitoring.register_event_duration_secs_listener(on_compile)
    tmp = tempfile.TemporaryDirectory(prefix="bench_trace_") if trace else None
    snap: Dict[str, Any] = {}

    def on_start():
        snap["stats0"] = srv.stats()
        window_open[0] = True

    def on_trace():
        # device ops only: the host tracer slows the host it records
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 0
        jax.profiler.start_trace(tmp.name, profiler_options=opts)
        snap["stats_trace"] = srv.stats()
        snap["t_trace"] = time.perf_counter()
        snap["to_ns"] = time.time_ns() - snap["t_trace"] * 1e9

    def on_close():
        window_open[0] = False
        snap["stats1"] = srv.stats()
        if trace:
            jax.profiler.stop_trace()

    marks = [(max(0.0, seconds - TRACE_S), on_trace)] if trace else []
    res = load.drive(srv, pool, mix, seed, seconds, on_start=on_start,
                     on_close=on_close, marks=marks)
    setup_s = res["t0"] - T_PROCESS
    mem = [d.memory_stats() or {} for d in used]
    mem_peak = max(int(m.get("peak_bytes_in_use", 0)) for m in mem)
    srv.stop()
    rec = res["records"]
    rec.out = [None if o is None else np.asarray(o) for o in rec.out]
    steps = program.plan_steps(cb, cfg)
    del srv, cb
    gc.collect()

    reduced = gaps = None
    if trace:
        import devtrace

        kernels = {}
        for m in spec["per_layer"]:
            base = reader_name(m["name"])
            if base.endswith("_roofline") and applies(m, cell_name):
                k = base[:-len("_roofline")]
                kernels[k] = tuple(load_module(
                    os.path.join(BENCH, "kernels", k + ".py")).NAMES)
        tr = devtrace.load(devtrace.find_xplane(tmp.name))
        tmp.cleanup()
        reduced = devtrace.reduce(tr, kernels)
        gaps = devtrace.idle_gaps(
            tr, host_spans(rec, snap["t_trace"], res["t1"], snap["to_ns"]))

    t_ref = time.perf_counter()
    checks = compare(cfg, seed, rec, pool)
    reference_s = time.perf_counter() - t_ref
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    ctx = Context(cell=cell_name, config=cfg, mix=mix, chips=chips,
                  seconds=seconds, records=rec, t0=res["t0"], t1=res["t1"],
                  t_trace=snap.get("t_trace"), stats0=snap["stats0"],
                  stats1=snap["stats1"], stats_trace=snap.get("stats_trace"),
                  trace=reduced, steps=steps, setup_s=setup_s, peaks=peaks)
    wanted = spec["per_layer"] if trace else spec["end_to_end"]
    metrics = {}
    for m in wanted:
        if not applies(m, cell_name):
            continue
        v = load_module(os.path.join(BENCH, "metrics", reader_name(m["name"]) + ".py")).read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": chips, "memory_peak_bytes": mem_peak}
    window = {"compiles": len(compiles),
              "compile_s": float(sum(d for _, d in compiles)),
              "reference_s": reference_s}
    if reduced is not None:
        device["busy_s"] = float(np.mean(reduced["busy_s"]))
        device["window_s"] = reduced["window_s"]
        # what the trace costs: the rate under it and before it
        a, b = ctx.untraced()
        window["images_per_s_untraced"] = ctx.completed_rows(a, b) / (b - a)
        window["images_per_s_traced"] = (ctx.completed_rows(snap["t_trace"], res["t1"])
                                         / (res["t1"] - snap["t_trace"]))
    out = {"correct": bool(correct), "attempted": len(rec.rows),
           "failed": int(checks["requests_failed"]["value"]),
           "metrics": metrics, "device": device}
    if reduced is not None:
        out["breakdown"] = {"device_ops": [list(x) for x in reduced["device_ops"]],
                            "idle_gaps": gaps}
    out["errors"] = [e for e in rec.error if e is not None][:5]
    out["window"] = window
    out["checks"] = checks
    return out


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except BenchError as e:
        print(f"bench: {e}; no result", file=sys.stderr)
        return 2
    for err in out.pop("errors"):
        print(f"failed request: {err}", file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
