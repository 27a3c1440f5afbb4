"""Readings that a cell's correctness limits are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seconds 51 \\
        --seeds <n> ... --control-seeds <n> ...

For every ``--seeds`` seed it makes one whole run of the cell (window
included, tracing off) and prints the numbers compared: the program's
readings, of which the largest is a limit's lower reading.  For every
``--control-seeds`` seed it puts the control in the program's place:
the plain reference with each entry conv's input one precision step
below the configuration's (bench/reference.py ``LOWER``), over every
image of the cell's pool, and prints the same numbers against the
reference; the smallest is the upper reading.  One JSON line per
reading.  The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

import run  # puts bench/ and src/ on the path


def control_reading(cell_name: str, seed: int) -> dict:
    import reference
    import weights

    spec = run._load_json(run.BENCHMARK_JSON)
    cell = {w["name"]: w for w in spec["workloads"]}[cell_name]
    entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg = run._load_json(os.path.join(run.ROOT, entry["file"]))
    mix = run._load_json(os.path.join(run.TRAFFIC_DIR, cell["traffic"] + ".json"))
    pool = weights.make_images(cfg["input"], int(mix["pool_images"]), seed)
    raw = weights.make_raw(cfg["layers"], seed)
    rows = cfg["reference_block_rows"]
    want = reference.logits(cfg["layers"], raw, pool, rows)
    got = reference.logits(cfg["layers"], raw, pool, rows, precision="control")
    gap, bad = run.logit_gaps(got, want, cfg["compare"].get("row_gap", 0.0))
    checks = run.checks_of(gap, bad, len(pool), 0, cfg["compare"])
    return {"kind": "control", "cell": cell_name, "seed": seed,
            "checks": checks, "correct": all(c["value"] <= c["limit"]
                                             for c in checks.values())}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args()
    for seed in args.seeds:
        out = run.run(args.workload, seed, args.seconds, False)
        print(json.dumps({"kind": "program", "cell": args.workload, "seed": seed,
                          "correct": out["correct"], "checks": out["checks"],
                          "metrics": out["metrics"], "window": out["window"],
                          "device": out["device"]}), flush=True)
        gc.collect()
    for seed in args.control_seeds:
        print(json.dumps(control_reading(args.workload, seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
