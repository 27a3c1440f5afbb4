"""Shapes of a configuration's layer table (bench/configs/<config>.json),
shared by the program glue, the kernel counts and the metrics."""
from __future__ import annotations

from typing import Any, Dict, List


def layer_shapes(cfg: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Each layer of the table; conv layers with their input and output
    maps' ``h_in``, ``w_in``, ``h_out``, ``w_out`` (before any pool)."""
    h, w, _ = cfg["input"]
    out = []
    for ly in cfg["layers"]:
        if ly["kind"] == "dense":
            out.append(dict(ly))
            continue
        ho = (h + 2 * ly["pad"] - ly["k"]) // ly["stride"] + 1
        wo = (w + 2 * ly["pad"] - ly["k"]) // ly["stride"] + 1
        out.append(dict(ly, h_in=h, w_in=w, h_out=ho, w_out=wo))
        h, w = ho, wo
        if ly.get("pool"):
            win, s = ly["pool"]
            h, w = (h - win) // s + 1, (w - win) // s + 1
    return out


def macs(ly: Dict[str, Any]) -> int:
    """Multiply-accumulates of one image through one layer."""
    if ly["kind"] == "dense":
        return ly["n_in"] * ly["n_out"]
    return ly["k"] ** 2 * ly["c_in"] * ly["c_out"] * ly["h_out"] * ly["w_out"]


def macs_per_image(cfg: Dict[str, Any]) -> int:
    return sum(macs(ly) for ly in layer_shapes(cfg))
