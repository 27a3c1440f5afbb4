"""Shapes of a configuration's layer table (bench/configs/<config>.json),
shared by the program glue, the kernel counts and the metrics.  Each
layer's kind (bench/kinds.py) knows its own shapes; the ``conv_*``
helpers are the arithmetic the conv kinds share."""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

from kinds import kind


def _walk(cfg: Dict[str, Any]) -> Tuple[List[Dict[str, Any]], tuple]:
    shape = tuple(cfg["input"])
    out = []
    for ly in cfg["layers"]:
        k = kind(ly["kind"])
        sly = k.shaped(ly, shape)
        out.append(sly)
        shape = tuple(k.out_shape(sly))
    return out, shape


def layer_shapes(cfg: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Each layer of the table with its geometry; conv layers with their
    input and output maps' ``h_in``, ``w_in``, ``h_out``, ``w_out``
    (before any pool)."""
    return _walk(cfg)[0]


def final_shape(cfg: Dict[str, Any]) -> tuple:
    """The shape of one image's output: ``(classes,)`` for logits."""
    return _walk(cfg)[1]


def macs(ly: Dict[str, Any]) -> int:
    """Multiply-accumulates of one image through one shaped layer or
    part."""
    return kind(ly["kind"]).macs(ly)


def macs_per_image(cfg: Dict[str, Any]) -> int:
    return sum(macs(ly) for ly in layer_shapes(cfg))


def conv_shaped(ly: Dict[str, Any], shape: tuple) -> Dict[str, Any]:
    h, w, _ = shape
    ho = (h + 2 * ly["pad"] - ly["k"]) // ly["stride"] + 1
    wo = (w + 2 * ly["pad"] - ly["k"]) // ly["stride"] + 1
    return dict(ly, h_in=h, w_in=w, h_out=ho, w_out=wo)


def conv_out_shape(sly: Dict[str, Any]) -> tuple:
    """The output map after the layer's optional max pool."""
    h, w = sly["h_out"], sly["w_out"]
    if sly.get("pool"):
        win, s = sly["pool"]
        h, w = (h - win) // s + 1, (w - win) // s + 1
    return h, w, sly["c_out"]


def conv_macs(sly: Dict[str, Any]) -> int:
    return sly["k"] ** 2 * sly["c_in"] * sly["c_out"] * sly["h_out"] * sly["w_out"]


def conv_row(sly: Dict[str, Any], integer: bool) -> Tuple[str, tuple]:
    """The layer as a ``core.workloads`` conv row: (name, z1, z2, x1,
    y1, x2, y2, k, integer)."""
    return "conv", (sly["name"], sly["c_in"], sly["c_out"], sly["w_in"],
                    sly["h_in"], sly["w_out"], sly["h_out"], sly["k"], integer)
