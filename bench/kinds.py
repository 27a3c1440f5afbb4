"""Layer kinds, found by name.

Each layer of a configuration's table names its ``kind``; the kind is
the module bench/layers/<kind>.py, which holds everything the benchmark
knows about that kind of layer:

* ``shaped(ly, shape)``: the layer with its geometry, given the shape
  of one image's activation coming in (``(H, W, C)`` or ``(K,)``);
* ``out_shape(sly)``: the shape of one image's activation going out;
* ``macs(sly)``: multiply-accumulates of one image through the layer;
* ``draw(key, ly)``: its raw float32 weights, from one threefry key;
* ``forward(ly, p, h, precision)``: the plain reference's step, float32
  ``jax.numpy`` at ``highest`` (``precision`` is ``"reference"`` or
  ``"control"``); the last layer's output is the logits;
* ``served(ly, p, pack)``: ``(group, entry)`` of the served parameter
  tree, ``pack`` being the program's packer;
* ``rows(sly)``: ``(group, tuple)`` rows the program's model must list
  for the layer, ``group`` being ``conv`` or ``fc`` (bench/program.py);
* ``parts(sly)``: the named layer dicts that plan steps may run, in the
  form bench/kernels/*.py read: each part's ``kind`` is that of a kind
  module whose ``macs`` counts it.

``sly`` is a layer as ``shaped`` gives it, ``ly`` one as the table
states it (a shaped layer serves as well).  Nothing here imports the
program.
"""
from __future__ import annotations

import functools
import importlib.util
import os
import re

BENCH = os.path.dirname(os.path.abspath(__file__))
LAYER_DIRS = [os.path.join(BENCH, "layers")]
_NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_-]*")


class BenchError(RuntimeError):
    """A run that cannot produce a result."""


def load_module(path: str):
    spec = importlib.util.spec_from_file_location(
        "bench_" + os.path.basename(path)[:-3].replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.lru_cache(maxsize=None)
def _load_kind(path: str):
    return load_module(path)


def kind(name: str):
    """The module of layer kind ``name``: the first
    ``<dir>/<name>.py`` of LAYER_DIRS, loaded once a process; a BenchError naming the kind and
    the files looked for where there is none."""
    paths = [os.path.join(d, f"{name}.py") for d in LAYER_DIRS]
    for path in paths if _NAME.fullmatch(name) else ():
        if os.path.isfile(path):
            return _load_kind(path)
    raise BenchError(f"no layer kind {name!r}: looked for {', '.join(paths)}")
