"""The plain reference: a configuration's forward pass in float32
``jax.numpy`` at ``highest`` matmul precision, from its layer table.

It imports nothing of the program and takes nothing the program made:
its weights come from bench/weights.py, its architecture from the
configuration file.  The semantics it states, layer by layer:

* ``entry_conv``: the input is rounded to the layer's stated
  ``input_dtype`` (the precision the program's float conv runs at),
  convolved with sign(w) (w > 0 gives +1) under real zero padding,
  then scaled by alpha per output channel; an optional float max pool
  follows.
* the first binary layer takes sign(x) of the float activation
  (x > 0 gives +1, else -1).
* ``binary_conv``: +-1 activations padded with -1, convolved with
  sign(w); the output is +1 where the integer sum reaches the
  channel's threshold t, else -1; an optional max pool follows.
* ``dense``: NHWC-flattened +-1 activations times sign(w)^T; with a
  threshold as above, and without one the integer sums are the logits.

``precision="control"`` runs the same forward with every entry conv's
input rounded one step below its stated dtype (``LOWER``): the control
that the comparison has to fail.
"""
from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
LOWER = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn"}


def _sign(x):
    return jnp.where(x > 0, 1.0, -1.0).astype(jnp.float32)


def _conv(x, w, stride, pad):
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(stride, stride),
        padding=((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI)


def _pool(x, pool):
    if not pool:
        return x
    win, s = pool
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, win, win, 1), (1, s, s, 1), "VALID")


def forward(layers: List[Dict[str, Any]], raw: List[Dict[str, Any]],
            x: jax.Array, precision: str = "reference") -> jax.Array:
    """Logits [N, classes] of images ``x`` [N, H, W, C] (float32)."""
    h = x.astype(jnp.float32)
    binary = False
    for ly, p in zip(layers, raw):
        kind = ly["kind"]
        if kind == "entry_conv":
            dt = ly["input_dtype"]
            if precision == "control":
                dt = LOWER[dt]
            hin = h.astype(jnp.dtype(dt)).astype(jnp.float32)
            h = _conv(hin, _sign(p["w"]), ly["stride"], ly["pad"]) * p["alpha"]
            h = _pool(h, ly.get("pool"))
            continue
        if not binary:
            h, binary = _sign(h), True
        if kind == "binary_conv":
            pd = ly["pad"]
            hp = jnp.pad(h, ((0, 0), (pd, pd), (pd, pd), (0, 0)),
                         constant_values=-1.0)
            s = _conv(hp, _sign(p["w"]), ly["stride"], 0)
            h = jnp.where(s >= p["t"], 1.0, -1.0)
            h = _pool(h, ly.get("pool"))
        elif kind == "dense":
            h = h.reshape(h.shape[0], -1)
            s = jnp.dot(h, _sign(p["w"]).T, precision=HI)
            if not ly.get("threshold", True):
                return s
            h = jnp.where(s >= p["t"], 1.0, -1.0)
        else:
            raise ValueError(f"unknown layer kind {kind!r}")
    raise ValueError("the layer table ends without an unthresholded "
                     "dense head")


def logits(layers, raw, images: np.ndarray, block_rows: int,
           precision: str = "reference") -> np.ndarray:
    """The forward over ``images`` in blocks of ``block_rows`` (one
    compiled program; the last block is zero-padded), on the host."""
    fn = jax.jit(lambda r, xb: forward(layers, r, xb, precision))
    out = []
    for i in range(0, len(images), block_rows):
        blk = images[i:i + block_rows]
        n = len(blk)
        if n < block_rows:
            blk = np.concatenate(
                [blk, np.zeros((block_rows - n, *blk.shape[1:]), blk.dtype)])
        out.append(np.asarray(fn(raw, blk))[:n])
    return np.concatenate(out)
