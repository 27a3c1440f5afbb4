"""The plain reference: a configuration's forward pass in float32
``jax.numpy`` at ``highest`` matmul precision, from its layer table.

It imports nothing of the program and takes nothing the program made:
its weights come from bench/weights.py, its architecture from the
configuration file.  Each layer's kind (bench/layers/<kind>.py) states
its own semantics and takes the activation one step; the last layer's
output is the logits.  The helpers below are the operations the kinds
share.

``precision="control"`` runs the same forward with every entry conv's
input rounded one step below its stated dtype (``LOWER``): the control
that the comparison has to fail.
"""
from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from kinds import kind

HI = jax.lax.Precision.HIGHEST
LOWER = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn"}


def sign(x):
    """+1 where x > 0, else -1 (float32)."""
    return jnp.where(x > 0, 1.0, -1.0).astype(jnp.float32)


def conv(x, w, stride, pad):
    """NHWC x HWIO convolution with real zero padding, at ``HI``."""
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(stride, stride),
        padding=((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI)


def max_pool(x, pool):
    """A ``[window, stride]`` max pool, or ``x`` itself for none."""
    if not pool:
        return x
    win, s = pool
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, win, win, 1), (1, s, s, 1), "VALID")


def forward(layers: List[Dict[str, Any]], raw: List[Dict[str, Any]],
            x: jax.Array, precision: str = "reference") -> jax.Array:
    """Logits [N, classes] of images ``x`` [N, H, W, C] (float32)."""
    h = x.astype(jnp.float32)
    for ly, p in zip(layers, raw):
        h = kind(ly["kind"]).forward(ly, p, h, precision)
    return h


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
