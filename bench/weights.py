"""Seeded weights and images for a configuration's layer table.

Nothing here imports the program.  The same raw weights feed the
served parameters (bench/program.py packs them through the program's
own packer) and the plain reference (bench/reference.py), so the
reference takes nothing the program made.

Weights are drawn on the device in one jitted call: a float32 normal
latent weight per layer (the sign is the binary weight), the entry
layers' per-channel scale alpha = mean |w|, and an integer threshold in
[-3, 3] per output channel of every thresholded layer.  Images are
8-bit pixel values held as float32, drawn on the host.
"""
from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

THRESHOLD_RANGE = 3


def seed_words(seed: int, salt: int = 0) -> np.ndarray:
    """Two uint32 words from any whole-number seed (64 bits and more
    are fine), for a raw threefry key or a numpy generator."""
    return np.random.SeedSequence([int(seed) % (1 << 64), salt]
                                  ).generate_state(2, dtype=np.uint32)


def weight_shapes(layers: List[Dict[str, Any]]) -> List[tuple]:
    """Latent weight shape of each layer: HWIO for convs, [N, K] for
    dense layers."""
    out = []
    for ly in layers:
        if ly["kind"] == "dense":
            out.append((ly["n_out"], ly["n_in"]))
        else:
            out.append((ly["k"], ly["k"], ly["c_in"], ly["c_out"]))
    return out


def draw_fn(layers: List[Dict[str, Any]]):
    """The pure function key -> per-layer ``{"w", "alpha"?, "t"?}``;
    ``make_raw`` jits it, and bench/program.py jits it together with
    the packing into served parameters."""
    shapes = weight_shapes(layers)
    kinds = tuple((ly["kind"], ly.get("threshold", True)) for ly in layers)
    out_ch = tuple(s[0] if k == "dense" else s[-1]
                   for s, (k, _) in zip(shapes, kinds))

    def draw(key):
        ks = jax.random.split(key, len(shapes))
        raw = []
        for kk, shape, (kind, thr), n in zip(ks, shapes, kinds, out_ch):
            kw, kt = jax.random.split(kk)
            p = {"w": jax.random.normal(kw, shape, jnp.float32)}
            if kind == "entry_conv":
                p["alpha"] = jnp.mean(jnp.abs(p["w"]), axis=(0, 1, 2))
            elif thr:
                p["t"] = jax.random.randint(kt, (n,), -THRESHOLD_RANGE,
                                            THRESHOLD_RANGE + 1, jnp.int32)
            raw.append(p)
        return raw

    return draw


def weight_key(seed: int) -> jax.Array:
    """The raw threefry key the weights of ``seed`` are drawn from."""
    return jnp.asarray(seed_words(seed, salt=1))


def make_raw(layers: List[Dict[str, Any]], seed: int) -> List[Dict[str, Any]]:
    """The raw weights of ``seed``, in one jitted call on the device."""
    return jax.jit(draw_fn(layers))(weight_key(seed))


def make_images(shape, n: int, seed: int) -> np.ndarray:
    """``n`` images of ``shape`` (H, W, C): 8-bit pixel values as
    float32, on the host."""
    rng = np.random.default_rng(seed_words(seed, salt=2))
    return rng.integers(0, 256, size=(n, *shape), dtype=np.uint8
                        ).astype(np.float32)
