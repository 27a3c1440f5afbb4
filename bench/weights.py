"""Seeded weights and images for a configuration's layer table.

Nothing here imports the program.  The same raw weights feed the
served parameters (bench/program.py packs them through the program's
own packer) and the plain reference (bench/reference.py), so the
reference takes nothing the program made.

Weights are drawn on the device in one jitted call: the key is split
into one key per layer, and each layer's kind (bench/layers/<kind>.py)
draws that layer's float32 weights from its key.  Images are 8-bit
pixel values held as float32, drawn on the host.
"""
from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from kinds import kind

THRESHOLD_RANGE = 3


def seed_words(seed: int, salt: int = 0) -> np.ndarray:
    """Two uint32 words from any whole-number seed (64 bits and more
    are fine), for a raw threefry key or a numpy generator."""
    return np.random.SeedSequence([int(seed) % (1 << 64), salt]
                                  ).generate_state(2, dtype=np.uint32)


def thresholds(key: jax.Array, n: int) -> jax.Array:
    """``n`` integer thresholds, uniform in [-3, 3] (int32): the stand-in
    for a folded batch norm."""
    return jax.random.randint(key, (n,), -THRESHOLD_RANGE,
                              THRESHOLD_RANGE + 1, jnp.int32)


def draw_fn(layers: List[Dict[str, Any]]):
    """The pure function key -> each layer's raw weights; ``make_raw``
    jits it, and bench/program.py jits it together with the packing
    into served parameters."""
    mods = [kind(ly["kind"]) for ly in layers]

    def draw(key):
        ks = jax.random.split(key, len(layers))
        return [m.draw(kk, ly) for m, ly, kk in zip(mods, layers, ks)]

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
