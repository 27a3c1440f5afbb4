"""Layer kind ``pool_fc``: the float head of a residual BNN, a global
average pool of the stream and a float32 dense with a bias.

Reference: the H*W positions of each channel summed in row-major
order, times 1 / (H*W); then ``pooled @ w + b`` at ``highest``
precision: the logits.  Weights: w normal with standard deviation
1 / sqrt(n_in), [n_in, classes]; b normal with standard deviation
0.1.  Served as the program's ``head`` entry ``{"w", "b"}``.
"""
import jax
import jax.numpy as jnp

import reference as R
from kinds import kind

bias = kind("reactnet_stem").bias


def shaped(ly, shape):
    h, w, c = shape
    if c != ly["n_in"]:
        raise ValueError(f"{ly['name']}: n_in {ly['n_in']}, the stream has {c}")
    return dict(ly, h_in=h, w_in=w)


def out_shape(sly):
    return (sly["classes"],)


def macs(sly):
    return sly["n_in"] * sly["classes"]


def draw(key, ly):
    kw, kb = jax.random.split(key)
    return {"w": jax.random.normal(kw, (ly["n_in"], ly["classes"]), jnp.float32)
            / ly["n_in"] ** 0.5,
            "b": bias(kb, ly["classes"])}


def forward(ly, p, x, precision):
    n, h, w, c = x.shape
    flat = x.reshape(n, h * w, c)
    acc = flat[:, 0]
    for i in range(1, h * w):
        acc = acc + flat[:, i]
    pooled = acc * (1.0 / (h * w))
    return jnp.dot(pooled, p["w"], precision=R.HI) + p["b"]


def served(ly, p, pack):
    return "head", {"w": p["w"], "b": p["b"]}


def rows(sly):
    return [("fc", (sly["name"], sly["n_in"], sly["classes"]))]


def parts(sly):
    return [dict(sly, kind="pool_fc")]
