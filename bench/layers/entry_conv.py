"""Layer kind ``entry_conv``: the program's integer entry layer, a conv
of the float activation with binary weights scaled per output channel.

Reference: the input is rounded to the layer's stated ``input_dtype``
(the precision the program's float conv runs at; one step lower,
``reference.LOWER``, for the control), convolved with sign(w) (w > 0
gives +1) under real zero padding, then scaled by alpha per output
channel; an optional float max pool follows.  Weights: a float32
normal latent HWIO weight and alpha = mean |w| per output channel.
Served as the program's ``conv`` entry ``{"w", "alpha"}``.
"""
import jax
import jax.numpy as jnp

import geometry
import reference as R

shaped = geometry.conv_shaped
out_shape = geometry.conv_out_shape
macs = geometry.conv_macs


def draw(key, ly):
    kw, _ = jax.random.split(key)
    w = jax.random.normal(kw, (ly["k"], ly["k"], ly["c_in"], ly["c_out"]), jnp.float32)
    return {"w": w, "alpha": jnp.mean(jnp.abs(w), axis=(0, 1, 2))}


def forward(ly, p, h, precision):
    dt = ly["input_dtype"]
    if precision == "control":
        dt = R.LOWER[dt]
    hin = h.astype(jnp.dtype(dt)).astype(jnp.float32)
    h = R.conv(hin, R.sign(p["w"]), ly["stride"], ly["pad"]) * p["alpha"]
    return R.max_pool(h, ly.get("pool"))


def served(ly, p, pack):
    return "conv", {"w": p["w"], "alpha": p["alpha"]}


def rows(sly):
    return [geometry.conv_row(sly, integer=True)]


def parts(sly):
    return [dict(sly, kind="entry_conv")]
