"""Layer kind ``binary_conv``: a conv of +-1 activations with binary
weights and an integer threshold per output channel.

Reference: sign(x) of the incoming activation (x > 0 gives +1, else -1;
the identity on +-1), padded with -1, convolved with sign(w); the
output is +1 where the integer sum reaches the channel's threshold t,
else -1; an optional max pool follows.  Weights: a float32 normal
latent HWIO weight and integer thresholds in [-3, 3].  Served as the
program's ``conv`` entry ``{"wf", "t"}``, the weight packed along its
input channels.
"""
import jax
import jax.numpy as jnp

import geometry
import reference as R
import weights

shaped = geometry.conv_shaped
out_shape = geometry.conv_out_shape
macs = geometry.conv_macs


def draw(key, ly):
    kw, kt = jax.random.split(key)
    return {"w": jax.random.normal(kw, (ly["k"], ly["k"], ly["c_in"], ly["c_out"]),
                                   jnp.float32),
            "t": weights.thresholds(kt, ly["c_out"])}


def forward(ly, p, h, precision):
    pd = ly["pad"]
    hp = jnp.pad(R.sign(h), ((0, 0), (pd, pd), (pd, pd), (0, 0)),
                 constant_values=-1.0)
    s = R.conv(hp, R.sign(p["w"]), ly["stride"], 0)
    return R.max_pool(jnp.where(s >= p["t"], 1.0, -1.0), ly.get("pool"))


def served(ly, p, pack):
    return "conv", {"wf": pack(p["w"], axis=2), "t": p["t"]}


def rows(sly):
    return [geometry.conv_row(sly, integer=False)]


def parts(sly):
    return [dict(sly, kind="binary_conv")]
