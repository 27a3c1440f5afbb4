"""Layer kind ``dense``: NHWC-flattened +-1 activations times binary
weights.

Reference: sign(x) of the incoming activation (the identity on +-1),
flattened, times sign(w)^T; with ``threshold`` (the default) the output
is +1 where the integer sum reaches the channel's threshold t, else -1;
without one the integer sums are the logits.  Weights: a float32 normal
latent [n_out, n_in] weight and, where thresholded, integer thresholds
in [-3, 3].  Served as the program's ``fc`` entry ``{"wp", "t"?}``, the
weight packed along its inputs.
"""
import jax
import jax.numpy as jnp

import reference as R
import weights


def shaped(ly, shape):
    return dict(ly)


def out_shape(sly):
    return (sly["n_out"],)


def macs(sly):
    return sly["n_in"] * sly["n_out"]


def draw(key, ly):
    kw, kt = jax.random.split(key)
    p = {"w": jax.random.normal(kw, (ly["n_out"], ly["n_in"]), jnp.float32)}
    if ly.get("threshold", True):
        p["t"] = weights.thresholds(kt, ly["n_out"])
    return p


def forward(ly, p, h, precision):
    h = R.sign(h).reshape(h.shape[0], -1)
    s = jnp.dot(h, R.sign(p["w"]).T, precision=R.HI)
    if not ly.get("threshold", True):
        return s
    return jnp.where(s >= p["t"], 1.0, -1.0)


def served(ly, p, pack):
    q = {"wp": pack(p["w"], axis=-1)}
    if "t" in p:
        q["t"] = p["t"]
    return "fc", q


def rows(sly):
    return [("fc", (sly["name"], sly["n_in"], sly["n_out"]))]


def parts(sly):
    return [dict(sly, kind="dense")]
