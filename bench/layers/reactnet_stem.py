"""Layer kind ``reactnet_stem``: the entry conv of a residual BNN, which
opens its float32 stream.

Reference: the input, rounded to the layer's stated ``input_dtype``
(8-bit pixels are exact in it), convolved with sign(w) (w > 0 gives
+1) under real zero padding, an exact integer sum; then its batch norm
as the inference-time affine ``s * alpha + beta`` per output channel.
The control rounds the stream it opens to bfloat16.  Weights: a float32
normal latent HWIO weight; alpha uniform in [0.5, 1.5] / (128 x
sqrt(k * k * c_in)) (a pixel is about 128), rounded to bfloat16;
beta normal with standard deviation 0.1.  Served as the program's
``conv`` entry ``{"w", "alpha", "beta"}``.
"""
import jax
import jax.numpy as jnp

import geometry
import reference as R

shaped = geometry.conv_shaped
macs = geometry.conv_macs


def out_shape(sly):
    return sly["h_out"], sly["w_out"], sly["c_out"]


def to_bf16(h):
    """Finite float32 ``h`` rounded to the nearest bfloat16 (ties to
    even), kept in float32.  Integer ops on the bits: a compiler that
    allows excess precision may fold away a float32 -> bfloat16 ->
    float32 round trip, but not these."""
    b = jax.lax.bitcast_convert_type(h, jnp.uint32)
    b = b + jnp.uint32(0x7FFF) + ((b >> 16) & jnp.uint32(1))
    return jax.lax.bitcast_convert_type(b & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


def bn_scale(key, n, fan):
    """U[0.5, 1.5] / fan, rounded to bfloat16: times an integer sum
    below 2**16 it is exact in float32."""
    k = jax.random.uniform(key, (n,), jnp.float32, 0.5, 1.5) / fan
    return to_bf16(k)


def bias(key, n):
    return 0.1 * jax.random.normal(key, (n,), jnp.float32)


def stream(h, precision):
    """The residual stream after an add: rounded to bfloat16 for the
    control, as it is otherwise."""
    if precision == "control":
        return to_bf16(h)
    return h


def draw(key, ly):
    kw, ka, kb = jax.random.split(key, 3)
    fan = ly["k"] * ly["k"] * ly["c_in"]
    return {"w": jax.random.normal(kw, (ly["k"], ly["k"], ly["c_in"], ly["c_out"]),
                                   jnp.float32),
            "alpha": bn_scale(ka, ly["c_out"], 128.0 * fan ** 0.5),
            "beta": bias(kb, ly["c_out"])}


def forward(ly, p, h, precision):
    hin = h.astype(jnp.dtype(ly["input_dtype"])).astype(jnp.float32)
    s = R.conv(hin, R.sign(p["w"]), ly["stride"], ly["pad"])
    return stream(s * p["alpha"] + p["beta"], precision)


def served(ly, p, pack):
    return "conv", {"w": p["w"], "alpha": p["alpha"], "beta": p["beta"]}


def rows(sly):
    return [geometry.conv_row(sly, integer=True)]


def parts(sly):
    return [dict(sly, kind="reactnet_stem")]
