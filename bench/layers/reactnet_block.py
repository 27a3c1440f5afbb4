"""Layer kind ``reactnet_block``: one block of ReActNet-A
(arXiv:2003.03488 §3-4, Figs. 2-3; the layer order of the authors'
imagenet_training/reactnet.py) over the float32 stream x of c_in
channels, with ``stride`` 1 or 2 and ``c_out`` c_in or 2 x c_in.

Reference, every float step an explicit expression in this order:

    a  = sign(x + rsign1)                   (RSign; x > 0 gives +1)
    s1 = conv3x3_stride(a, sign(w1)), zero padding 1 (exact integers)
    y1 = s1 * bn1_scale + bn1_bias
    sc = x, or ((x00 + x01) + (x10 + x11)) * 0.25 at stride 2
    v1 = (y1 + sc) + move12
    u  = where(v1 > 0, v1, prelu1 * v1) + move13      (RPReLU1)
    b  = sign(u + rsign2)
    s2 = conv1x1(b, sign(w2)); for c_out = 2 c_in the halves
         w2[..., :c_in] and w2[..., c_in:] are two convs whose outputs
         each add u, concatenated
    y2 = s2 * bn2_scale + bn2_bias
    v2 = (y2 + u) + move22
    out = where(v2 > 0, v2, prelu2 * v2) + move23     (RPReLU2)

The control rounds the stream to bfloat16 after every add.  Weights:
float32 normal latent w1 [3, 3, c_in, c_in] and w2 [1, 1, c_in,
c_out]; BN scales uniform in [0.5, 1.5] / sqrt(fan-in), rounded to
bfloat16; every bias (rsign, BN, move) normal with standard deviation
0.1; PReLU slopes uniform in [0.1, 0.3].  Served as the program's
``res`` entry, the weights packed along their input channels.

The program runs the block as four parts: the 3x3 conv (a
``binary_conv`` without threshold: int32 sums), its epilogue, the 1x1
conv, its epilogue.  An epilogue writes the float32 stream and, unless
the table marks the block ``"next_sign": false`` (no binary conv reads
its output), the packed signs of the next conv.
"""
import jax
import jax.numpy as jnp

import reference as R
from kinds import kind

_stem = kind("reactnet_stem")
bias, bn_scale, stream = _stem.bias, _stem.bn_scale, _stem.stream

# per-channel parameters over the c_in channels of u, and over the
# c_out channels of the block's output
PARAMS_IN = ("rsign1", "bn1_scale", "bn1_bias", "move12", "prelu1", "move13",
             "rsign2")
PARAMS_OUT = ("bn2_scale", "bn2_bias", "move22", "prelu2", "move23")


def shaped(ly, shape):
    h, w, c = shape
    if c != ly["c_in"]:
        raise ValueError(f"{ly['name']}: c_in {ly['c_in']}, the stream has {c}")
    s = ly["stride"]
    return dict(ly, h_in=h, w_in=w, h_out=(h - 1) // s + 1, w_out=(w - 1) // s + 1)


def out_shape(sly):
    return sly["h_out"], sly["w_out"], sly["c_out"]


def macs(sly):
    """The block's two binary convs; an epilogue part counts none."""
    if "epilogue" in sly:
        return 0
    px = sly["h_out"] * sly["w_out"]
    return 9 * sly["c_in"] ** 2 * px + sly["c_in"] * sly["c_out"] * px


def draw(key, ly):
    c, f = ly["c_in"], ly["c_out"]
    names = PARAMS_IN + PARAMS_OUT
    ks = jax.random.split(key, 2 + len(names))
    p = {"w1": jax.random.normal(ks[0], (3, 3, c, c), jnp.float32),
         "w2": jax.random.normal(ks[1], (1, 1, c, f), jnp.float32)}
    for name, k in zip(names, ks[2:]):
        n = c if name in PARAMS_IN else f
        if name == "bn1_scale":
            p[name] = bn_scale(k, n, (9 * c) ** 0.5)
        elif name == "bn2_scale":
            p[name] = bn_scale(k, n, c ** 0.5)
        elif name.startswith("prelu"):
            p[name] = jax.random.uniform(k, (n,), jnp.float32, 0.1, 0.3)
        else:
            p[name] = bias(k, n)
    return p


def _rprelu(v, slope, move, precision):
    return stream(jnp.where(v > 0, v, slope * v) + move, precision)


def forward(ly, p, x, precision):
    c = ly["c_in"]
    a = R.sign(x + p["rsign1"])
    s1 = R.conv(a, R.sign(p["w1"]), ly["stride"], 1)
    y1 = s1 * p["bn1_scale"] + p["bn1_bias"]
    if ly["stride"] == 2:
        sc = ((x[:, 0::2, 0::2] + x[:, 0::2, 1::2])
              + (x[:, 1::2, 0::2] + x[:, 1::2, 1::2])) * 0.25
    else:
        sc = x
    v1 = stream(stream(y1 + sc, precision) + p["move12"], precision)
    u = _rprelu(v1, p["prelu1"], p["move13"], precision)
    b = R.sign(u + p["rsign2"])
    w2 = R.sign(p["w2"])
    halves = [w2] if ly["c_out"] == c else [w2[..., :c], w2[..., c:]]
    y2 = jnp.concatenate([R.conv(b, wh, 1, 0) for wh in halves], axis=-1)
    y2 = y2 * p["bn2_scale"] + p["bn2_bias"]
    u2 = jnp.concatenate([u] * len(halves), axis=-1)
    v2 = stream(stream(y2 + u2, precision) + p["move22"], precision)
    return _rprelu(v2, p["prelu2"], p["move23"], precision)


def served(ly, p, pack):
    q = {k: v for k, v in p.items() if k not in ("w1", "w2")}
    q["w1"] = pack(p["w1"], axis=2)
    q["w2"] = pack(p["w2"], axis=2)
    return "res", q


def rows(sly):
    n, c, f = sly["name"], sly["c_in"], sly["c_out"]
    hi, wi, ho, wo = sly["h_in"], sly["w_in"], sly["h_out"], sly["w_out"]
    return [("conv", (f"{n}.conv3x3", c, c, wi, hi, wo, ho, 3, False)),
            ("conv", (f"{n}.conv1x1", c, f, wo, ho, wo, ho, 1, False))]


def _table_rows(sly):
    """Row classes of the 3x3 conv's zero-border table: the interior,
    the first output row, and the last where its window leaves the
    map."""
    s = sly["stride"]
    return 2 + (s * (sly["h_out"] - 1) + 1 >= sly["h_in"])


def parts(sly):
    n, c, f, s = sly["name"], sly["c_in"], sly["c_out"], sly["stride"]
    hi, wi, ho, wo = sly["h_in"], sly["w_in"], sly["h_out"], sly["w_out"]
    conv = dict(kind="binary_conv", threshold=False, pool=None)
    ep = dict(kind="reactnet_block", h_out=ho, w_out=wo, shortcut=c)
    return [
        dict(conv, name=f"{n}.conv3x3", c_in=c, c_out=c, k=3, stride=s, pad=1,
             h_in=hi, w_in=wi, h_out=ho, w_out=wo),
        dict(ep, name=f"{n}.epilogue1", epilogue=1, channels=c, pack=True,
             table_rows=_table_rows(sly)),
        dict(conv, name=f"{n}.conv1x1", c_in=c, c_out=f, k=1, stride=1, pad=0,
             h_in=ho, w_in=wo, h_out=ho, w_out=wo),
        dict(ep, name=f"{n}.epilogue2", epilogue=2, channels=f,
             pack=sly.get("next_sign", True), table_rows=0),
    ]
