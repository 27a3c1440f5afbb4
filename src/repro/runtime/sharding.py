"""Sharding rules: logical-axis -> mesh-axis mapping with divisibility
fallbacks, parameter PartitionSpec trees, and activation constraints.

Mesh axes (launch/mesh.py):
  single-pod: ("data", "model")       = (16, 16)
  multi-pod:  ("pod", "data", "model") = (2, 16, 16)

Policy (DESIGN.md §4):
  * FSDP/ZeRO-3 over "data": every parameter is additionally sharded on
    its largest remaining dim over "data"; XLA all-gathers per layer.
  * TP over "model": attention heads / d_ff / vocab.
  * "pod" is pure DP (gradient all-reduce crosses pods only).
  * any dim not divisible by its mesh axis falls back to replication —
    never a crash (e.g. 10-head recurrentgemma attention).
"""
from __future__ import annotations

import re
from typing import Any, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def axis_size(mesh: Optional[Mesh], name: str) -> int:
    if mesh is None or name not in mesh.shape:
        return 1
    return mesh.shape[name]


def fit_spec(shape: Sequence[int], want: Sequence[Any],
             mesh: Optional[Mesh]) -> P:
    """Drop mesh axes that don't divide their dim (replicate instead)."""
    out = []
    for dim, ax in zip(shape, want):
        if ax is None:
            out.append(None)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        keep = []
        rem = dim
        for a in axes:
            s = axis_size(mesh, a)
            if s > 1 and rem % s == 0:
                keep.append(a)
                rem //= s
        out.append(tuple(keep) if len(keep) > 1 else
                   (keep[0] if keep else None))
    return P(*out)


def shard_act(x: jax.Array, want: Sequence[Any]) -> jax.Array:
    """with_sharding_constraint that no-ops outside a mesh context and
    degrades gracefully on non-divisible dims (fit_spec replicates
    them); any other sharding error propagates."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    return jax.lax.with_sharding_constraint(x, fit_spec(x.shape, want, mesh))


# ------------------------------------------------------------------ #
# parameter sharding rules                                             #
# ------------------------------------------------------------------ #
# rules matched against the '/'-joined param path; first match wins.
# specs are *logical*: "model" = TP axis, "fsdp" = the data axis reused
# for ZeRO-3 parameter sharding.  Packed projections are PackedArray
# pytree nodes whose words leaf flattens to a ".../{name}_p/words"
# path — the optional (/words)? suffix lets the same rule shard the
# words (same rank as the latent weight, K replaced by K/32).
_RULES: Tuple[Tuple[str, Tuple[Any, ...]], ...] = (
    # embeddings / logits: vocab on model, d_model on fsdp
    (r"embed|lm_head",                 ("model", "fsdp")),
    (r"pos_emb",                       (None, "fsdp")),
    # attention projections (leading layer-stack dim handled separately)
    (r"attn/(wq|wk|wv)(_p)?(/words)?$", ("fsdp", "model")),
    (r"attn/(bq|bk|bv)$",              ("model",)),
    (r"attn/wo(_p)?(/words)?$",        ("model", "fsdp")),
    (r"_alpha$",                       (None,)),
    (r"attn/bo$",                      (None,)),
    # MoE: experts on fsdp when divisible, d_ff on model
    (r"moe/router$",                   ("fsdp", None)),
    (r"moe/(w_gate|w_up)(_p)?(/words)?$", ("fsdp", None, "model")),
    (r"moe/w_down(_p)?(/words)?$",     ("fsdp", "model", None)),
    # dense FFN
    (r"mlp/(w_gate|w_up)(_p)?(/words)?$", ("fsdp", "model")),
    (r"mlp/w_down(_p)?(/words)?$",     ("model", "fsdp")),
    (r"mlp/(b_gate|b_up)$",            ("model",)),
    (r"mlp/b_down$",                   (None,)),
    # mamba
    (r"ssm/in_proj(_p)?(/words)?$",    ("fsdp", "model")),
    (r"ssm/conv_w$",                   ("model", None)),
    (r"ssm/conv_b$",                   ("model",)),
    (r"ssm/x_proj$",                   ("model", None)),
    (r"ssm/dt_proj$",                  (None, "model")),
    (r"ssm/dt_bias$",                  ("model",)),
    (r"ssm/(A_log|D)$",                ("model", None)),
    (r"ssm/out_proj(_p)?(/words)?$",   ("model", "fsdp")),
    # rg-lru
    (r"lru/(in_proj|gate_proj)(_p)?(/words)?$", ("fsdp", "model")),
    (r"lru/conv_w$",                   ("model", None)),
    (r"lru/(a_param|conv_b|in_bias|gate_bias)$", ("model",)),
    (r"lru/out_proj(_p)?(/words)?$",   ("model", "fsdp")),
    # norms, scales, biases: replicate (small)
    (r"norm|scale|bias",               (None,)),
)


def spec_for_param(path: str, shape: Sequence[int],
                   mesh: Optional[Mesh], stacked: bool,
                   fsdp_axis: str = "data") -> P:
    """PartitionSpec for one parameter.

    stacked: params inside a scan-over-layers stack carry a leading
    [n_layers] dim that stays unsharded."""
    want: Optional[Tuple[Any, ...]] = None
    core_shape = shape[1:] if stacked else shape
    for pat, spec in _RULES:
        if re.search(pat, path):
            want = spec
            break
    if want is None or len(want) != len(core_shape):
        want = (None,) * len(core_shape)
    want = tuple(fsdp_axis if a == "fsdp" else a for a in want)
    spec = fit_spec(core_shape, want, mesh)
    if stacked:
        spec = P(None, *spec)
    # ZeRO-3 fallback: if nothing got the fsdp axis, put it on the
    # largest remaining divisible dim
    if mesh is not None and fsdp_axis in mesh.shape:
        flat = list(spec)
        used = {a for s in flat if s for a in
                ((s,) if isinstance(s, str) else s)}
        if fsdp_axis not in used:
            size = axis_size(mesh, fsdp_axis)
            dims = sorted(range(len(core_shape)),
                          key=lambda i: -core_shape[i])
            off = 1 if stacked else 0
            for i in dims:
                cur = flat[i + off]
                if cur is None and core_shape[i] % size == 0 \
                        and core_shape[i] >= 4 * size:
                    flat[i + off] = fsdp_axis
                    break
            spec = P(*flat)
    return spec


def param_specs(params: Any, mesh: Optional[Mesh],
                stacked_prefixes: Tuple[str, ...] = ("layers",),
                fsdp_axis: str = "data") -> Any:
    """PartitionSpec tree for a parameter pytree (dict-of-dicts)."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    treedef = jax.tree_util.tree_structure(params)
    specs = []
    for path, leaf in flat:
        pstr = "/".join(_key_str(k) for k in path)
        stacked = any(pstr.startswith(p) for p in stacked_prefixes)
        specs.append(spec_for_param(pstr, np.shape(leaf), mesh, stacked,
                                    fsdp_axis))
    return jax.tree_util.tree_unflatten(treedef, specs)


def _key_str(k) -> str:
    if hasattr(k, "key"):
        return str(k.key)
    if hasattr(k, "idx"):
        return str(k.idx)
    if hasattr(k, "name"):      # GetAttrKey (e.g. PackedArray.words)
        return str(k.name)
    return str(k)


def named(tree_specs: Any, mesh: Mesh) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_specs,
                        is_leaf=lambda x: isinstance(x, P))


# ------------------------------------------------------------------ #
# batch / cache shardings                                              #
# ------------------------------------------------------------------ #
BATCH_AXES = ("pod", "data")


def batch_specs(batch: Any, mesh: Optional[Mesh]) -> Any:
    """Input-batch PartitionSpecs: batch dim over (pod, data); d_model-
    like trailing dims of frontend embeddings over model; KV caches get
    split-KV sharding (seq over model when heads don't divide)."""
    flat = jax.tree_util.tree_flatten_with_path(batch)[0]
    treedef = jax.tree_util.tree_structure(batch)
    specs = []
    for path, leaf in flat:
        pstr = "/".join(_key_str(k) for k in path)
        shape = np.shape(leaf)
        specs.append(_batch_leaf_spec(pstr, shape, mesh))
    return jax.tree_util.tree_unflatten(treedef, specs)


def _batch_leaf_spec(path: str, shape, mesh) -> P:
    nd = len(shape)
    last = path.rsplit("/", 1)[-1]
    if "caches" in path:
        # stacked cache leaves carry a leading [n_cycles] dim
        lead = (None,) if nd >= 3 and "layers" in path else ()
        core = shape[len(lead):]
        if last in ("k", "v"):
            # [B, W(seq), H, D]: heads over model if divisible, else
            # split-KV (seq over model)
            hdim = core[2] if len(core) >= 4 else 1
            if mesh is not None and axis_size(mesh, "model") > 1 \
                    and hdim % axis_size(mesh, "model") == 0:
                want = lead + (BATCH_AXES, None, "model", None)
            else:
                want = lead + (BATCH_AXES, "model", None, None)
        elif last in ("pos", "k_scale", "v_scale"):
            want = lead + (BATCH_AXES,) + (None,) * (len(core) - 1)
        elif last == "conv":
            want = lead + (BATCH_AXES, None, "model")
        elif last == "h":
            want = lead + (BATCH_AXES, "model") + (None,) * (len(core) - 2)
        else:
            want = lead + (BATCH_AXES,) + (None,) * (len(core) - 1)
        want = want[:nd]
    elif last in ("frames", "image_embeds"):
        want = (BATCH_AXES, None, "model")
    else:  # tokens / targets / step
        want = (BATCH_AXES,) + (None,) * (nd - 1)
    return fit_spec(shape, want, mesh)
