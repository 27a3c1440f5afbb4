"""compile(spec) -> CompiledBNN: one spec, two targets (DESIGN.md §8).

The paper's architecture is a *compiler*: "novel algorithms for mapping
arbitrary nodes of a BNN onto the TULIP-PEs" (§IV).  This module is
that shape as an API — a declarative :class:`~repro.graph.ir.BNNSpec`
goes in, and the :class:`CompiledBNN` that comes out drives BOTH

  * the packed Pallas/XLA executable (``init`` / ``apply`` — bit-
    identical to the legacy builder chain on every backend, int32
    activations never materialized in HBM), and
  * the TULIP-PE schedule model (``tulip_mapping`` / ``table3_rows``
    bridging into core/mapping.py rows and core/schedules.py
    fragments, ``traffic`` for the HBM byte model).

Pipeline (see graph/passes.py for passes 2-5):
  (1) lower — core/workloads.py dataclasses into the IR,
  (2) fold BN to per-channel thresholds (param-bind time: FoldedThreshold
      params are rewritten through core.bnn_layers.fold_* with the
      gamma<0 row negation absorbed into the weights),
  (3) segment dense runs into megakernel launches under the VMEM budget,
  (4) pick the conv impl via the shared VMEM estimate,
  (5) prefetch every launch's autotune key.

The legacy builders (models.layers.packed_cnn_*, packed_mlp,
core.bnn_layers.bnn_mlp_serve_folded) are thin deprecated shims over
this entry point.

A residual spec (graph/ir.py ResidualEntry / ResidualBlock / PoolFC)
carries ``(stream, packed bits or int32 sums)`` from step to step: the
float32 stream beside the packed input of the next binary conv.
"""
from __future__ import annotations

import functools
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core.bnn_layers import (FoldedThreshold, binary_conv,
                                   binary_weight_conv,
                                   fold_to_channel_thresholds,
                                   maxpool_packed)
from repro.core.mapping import (TULIP, YODANN, ArchParams, map_conv,
                                map_fc, table3_rows)
from repro.core.schedules import compare_fragment, maxpool_fragment
from repro.core.workloads import Workload
from repro.graph.ir import (BinaryConv, BinaryDense, BNNSpec,
                            IntegerEntry, MaxPool, PoolFC, ResidualBlock,
                            from_dense_stack, from_workload,
                            spec_to_workload)
from repro.graph.passes import (PlanStep, batches_tuning_keys, build_plan,
                                mxu_convs, plan_tuning_keys)
from repro.kernels import ops as kops
from repro.kernels.fused_mlp import fused_binary_mlp
from repro.kernels.packed import PackedArray
from repro.kernels.residual import (avg_pool2, border_classes,
                                    border_table, epilogue_bytes)
from repro.runtime import spans

__all__ = ["CompiledBNN", "compile", "compile_dense_stack",
           "serve_folded_stack"]


def _maxpool_float(x: jax.Array, window: int, stride: int) -> jax.Array:
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, window, window, 1),
        (1, stride, stride, 1), "VALID")


def _global_mean(x: jax.Array) -> jax.Array:
    """Global average pool of NHWC ``x``: the H*W positions summed in
    row-major order, times 1 / (H*W)."""
    n, hh, ww, c = x.shape
    flat = x.reshape(n, hh * ww, c)
    acc = flat[:, 0]
    for i in range(1, hh * ww):
        acc = acc + flat[:, i]
    return acc * (1.0 / (hh * ww))


# a residual block's per-channel parameters of each epilogue, in
# kernels.residual.VEC_ROWS order (the last row, the bias of the next
# sign, is the next conv's)
_EPILOGUE_PARAMS = {1: ("bn1_scale", "bn1_bias", "move12", "prelu1",
                        "move13"),
                    2: ("bn2_scale", "bn2_bias", "move22", "prelu2",
                        "move23")}


def _bn_scale(key: jax.Array, n: int, fan: float) -> jax.Array:
    """Inference-time BN scales U[0.5, 1.5] / fan, rounded to bfloat16:
    8 significant bits times an integer sum below 2**16 is exact in
    float32, so the scale-and-bias gives the same bits whether or not
    a compiler fuses the multiply into the add."""
    k = jax.random.uniform(key, (n,), jnp.float32, 0.5, 1.5) / fan
    return k.astype(jnp.bfloat16).astype(jnp.float32)


def _bias(key: jax.Array, n: int) -> jax.Array:
    return 0.1 * jax.random.normal(key, (n,), jnp.float32)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _border_table(wf: PackedArray, h: int, w: int, stride: int,
                  pad: int) -> jax.Array:
    return border_table(wf, h, w, stride, pad)[0]


def _bind_dense(p: Dict[str, Any]) -> Tuple[PackedArray, Any]:
    """Pass 2 at param-bind time: a FoldedThreshold param is rewritten
    to the fused per-channel form (gamma<0 flips absorbed into the
    weight words, T' = 1 - T)."""
    wp, t = p["wp"], p.get("t")
    if isinstance(t, FoldedThreshold):
        wp, t = fold_to_channel_thresholds(wp, t)
    return wp, t


class CompiledBNN:
    """The executable + analyzable artifact ``compile`` returns.

    ``plan`` is the tuple of :class:`~repro.graph.passes.PlanStep`
    (every lowering decision, human-readable via ``describe()``);
    ``tuning_keys`` are the autotune keys prefetched for its launches.
    """

    def __init__(self, spec: BNNSpec, plan: Tuple[PlanStep, ...],
                 backend: Optional[str], vmem_budget: Optional[int],
                 batch: int):
        self.spec = spec
        self.plan = plan
        self.backend = backend
        self.vmem_budget = vmem_budget
        self.batch = batch
        self.tuning_keys: Tuple[tuple, ...] = tuple(
            k for s in plan for k in s.keys)

    # -------------------------------------------------------------- #
    def describe(self) -> str:
        be = self.backend or kops.default_backend()
        head = (f"compiled {self.spec.name} "
                f"(input {self.spec.input_shape}, backend {be}, "
                f"batch hint {self.batch}): "
                f"{len(self.plan)} steps, "
                f"{self.launch_count()} kernel launches "
                f"(legacy chain: {self.legacy_launch_count()})")
        return "\n".join([head] + [f"  {s}" for s in self.plan])

    # plan steps that are one kernel launch each (a residual_conv on
    # the MXU is an XLA conv, not a launch)
    _LAUNCHES = ("binarize", "binary_conv", "dense", "residual_entry",
                 "residual_conv", "residual_epilogue")

    @classmethod
    def _launches(cls, s: PlanStep) -> int:
        if s.kind == "fused_stack":
            return 1
        return int(s.kind in cls._LAUNCHES
                   and s.args.get("impl") != "mxu")

    def launch_count(self) -> int:
        """Kernel launches per forward pass under this plan (the
        integer-entry and MXU XLA convs, the float head and reshapes
        don't count; a residual block is two epilogues, plus its two
        convs where they are forced off the MXU)."""
        return sum(self._launches(s) for s in self.plan)

    def legacy_launch_count(self) -> int:
        """What the legacy layer-by-layer builder chain would launch:
        every fused_stack segment unrolls to one launch per layer."""
        return sum(len(s.args["fc_indices"]) if s.kind == "fused_stack"
                   else self._launches(s) for s in self.plan)

    def tuning_keys_for_batch(self, batch: int) -> Tuple[tuple, ...]:
        """The autotune keys this plan's launches resolve to at a
        different batch size — the SAME plan (segment boundaries, conv
        impls), only the row terms rescaled.  The serving engine
        (repro.serving.BNNServer) calls this once per batch bucket and
        feeds the result to ``kernels.autotune.warm`` instead of
        recompiling per bucket."""
        if batch == self.batch:
            return self.tuning_keys
        return plan_tuning_keys(self.spec, self.plan, batch,
                                backend=self.backend,
                                vmem_budget=self.vmem_budget)

    def tuning_keys_for_batches(self, batches: Sequence[int]
                                ) -> Tuple[tuple, ...]:
        """Deduplicated union of ``tuning_keys_for_batch`` over many
        batch sizes — the serving engine's prewarm set: one call covers
        every (bucket, ragged-valid) dispatch level the bucketing
        policy admits (serving/bucketing.py ``dispatch_grid``)."""
        return batches_tuning_keys(self.spec, self.plan, batches,
                                   backend=self.backend,
                                   vmem_budget=self.vmem_budget)

    def with_backend(self, backend: Optional[str]) -> "CompiledBNN":
        """Recompile this spec for a different execution backend —
        same spec, same vmem budget, same batch hint, so the plan is
        re-derived under the target backend's rules.  Every backend is
        bit-identical on the same inputs (the registry contract), which
        is what makes this the serving engine's graceful-degradation
        hook: a pallas kernel-launch failure re-executes the flight on
        the xla path with byte-for-byte identical results."""
        if backend == self.backend:
            return self
        return compile(self.spec, backend=backend,
                       vmem_budget=self.vmem_budget, batch=self.batch)

    def serving_jit_kwargs(self, donate: bool = True) -> dict:
        """The jit contract a serving engine wraps ``apply`` with —
        owned by the compiler so the server cannot drift from the
        executable's signature:

        * ``valid_rows`` is a *static* argument (it changes launch
          shapes — one trace per (bucket, valid) pair, bounded by the
          bucketing policy);
        * the batch input ``x`` (argnum 1) may be **donated**: its
          buffer is consumed by the dispatch, letting XLA reuse the
          allocation for same-shaped intermediates, so steady-state
          serving stops allocating a fresh input block per batch on
          backends that honor donation (TPU/GPU; CPU ignores it).
          The caller must therefore pass a buffer it owns —
          ``BNNServer`` pads/copies into a server-owned staging buffer
          before every donated dispatch; host image rows reach it as
          a fresh flat ``[rows, H*W*C]`` device buffer, which the
          server's wrapper reshapes back inside the jit (DESIGN.md
          §10).  ``params``
          (argnum 0) are NEVER donated: they are replicated once and
          reused by every dispatch.
        """
        kw: dict = {"static_argnames": ("valid_rows",)}
        if donate:
            kw["donate_argnums"] = (1,)
        return kw

    def audit(self, params: Optional[Dict[str, Any]] = None,
              x: Any = None, batch: Optional[int] = None,
              max_batch: int = 64) -> Any:
        """Design-rule check this artifact (repro.analysis.jaxpr_audit,
        DESIGN.md §13): no banned int32 activation in the traced jaxpr
        (kernel backends), plan residency claims re-derived under the
        budget, the donation contract, and the bucketed trace bound.
        Raises :class:`~repro.analysis.jaxpr_audit.AuditError` on any
        violation; returns the :class:`AuditReport` otherwise."""
        from repro.analysis.jaxpr_audit import audit_compiled
        return audit_compiled(self, params=params, x=x, batch=batch,
                              max_batch=max_batch).raise_if_failed()

    # -------------------------------------------------------------- #
    def bind(self, params: Dict[str, Any]) -> Dict[str, Any]:
        """The parameter tree as ``apply`` reads it, prepared once, not
        per forward: each ResidualBlock's entry gains ``border``, the
        zero-border table of its 3x3 conv (kernels.residual
        .border_table of the packed ``w1``).  A spec without residual
        blocks is returned as it is.  ``init`` and ``BNNServer`` bind."""
        blocks = self.spec.residual_blocks
        if not blocks:
            return params
        res = [dict(p, border=_border_table(p["w1"], nd.h_in, nd.w_in,
                                            nd.stride, 1))
               for nd, p in zip(blocks, params["res"])]
        return dict(params, res=res)

    def init(self, key: jax.Array, threshold_range: int = 3,
             dtype: Any = jnp.float32) -> Dict[str, Any]:
        """Random packed serving parameters for the spec — key-split
        order and shapes are bit-compatible with the legacy
        packed_cnn_init (integer entries keep float latent weights +
        alpha; binary convs hold channel-packed filters + per-channel
        int32 thresholds standing in for folded BN; dense layers hold
        [N, K] PackedArrays, thresholded ones a ``t`` vector)."""
        if self.spec.residual_blocks:
            return self._init_residual(key)
        conv_nodes = self.spec.conv_nodes
        dense_nodes = self.spec.dense_nodes
        thresholded = [self.spec.thresholded(n) for n in dense_nodes]
        ks = jax.random.split(key, len(conv_nodes) + len(dense_nodes))
        params: Dict[str, Any] = {"conv": [], "fc": []}
        for i, nd in enumerate(conv_nodes):
            w = jax.random.normal(ks[i], (nd.kh, nd.kw, nd.c_in,
                                          nd.c_out), dtype)
            if isinstance(nd, IntegerEntry):
                alpha = jnp.mean(jnp.abs(w.astype(jnp.float32)),
                                 axis=(0, 1, 2))
                params["conv"].append({"w": w, "alpha": alpha})
            else:
                t = jax.random.randint(jax.random.fold_in(ks[i], 1),
                                       (nd.c_out,), -threshold_range,
                                       threshold_range + 1, jnp.int32)
                params["conv"].append({"wf": PackedArray.pack(w, axis=2),
                                       "t": t})
        for j, nd in enumerate(dense_nodes):
            kj = ks[len(conv_nodes) + j]
            w = jax.random.normal(kj, (nd.n_out, nd.n_in), dtype)
            p = {"wp": PackedArray.pack(w, axis=-1)}
            if thresholded[j]:
                p["t"] = jax.random.randint(
                    jax.random.fold_in(kj, 1), (nd.n_out,),
                    -threshold_range, threshold_range + 1, jnp.int32)
            params["fc"].append(p)
        return params

    def _init_residual(self, key: jax.Array) -> Dict[str, Any]:
        """Random parameters of a residual spec: ``conv`` holds the
        entry conv (latent ``w``, BN scale ``alpha`` and bias
        ``beta``), ``res`` one dict per ResidualBlock (packed ``w1``
        [3, 3, C, C] and ``w2`` [1, 1, C, C_out], the RSign biases
        ``rsign1``/``rsign2`` and each epilogue's ``_EPILOGUE_PARAMS``),
        ``head`` the PoolFC dense (``w`` [n_in, classes], ``b``).  BN
        keeps each branch O(1): scales U[0.5, 1.5] / sqrt(fan-in)
        (bfloat16, ``_bn_scale``; the entry conv's over 128 x its
        fan-in, for 8-bit pixels), biases N(0, 0.1), PReLU slopes
        U[0.1, 0.3], dense weights N(0, 1 / n_in)."""
        stem = self.spec.conv_nodes[0]
        blocks = self.spec.residual_blocks
        head = next(n for n in self.spec.nodes if isinstance(n, PoolFC))
        ks = jax.random.split(key, len(blocks) + 2)
        k = jax.random.split(ks[0], 3)
        fan0 = stem.kh * stem.kw * stem.c_in
        params: Dict[str, Any] = {"conv": [{
            "w": jax.random.normal(k[0], (stem.kh, stem.kw, stem.c_in,
                                          stem.c_out), jnp.float32),
            "alpha": _bn_scale(k[1], stem.c_out, 128.0 * fan0 ** 0.5),
            "beta": _bias(k[2], stem.c_out)}], "res": [], "head": []}
        for nd, kb in zip(blocks, ks[1:]):
            c, f = nd.c_in, nd.c_out
            k = jax.random.split(kb, 14)
            p = {"w1": PackedArray.pack(jax.random.normal(
                     k[0], (3, 3, c, c), jnp.float32), axis=2),
                 "w2": PackedArray.pack(jax.random.normal(
                     k[1], (1, 1, c, f), jnp.float32), axis=2),
                 "rsign1": _bias(k[2], c), "rsign2": _bias(k[3], c)}
            for part, n, fan, kk in ((1, c, 9 * c, k[4:9]),
                                     (2, f, c, k[9:14])):
                scale, bias, m_in, slope, m_out = _EPILOGUE_PARAMS[part]
                p[scale] = _bn_scale(kk[0], n, fan ** 0.5)
                p[bias] = _bias(kk[1], n)
                p[m_in] = _bias(kk[2], n)
                p[slope] = jax.random.uniform(kk[3], (n,), jnp.float32,
                                              0.1, 0.3)
                p[m_out] = _bias(kk[4], n)
            params["res"].append(p)
        k = jax.random.split(ks[-1])
        params["head"].append({
            "w": jax.random.normal(k[0], (head.n_in, head.classes),
                                   jnp.float32) / head.n_in ** 0.5,
            "b": _bias(k[1], head.classes)})
        return self.bind(params)

    # -------------------------------------------------------------- #
    def apply(self, params: Dict[str, Any], x: Any,
              valid_rows: Optional[int] = None) -> Any:
        """Execute the plan.  ``x``: float NHWC for image specs, a
        PackedArray [..., K0] for dense-entry specs.  Bit-identical to
        the legacy builder chain on pallas/interpret/xla; inter-layer
        activations stay 1-bit (no int32 in HBM on kernel backends).

        ``valid_rows`` (static) is the ragged last-bucket mask for
        bucketed serving: only the first ``valid_rows`` rows are
        computed and returned (``kernels.ops.mask_rows`` — the M-axis
        twin of the pack epilogue's ``valid_n`` masking), so a
        bucket-padded batch stops paying GEMM work for its pad rows.
        Bit-identical to ``apply(params, x)[:valid_rows]``; under jit
        it must be a static argument (``serving_jit_kwargs``)."""
        h: Any = x if valid_rows is None else kops.mask_rows(x, valid_rows)
        for step in self.plan:
            # the scope names the step in the device trace's op metadata
            with jax.named_scope(f"{step.kind}.{step.name}"):
                h = self._apply_step(step, params, h)
        return h

    def _apply_step(self, step: PlanStep, params: Dict[str, Any],
                    h: Any) -> Any:
        """One plan step of ``apply``."""
        be = self.backend
        a = step.args
        if step.kind == "integer_conv":
            p = params["conv"][a["conv_idx"]]
            h = binary_weight_conv(h, p["w"], stride=a["stride"],
                                   padding=a["pad"],
                                   alpha=p["alpha"])
        elif step.kind == "float_pool":
            h = _maxpool_float(h, a["window"], a["stride"])
        elif step.kind == "binarize":
            if a["flatten"]:
                h = h.reshape(h.shape[0], -1)
            h = kops.binarize_pack(h, backend=be)
        elif step.kind == "binary_conv":
            p = params["conv"][a["conv_idx"]]
            h = binary_conv(h, p["wf"], fold=p["t"],
                            stride=a["stride"], padding=a["pad"],
                            pack_out=True, backend=be,
                            impl=a["impl"])
        elif step.kind == "packed_pool":
            h = maxpool_packed(h, a["window"], a["stride"])
        elif step.kind == "flatten":
            if h.length % 32:
                raise ValueError(
                    f"flattening needs C % 32 == 0 to keep the "
                    f"word layout contiguous, got C={h.length}")
            nb = h.words.shape[0]
            spatial = h.words.shape[1] * h.words.shape[2]
            h = PackedArray(h.words.reshape(nb, -1),
                            length=spatial * h.length, axis=-1)
            if h.length != a["n_in"]:
                raise ValueError(f"flattened width {h.length} != "
                                 f"{step.name} n_in={a['n_in']}")
        elif step.kind == "fused_stack":
            ws, ts = [], []
            for j in a["fc_indices"]:
                wp, t = _bind_dense(params["fc"][j])
                ws.append(wp)
                ts.append(t)
            # thread the compile-time budget so the kernel's own
            # residency re-check uses the same rule as the plan
            h = fused_binary_mlp(h, ws, ts, backend=be,
                                 vmem_budget=self.vmem_budget)
        elif step.kind == "dense":
            wp, t = _bind_dense(params["fc"][a["fc_idx"]])
            h = kops.binary_binary_dense(
                h, wp, threshold=t if a["thresholded"] else None,
                pack_out=a["pack_out"], backend=be)
        elif step.kind == "logits":
            h = h.astype(jnp.float32)
        elif step.kind == "residual_entry":
            x = h + params["conv"][a["conv_idx"]]["beta"]
            h = (x, kops.binarize_pack(x + params["res"][0]["rsign1"],
                                       backend=be))
        elif step.kind == "residual_conv":
            x, bits = h
            w = params["res"][a["res_idx"]][a["weight"]]
            h = (x, kops.binary_conv2d(bits, w, stride=a["stride"],
                                       padding=a["pad"], backend=be,
                                       impl=a["impl"]))
        elif step.kind == "residual_epilogue":
            h = self._residual_epilogue(a, params, *h)
        elif step.kind == "pool_fc":
            p = params["head"][0]
            h = jnp.dot(_global_mean(h[0]), p["w"],
                        precision=jax.lax.Precision.HIGHEST) + p["b"]
        else:                      # pragma: no cover
            raise AssertionError(f"unknown plan step {step.kind}")
        return h

    def _residual_epilogue(self, a: dict, params: Dict[str, Any],
                           x: jax.Array, s: jax.Array):
        """One ``residual_epilogue`` step: the block's per-channel rows,
        the next conv's sign bias (none after the last block), the
        zero-border table of a 3x3 conv, and the shortcut."""
        res = params["res"]
        p = res[a["res_idx"]]
        if not a["next"]:
            nxt = jnp.zeros((s.shape[-1],), jnp.float32)
        elif a["part"] == 1:
            nxt = p["rsign2"]
        else:
            nxt = res[a["res_idx"] + 1]["rsign1"]
        vec = jnp.stack([p[k] for k in _EPILOGUE_PARAMS[a["part"]]]
                        + [nxt]).astype(jnp.float32)
        table = rows = None
        if a["border"] is not None:
            hh, ww, k, stride, pad = a["border"]
            table = p["border"]
            rows = border_classes(hh, ww, k, stride, pad)[0]
        sc = avg_pool2(x) if a["pool"] else x
        return kops.residual_epilogue(s, sc, vec, table, rows,
                                      pack=a["next"], backend=self.backend)

    # -------------------------------------------------------------- #
    def traffic(self, batch: int = 1) -> Dict[str, Any]:
        """Static HBM byte model of one forward pass: activation and
        weight bytes moved by the packed datapath vs a bf16 NHWC
        baseline, per layer and total (absorbs the legacy
        packed_cnn_traffic math; integer layers move float activations
        on both paths, binary layers 1 bit/value packed vs 16 bf16)."""
        layers = []
        for nd in self.spec.conv_nodes:
            n_in = batch * nd.h_in * nd.w_in * nd.c_in
            n_w = nd.kh * nd.kw * nd.c_in * nd.c_out
            if isinstance(nd, IntegerEntry):
                a_p, a_b = 2 * n_in, 2 * n_in
                w_p, w_b = n_w // 8 or n_w, 2 * n_w
            else:
                a_p, a_b = n_in // 8, 2 * n_in
                w_p, w_b = n_w // 8, 2 * n_w
            layers.append({"name": nd.name, "packed_bytes": a_p + w_p,
                           "bf16_bytes": a_b + w_b})
        for nd in self.spec.dense_nodes:
            n_in, n_w = batch * nd.n_in, nd.n_in * nd.n_out
            layers.append({"name": nd.name,
                           "packed_bytes": n_in // 8 + n_w // 8,
                           "bf16_bytes": 2 * n_in + 2 * n_w})
        layers += self._residual_traffic(batch)
        packed = sum(d["packed_bytes"] for d in layers)
        bf16 = sum(d["bf16_bytes"] for d in layers)
        return {"layers": layers, "packed_bytes": packed,
                "bf16_bytes": bf16,
                "ratio_bf16_over_packed": bf16 / packed}

    def _residual_traffic(self, batch: int) -> List[dict]:
        """Per step of each ResidualBlock: a conv moves its packed input
        and weights and writes int32 sums; an epilogue reads the sums,
        the float32 shortcut, its [6, C] rows and (3x3) the int32
        border table, and writes the float32 stream and (unless last)
        the packed signs; a stride-2 shortcut is pooled first.  The
        entry reads the conv's output and writes the stream and its
        packed signs; the head reads the stream and its float32 dense.
        The bf16 baseline moves every value at 2 bytes."""
        layers: List[dict] = []
        blocks = self.spec.residual_blocks

        def add(name, packed, values):
            layers.append({"name": name, "packed_bytes": int(packed),
                           "bf16_bytes": int(2 * values)})

        if blocks:
            b0 = blocks[0]
            v = batch * b0.h_in * b0.w_in * b0.c_in
            add("stem.bn", 8 * v + 4 * b0.c_in + v // 8, 3 * v)
        for i, nd in enumerate(blocks):
            c, f = nd.c_in, nd.c_out
            m_in = batch * nd.h_in * nd.w_in
            m = batch * nd.h_out * nd.w_out
            for name, px, k, cin, cout in ((".conv3x3", m_in, 3, c, c),
                                           (".conv1x1", m, 1, c, f)):
                w = k * k * cin * cout
                add(nd.name + name, px * cin // 8 + w // 8 + 4 * m * cout,
                    px * cin + w + m * cout)
                if name == ".conv3x3":
                    if nd.stride == 2:
                        add(nd.name + ".shortcut", 4 * m_in * c + 4 * m * c,
                            m_in * c + m * c)
                    _, _, rsets, _ = border_classes(
                        nd.h_in, nd.w_in, 3, nd.stride, 1)
                    add(nd.name + ".epilogue1",
                        epilogue_bytes(m, nd.w_out, c, c, True,
                                       len(rsets)), 4 * m * c)
            last = i == len(blocks) - 1
            add(nd.name + ".epilogue2",
                epilogue_bytes(m, nd.w_out, f, c, not last, None),
                (3 * f + c) * m)
        for nd in self.spec.nodes:
            if isinstance(nd, PoolFC):
                v = batch * blocks[-1].h_out * blocks[-1].w_out * nd.n_in
                add(nd.name, 4 * v + 4 * nd.n_in * nd.classes
                    + 4 * nd.classes, v + nd.n_in * nd.classes)
        return layers

    # -------------------------------------------------------------- #
    def tulip_mapping(self, arch: ArchParams = TULIP) -> List[dict]:
        """Bridge the spec into the TULIP-PE schedule model: one row
        per mapped layer with the core/mapping.py LayerMapping (P, Z,
        refetch product) plus representative core/schedules.py
        fragment cycle counts (the bit-serial threshold compare for
        binary nodes, the OR-reduce for pools)."""
        wl = spec_to_workload(self.spec)
        rows: List[dict] = []
        conv_i = fc_i = 0
        for nd in self.spec.nodes:
            if isinstance(nd, (IntegerEntry, BinaryConv, ResidualBlock)):
                for _ in range(2 if isinstance(nd, ResidualBlock) else 1):
                    cl = wl.conv[conv_i]
                    m = map_conv(cl, arch)
                    conv_i += 1
                    rows.append({"node": cl.name, "kind": "conv",
                                 "mapping": m,
                                 "cmp_cycles": _cmp_cycles(m.node_inputs)
                                 if m.uses_pe else None})
            elif isinstance(nd, (BinaryDense, PoolFC)):
                m = map_fc(wl.fc[fc_i], arch)
                fc_i += 1
                rows.append({"node": nd.name, "kind": "dense",
                             "mapping": m,
                             "cmp_cycles": _cmp_cycles(m.node_inputs)
                             if m.uses_pe else None})
            elif isinstance(nd, MaxPool):
                frag = maxpool_fragment(
                    0, list(range(nd.window * nd.window)))
                rows.append({"node": nd.name, "kind": "pool",
                             "mapping": None,
                             "pool_cycles": frag.n_cycles()})
        return rows

    def table3_rows(self, arch_a: ArchParams = YODANN,
                    arch_b: ArchParams = TULIP) -> List[dict]:
        """The paper's Table III straight from the spec — identical to
        core.mapping.table3_rows on the source Workload."""
        return table3_rows(spec_to_workload(self.spec), arch_a, arch_b)


def _cmp_cycles(node_inputs: int) -> int:
    """Cycles of the bit-serial comparator that applies the folded-BN
    threshold to a ``node_inputs``-wide popcount sum (paper Fig 5(a)):
    one cycle per accumulator bit + the carry reset."""
    bits = min(16, node_inputs.bit_length() + 1)
    return compare_fragment(0, 1, list(range(bits)),
                            const=0).n_cycles()


# ------------------------------------------------------------------ #
# the front door                                                       #
# ------------------------------------------------------------------ #
def compile(spec: Union[BNNSpec, Workload],
            backend: Optional[str] = None,
            vmem_budget: Optional[int] = None, batch: int = 1,
            conv_impl: str = "auto") -> CompiledBNN:
    """Compile a BNNSpec (or a paper Workload, lowered first) into a
    CompiledBNN.

    backend: "pallas" | "interpret" | "xla" | None (host default) —
    baked into the compiled apply; vmem_budget: residency budget in
    bytes for the megakernel/conv decisions (None: the shared
    kernels.packed.VMEM_BUDGET_BYTES); batch: row hint the plan is
    computed for (decisions that depend on it are re-checked at trace
    time and are bit-identical either way); conv_impl: force
    "direct"/"im2col" instead of "auto" (int32-sum convs on the MXU,
    thresholded ones by the VMEM estimate).
    """
    t0 = time.perf_counter() if spans.on else 0.0
    if isinstance(spec, Workload):
        spec = from_workload(spec)
    spec.validate()
    plan = build_plan(spec, backend=backend, vmem_budget=vmem_budget,
                      batch=batch, conv_impl=conv_impl)
    if t0:
        spans.record("setup.compile", t0, time.perf_counter(),
                     steps=len(plan), mxu_convs=mxu_convs(plan))
    return CompiledBNN(spec, plan, backend, vmem_budget, batch)


def compile_dense_stack(k0: int, ns: Sequence[int],
                        thresholded: Optional[Sequence[bool]] = None,
                        name: str = "mlp",
                        backend: Optional[str] = None,
                        vmem_budget: Optional[int] = None,
                        batch: int = 1,
                        per_channel: Optional[Sequence[bool]] = None
                        ) -> CompiledBNN:
    """compile() for a fully-binary MLP stack spec."""
    return compile(from_dense_stack(k0, ns, thresholded, name=name,
                                    per_channel=per_channel),
                   backend=backend, vmem_budget=vmem_budget,
                   batch=batch)


def serve_folded_stack(xp: PackedArray,
                       layers: Sequence[Tuple[PackedArray, Any]],
                       backend: Optional[str] = None,
                       vmem_budget: Optional[int] = None) -> PackedArray:
    """Serve (wp [N, K] PackedArray, FoldedThreshold) layer pairs —
    quantize_for_serving's output — through the compiled pipeline: the
    folds are rewritten to per-channel thresholds at param-bind time
    and the stack runs under the plan's megakernel segmentation.
    The engine behind the deprecated core.bnn_layers.
    bnn_mlp_serve_folded shim."""
    if not isinstance(xp, PackedArray):
        raise ValueError("serve_folded_stack takes a PackedArray input")
    ws = [wp.move_pack_axis_last() for wp, _ in layers]
    rows = 1
    for d in xp.move_pack_axis_last().words.shape[:-1]:
        rows *= int(d)
    cb = compile_dense_stack(
        ws[0].length, [w.words.shape[0] for w in ws],
        backend=backend, vmem_budget=vmem_budget, batch=rows)
    params = {"fc": [{"wp": w, "t": fold}
                     for w, (_, fold) in zip(ws, layers)]}
    return cb.apply(params, xp)
