"""The compile passes: BNNSpec -> executable plan (DESIGN.md §8).

``build_plan`` runs the explicit lowering pipeline over a validated
spec and returns a tuple of :class:`PlanStep`:

  (2) threshold folding  — every BNThreshold is fused into its
      producer's threshold->pack epilogue (the folded-BN comparator of
      §IV-D; gamma<0 row negation happens at param-bind time through
      core.bnn_layers.fold_*_to_channel_thresholds);
  (3) dense-run segmentation — contiguous thresholded BinaryDense runs
      are greedily packed into fused_mlp megakernel launches under the
      VMEM budget (kernels.fused_mlp.stack_plan, THE shared
      residency rule), falling back to chained per-layer launches;
  (4) conv impl selection — int32 sums on the MXU, thresholded convs
      direct vs im2col via the VMEM-residency estimate
      (kernels.ops.plan_conv_launch, shared with dispatch);
  (5) autotune prefetch — every planned kernel launch resolves its
      tuning-table key up front (kernels.autotune memoizes), and the
      keys are recorded on the steps.

A ResidualBlock lowers to four steps: ``residual_conv`` (the 3x3
conv, int32 sums: an XLA int8 conv of the unpacked +-1 values on the
MXU under ``auto``, direct or im2col when forced),
``residual_epilogue`` (zero-border correction, BN, shortcut, RPReLU,
the next sign packed), ``residual_conv`` (the 1x1 conv, likewise on
the MXU; forced, an im2col popcount GEMM, whose patch matrix is the
packed map itself), and ``residual_epilogue`` again.
``residual_entry`` opens the stream after the entry conv; ``pool_fc``
closes it.

Every step carries a human-readable ``detail`` string: ``CompiledBNN.
describe()`` is the paper's mapping algorithm made inspectable.

The plan is computed for a ``batch`` row hint; launch *decisions* that
depend on the row count (fused-vs-chained residency) are re-checked by
the kernels at trace time with the actual rows, and both outcomes are
bit-identical — the plan can only ever differ from execution in
performance, never in bits.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Optional, Sequence, Tuple

from repro.graph.ir import (Binarize, BinaryConv, BinaryDense, BNNSpec,
                            BNThreshold, IntegerEntry, Logits, MaxPool,
                            PoolFC, ResidualBlock, ResidualEntry)
from repro.kernels.fused_mlp import stack_plan
from repro.kernels.ops import plan_conv_launch, plan_dense_launch
from repro.runtime import spans

__all__ = ["PlanStep", "batches_tuning_keys", "build_plan", "mxu_convs",
           "plan_tuning_keys"]


@dataclass(frozen=True)
class PlanStep:
    """One executable step + the lowering decision that produced it.

    kind: integer_conv | float_pool | binarize | binary_conv |
          packed_pool | flatten | fused_stack | dense | logits |
          residual_entry | residual_conv | residual_epilogue | pool_fc
    args: static operands for the executor (param indices, geometry,
          impl choices);  keys: autotune keys prefetched for the step.
    """
    kind: str
    name: str
    args: dict = field(default_factory=dict)
    detail: str = ""
    keys: Tuple[tuple, ...] = ()

    def __str__(self) -> str:
        return f"{self.kind:<13s} {self.name:<18s} {self.detail}"


def _fmt_mb(b: int) -> str:
    return f"{b / 1e6:.2f}MB"


def mxu_convs(plan: Sequence[PlanStep]) -> int:
    """Conv steps of a plan that run as XLA convs on the MXU."""
    return sum(s.kind == "residual_conv" and s.args["impl"] == "mxu"
               for s in plan)


def _keys(d: dict) -> Tuple[tuple, ...]:
    """The tuning key of a planned launch; none for an MXU conv."""
    return (d["key"],) if "key" in d else ()


def _residual_conv_launch(a: dict, batch: int, backend: Optional[str],
                          vmem_budget: Optional[int]) -> dict:
    """plan_conv_launch of a ``residual_conv`` step's recorded geometry
    (int32 sums out: no threshold, no pack)."""
    return plan_conv_launch(a["h"], a["w"], a["c"], a["f"], a["k"],
                            a["k"], stride=a["stride"], padding=a["pad"],
                            backend=backend, pack_out=False,
                            impl=a["impl"], vmem_budget=vmem_budget,
                            nb=batch)


def _lower_residual_block(nd: ResidualBlock, idx: int, last: bool,
                          batch: int, backend: Optional[str],
                          vmem_budget: Optional[int], conv_impl: str):
    """The four steps of one ResidualBlock (module docstring)."""
    c, f = nd.c_in, nd.c_out
    conv3 = {"res_idx": idx, "weight": "w1", "h": nd.h_in, "w": nd.w_in,
             "c": c, "f": c, "k": 3, "stride": nd.stride, "pad": 1,
             "impl": conv_impl}
    d3 = _residual_conv_launch(conv3, batch, backend, vmem_budget)
    conv3["impl"] = d3["impl"]
    conv1 = {"res_idx": idx, "weight": "w2", "h": nd.h_out, "w": nd.w_out,
             "c": c, "f": f, "k": 1, "stride": 1, "pad": 0,
             "impl": "auto" if conv_impl == "auto" else "im2col"}
    d1 = _residual_conv_launch(conv1, batch, backend, vmem_budget)
    conv1["impl"] = d1["impl"]
    if d3["impl"] == "mxu":
        how3 = "impl=mxu (int32 sums: XLA int8 conv), -1 border"
        how1 = ", impl=mxu (int32 sums: XLA int8 conv)"
    else:
        how3 = f"impl={d3['impl']} (forced); int32 sums, -1 border"
        how1 = " as an im2col popcount GEMM; int32 sums"
    shortcut = "avgpool2x2(x)" if nd.stride == 2 else "x"
    halves = f", {c} tiled twice" if f == 2 * c else ""
    return [
        PlanStep("residual_conv", f"{nd.name}.conv3x3", conv3,
                 f"packed conv {c}->{c} k3 s{nd.stride} p1, {how3}",
                 _keys(d3)),
        PlanStep("residual_epilogue", f"{nd.name}.epilogue1",
                 {"res_idx": idx, "part": 1, "pool": nd.stride == 2,
                  "border": (nd.h_in, nd.w_in, 3, nd.stride, 1),
                  "next": True},
                 f"zero-border correction, BN1, + {shortcut}, RPReLU1; "
                 f"float stream + sign(u + rsign2) packed"),
        PlanStep("residual_conv", f"{nd.name}.conv1x1", conv1,
                 f"packed conv {c}->{f} k1{how1}", _keys(d1)),
        PlanStep("residual_epilogue", f"{nd.name}.epilogue2",
                 {"res_idx": idx, "part": 2, "pool": False,
                  "border": None, "next": not last},
                 f"BN2, + u{halves}, RPReLU2; float stream"
                 + ("" if last else " + the next block's sign packed")),
    ]


def _segment_dense_run(run, k0: int, batch: int,
                       backend: Optional[str], budget: Optional[int]):
    """Pass 3: greedily grow megakernel segments over a contiguous run
    of thresholded dense layers; each segment must sit VMEM-resident
    (weights + ping-pong activation buffers + per-channel threshold
    vectors where the spec declares them) under the budget."""
    steps = []
    i = 0
    while i < len(run):
        ns, tvs, j = [], [], i
        sp = None
        while j < len(run):
            cand = ns + [run[j][1].n_out]
            cand_tv = tvs + [run[j][2].per_channel]
            trial = stack_plan(batch, k0, cand, cand_tv,
                               backend=backend, budget=budget)
            if not trial["fits"]:
                break
            ns, tvs, sp, j = cand, cand_tv, trial, j + 1
        if j == i:                     # single layer exceeds the budget
            fc_idx, nd, _ = run[i]
            d = plan_dense_launch(batch, nd.n_out, nd.n_in,
                                  backend=backend, pack_out=True)
            steps.append(PlanStep(
                "dense", nd.name,
                {"fc_idx": fc_idx, "thresholded": True, "pack_out": True},
                f"{nd.n_in}->{nd.n_out} chained launch (layer alone "
                f"exceeds the VMEM budget; threshold->pack fused)",
                (d["key"],)))
            k0 = nd.n_out
            i += 1
        elif j - i == 1:               # fusing one layer buys nothing
            fc_idx, nd, _ = run[i]
            d = plan_dense_launch(batch, nd.n_out, nd.n_in,
                                  backend=backend, pack_out=True)
            steps.append(PlanStep(
                "dense", nd.name,
                {"fc_idx": fc_idx, "thresholded": True, "pack_out": True},
                f"{nd.n_in}->{nd.n_out} single launch (segment of one; "
                f"threshold->pack fused)", (d["key"],)))
            k0 = nd.n_out
            i = j
        else:
            idxs = tuple(fc for fc, _, _ in run[i:j])
            names = " -> ".join(str(nd.n_out) for _, nd, _ in run[i:j])
            steps.append(PlanStep(
                "fused_stack", run[i][1].name,
                {"fc_indices": idxs},
                f"megakernel over {j - i} layers ({k0}->{names}), "
                f"activations VMEM-resident "
                f"({_fmt_mb(sp['vmem_bytes'])} of budget), "
                f"1 launch vs {j - i} chained", (sp["key"],)))
            k0 = run[j - 1][1].n_out
            i = j
    return steps


def _dense_thresholds(spec: BNNSpec):
    """fc-index-ordered (BinaryDense node, following BNThreshold or
    None) pairs — the same pairing build_plan walks."""
    out = []
    nodes = spec.nodes
    for i, nd in enumerate(nodes):
        if isinstance(nd, BinaryDense):
            thr = nodes[i + 1] if i + 1 < len(nodes) and \
                isinstance(nodes[i + 1], BNThreshold) else None
            out.append((nd, thr))
    return out


def plan_tuning_keys(spec: BNNSpec, plan: Tuple[PlanStep, ...],
                     batch: int, backend: Optional[str] = None,
                     vmem_budget: Optional[int] = None
                     ) -> Tuple[tuple, ...]:
    """The autotune keys an existing plan's launches resolve to at a
    *different* batch size — same plan structure (segment boundaries,
    conv impls), only the M/row terms rescaled through the same
    plan_* twins dispatch consults.  This is how the serving engine
    (repro.serving) warms the tuning table per batch bucket while
    reusing ONE compiled plan: recompiling per bucket would re-run
    segmentation, whose decisions may shift with m — the bits never
    change (stack_plan/ops re-check residency at trace time), but the
    plan the server reports would silently disagree with the one it
    serves."""
    dn = _dense_thresholds(spec)
    conv_nodes = spec.conv_nodes
    keys = []
    for s in plan:
        if s.kind == "residual_conv":
            keys.extend(_keys(_residual_conv_launch(
                s.args, batch, backend, vmem_budget)))
        elif s.kind == "binary_conv":
            nd = conv_nodes[s.args["conv_idx"]]
            d = plan_conv_launch(
                nd.h_in, nd.w_in, nd.c_in, nd.c_out, nd.kh, nd.kw,
                stride=s.args["stride"], padding=s.args["pad"],
                backend=backend, pack_out=True, impl=s.args["impl"],
                vmem_budget=vmem_budget, nb=batch)
            keys.append(d["key"])
        elif s.kind == "dense":
            nd, _ = dn[s.args["fc_idx"]]
            d = plan_dense_launch(batch, nd.n_out, nd.n_in,
                                  backend=backend,
                                  pack_out=s.args["pack_out"])
            keys.append(d["key"])
        elif s.kind == "fused_stack":
            nds = [dn[j] for j in s.args["fc_indices"]]
            sp = stack_plan(batch, nds[0][0].n_in,
                            [nd.n_out for nd, _ in nds],
                            [t.per_channel for _, t in nds],
                            backend=backend, budget=vmem_budget)
            keys.append(sp["key"])
    return tuple(keys)


def batches_tuning_keys(spec: BNNSpec, plan: Tuple[PlanStep, ...],
                        batches: Sequence[int],
                        backend: Optional[str] = None,
                        vmem_budget: Optional[int] = None
                        ) -> Tuple[tuple, ...]:
    """Deduplicated union of ``plan_tuning_keys`` over many batch
    sizes, in first-seen order.  The serving engine's ragged-mask
    dispatch launches at *valid-row* counts, not just pow2 buckets, so
    its prewarm set is the whole (bucket, valid) grid — and because the
    backend's ``pad_m`` collapses nearby row counts onto the same
    padded M, adjacent levels often resolve to identical keys, which is
    why the union is deduplicated here rather than warmed per level."""
    keys, seen = [], set()
    for b in batches:
        for k in plan_tuning_keys(spec, plan, b, backend=backend,
                                  vmem_budget=vmem_budget):
            if k not in seen:
                seen.add(k)
                keys.append(k)
    return tuple(keys)


def build_plan(spec: BNNSpec, backend: Optional[str] = None,
               vmem_budget: Optional[int] = None, batch: int = 1,
               conv_impl: str = "auto") -> Tuple[PlanStep, ...]:
    """Run passes 2-5 over a validated spec (see module docstring)."""
    if conv_impl not in ("auto", "direct", "im2col"):
        raise ValueError(f"conv_impl must be 'auto', 'direct', or "
                         f"'im2col', got {conv_impl!r}")
    steps = []
    conv_i = fc_i = 0
    domain = "float" if len(spec.input_shape) == 3 else "packed_flat"
    h, w = (spec.input_shape[:2] if domain == "float" else (0, 0))
    nodes = spec.nodes
    n_blocks = len(spec.residual_blocks)
    res_i = 0
    i = 0
    while i < len(nodes):
        nd = nodes[i]
        if isinstance(nd, ResidualEntry):
            steps.append(PlanStep(
                "residual_entry", nd.name,
                {"conv_idx": conv_i - 1},
                f"+ BN bias: opens the float32 residual stream "
                f"[{h}x{w}x{nd.channels}]; sign(x + block1.rsign1) "
                f"packed"))
        elif isinstance(nd, ResidualBlock):
            t0 = time.perf_counter() if spans.on else 0.0
            steps.extend(_lower_residual_block(
                nd, res_i, res_i == n_blocks - 1, batch, backend,
                vmem_budget, conv_impl))
            res_i += 1
            if t0:
                spans.record("setup.residual", t0, time.perf_counter(),
                             block=nd.name, impl3=steps[-4].args["impl"],
                             impl1=steps[-2].args["impl"])
            h, w = nd.h_out, nd.w_out
        elif isinstance(nd, PoolFC):
            steps.append(PlanStep(
                "pool_fc", nd.name, {},
                f"global average pool {h}x{w} + float32 dense "
                f"{nd.n_in}->{nd.classes} at highest precision (XLA) "
                f"-> logits"))
        elif isinstance(nd, IntegerEntry):
            steps.append(PlanStep(
                "integer_conv", nd.name,
                {"conv_idx": conv_i, "stride": nd.stride, "pad": nd.pad},
                f"float NHWC conv {nd.c_in}->{nd.c_out} k{nd.kh} "
                f"s{nd.stride} p{nd.pad}, alpha*sign(w) on the MXU "
                f"(XLA, real zero padding)"))
            conv_i += 1
            h, w = nd.h_out, nd.w_out
        elif isinstance(nd, Binarize):
            steps.append(PlanStep(
                "binarize", nd.name, {"flatten": nd.flatten},
                "flatten + sign+pack to 1 bit/value" if nd.flatten else
                "sign+pack NHWC channels to 1 bit/value"))
            domain = "packed_flat" if nd.flatten else "packed_conv"
        elif isinstance(nd, BinaryConv):
            d = plan_conv_launch(
                h, w, nd.c_in, nd.c_out, nd.kh, nd.kw, stride=nd.stride,
                padding=nd.pad, backend=backend, pack_out=True,
                impl=conv_impl, vmem_budget=vmem_budget, nb=batch)
            thr = nodes[i + 1]         # BNThreshold, by validation
            why = "forced" if conv_impl != "auto" else (
                f"resident {_fmt_mb(d['vmem_bytes'])} "
                + ("> budget" if d["impl"] == "im2col" else "fits"))
            steps.append(PlanStep(
                "binary_conv", nd.name,
                {"conv_idx": conv_i, "stride": nd.stride, "pad": nd.pad,
                 "impl": d["impl"]},
                f"packed conv {nd.c_in}->{nd.c_out} k{nd.kh} "
                f"s{nd.stride} p{nd.pad}, impl={d['impl']} ({why}); "
                f"{thr.name} folded into the threshold->pack epilogue",
                _keys(d)))
            conv_i += 1
            h, w = nd.h_out, nd.w_out
            i += 1                     # consume the fused BNThreshold
        elif isinstance(nd, MaxPool):
            if domain == "packed_conv":
                steps.append(PlanStep(
                    "packed_pool", nd.name,
                    {"window": nd.window, "stride": nd.stride},
                    f"max {nd.window}x{nd.window}/s{nd.stride} as "
                    f"bitwise OR on packed words (sign is monotonic)"))
            else:
                steps.append(PlanStep(
                    "float_pool", nd.name,
                    {"window": nd.window, "stride": nd.stride},
                    f"float max-pool {nd.window}x{nd.window}"
                    f"/s{nd.stride} (reduce_window)"))
            h = (h - nd.window) // nd.stride + 1
            w = (w - nd.window) // nd.stride + 1
        elif isinstance(nd, BinaryDense):
            if domain == "packed_conv":
                steps.append(PlanStep(
                    "flatten", f"flatten@{nd.name}", {"n_in": nd.n_in},
                    f"word-level reshape [N,H,W,C/32] -> [N, "
                    f"{nd.n_in}/32] (no unpacking; C%32==0 required)"))
                domain = "packed_flat"
            # gather the maximal contiguous thresholded dense run
            run, k0 = [], nd.n_in
            while i < len(nodes) and isinstance(nodes[i], BinaryDense) \
                    and i + 1 < len(nodes) \
                    and isinstance(nodes[i + 1], BNThreshold):
                run.append((fc_i, nodes[i], nodes[i + 1]))
                fc_i += 1
                i += 2                 # skip the fused BNThreshold
            if run:
                steps.extend(_segment_dense_run(
                    run, k0, batch, backend, vmem_budget))
            if i < len(nodes) and isinstance(nodes[i], BinaryDense):
                tail = nodes[i]        # un-thresholded (Logits) tail
                d = plan_dense_launch(batch, tail.n_out, tail.n_in,
                                      backend=backend, pack_out=False)
                steps.append(PlanStep(
                    "dense", tail.name,
                    {"fc_idx": fc_i, "thresholded": False,
                     "pack_out": False},
                    f"{tail.n_in}->{tail.n_out} int32 dot (no "
                    f"threshold: classifier head)", (d["key"],)))
                fc_i += 1
                i += 1
            continue                   # i already advanced past the run
        elif isinstance(nd, BNThreshold):
            raise AssertionError(f"{nd.name}: BNThreshold not consumed "
                                 f"by its producer (validate() should "
                                 f"have caught this)")
        elif isinstance(nd, Logits):
            steps.append(PlanStep(
                "logits", nd.name, {},
                f"int32 dot -> float32 logits [{nd.classes}]"))
        i += 1
    return tuple(steps)
