"""Kernel microbenchmark: structural roofline terms for the binarized
GEMM kernels plus measured wall-times on this host, emitted as
BENCH_kernels.json so future PRs have a perf trajectory to compare
against.

No TPU wall-clock on a CPU host — interpret mode checks correctness;
the byte model is the data-movement term that drives BlockSpec choices.
For a [M,K]x[K,N] binary-weight matmul at bf16 activations:
  dense bf16 weights:  bytes = 2(MK + KN + MN)
  packed weights:      bytes = 2*MK + KN/8 + 2*MN      (16x less W traffic)
  fully binary packed: bytes = MK/8 + KN/8 + 4*MN      (popcount path)
"""
import os
import sys

# --serve measures device-count scaling on a single host: the virtual
# CPU-device flag must land before jax initializes its backend, hence
# before any other import pulls jax in (per-file E402 ignore in
# pyproject covers the imports below).
if "--serve" in sys.argv:
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=4").strip()

import argparse
import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.ops import (binarize_pack, binary_binary_dense,
                              binary_dense)
from repro.kernels.packed import PackedArray
from repro.launch.cache import use_compile_cache

# Published per-chip peaks keyed by jax's device_kind (Google Cloud
# documentation, "TPU v5e": 819 GB/s HBM, 197 TFLOP/s bf16).  A device
# missing from the table is an error, never a default.
PEAK_RATES = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
}
# the chip the host-side roofline model describes when no TPU is attached
TARGET_KIND = "TPU v5 lite"


def peak_rates(kind):
    try:
        return PEAK_RATES[kind]
    except KeyError:
        raise ValueError(f"no published peak rates for device kind "
                         f"{kind!r}: add them to PEAK_RATES with their "
                         f"source") from None

_HERE = os.path.dirname(os.path.abspath(__file__))
DEFAULT_OUT = os.path.join(_HERE, "BENCH_kernels.json")
FUSED_OUT = os.path.join(_HERE, "BENCH_fused.json")
CONV_OUT = os.path.join(_HERE, "BENCH_conv.json")
COMPILE_OUT = os.path.join(_HERE, "BENCH_compile.json")
SERVE_OUT = os.path.join(_HERE, "BENCH_serve.json")
FAULTS_OUT = os.path.join(_HERE, "BENCH_faults.json")
TRAIN_OUT = os.path.join(_HERE, "BENCH_train.json")
DSE_OUT = os.path.join(_HERE, "BENCH_dse.json")


def model_bytes(m, k, n):
    return {
        "bf16": 2 * (m * k + k * n + m * n),
        "packed_w": 2 * m * k + k * n // 8 + 2 * m * n,
        "packed_both": m * k // 8 + k * n // 8 + 4 * m * n,
    }


def _block(out):
    jax.tree.map(
        lambda a: a.block_until_ready() if hasattr(
            a, "block_until_ready") else a, out)


def _wall(fn, *args, iters=5, warmup=1, **kw):
    """Median wall-time of fn(*args): ``warmup`` untimed calls first
    (jit tracing + cache fill never pollutes a sample), then ``iters``
    timed repeats, each fenced with block_until_ready so async
    dispatch cannot hide device time; the median deflects scheduler
    outliers a mean would absorb."""
    for _ in range(warmup):
        _block(fn(*args, **kw))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        _block(fn(*args, **kw))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def _env():
    """Provenance block stamped into every BENCH_*.json: a number is
    only comparable against the runtime that produced it."""
    dev = jax.devices()[0]
    return {
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "device_kind": dev.device_kind,
        "device_count": jax.device_count(),
    }


def run(log=print, out_json=DEFAULT_OUT):
    dev = jax.devices()[0]
    kind = dev.device_kind if dev.platform == "tpu" else TARGET_KIND
    hbm_bw = peak_rates(kind)["hbm_bytes_per_s"]
    log(f"\n== Kernel roofline model (decode-shape binary GEMMs, "
        f"{kind} peaks) ==")
    shapes = [(128, 4096, 4096), (128, 12288, 12288), (1, 8192, 8192)]
    log(f"{'M,K,N':>18s} | {'bf16 MB':>9s} {'packedW':>9s} {'both':>9s} | "
        f"{'t_mem bf16':>10s} {'packedW':>9s} {'AI bf16':>8s} {'packedW':>8s}")
    rows = []
    for m, k, n in shapes:
        b = model_bytes(m, k, n)
        flops = 2 * m * k * n
        t_b = b["bf16"] / hbm_bw
        t_p = b["packed_w"] / hbm_bw
        rows.append({
            "m": m, "k": k, "n": n, "bytes": b,
            "flops": flops,
            "t_mem_bf16_s": t_b, "t_mem_packed_w_s": t_p,
            "hbm_ratio_bf16_over_packed_w": b["bf16"] / b["packed_w"],
            "hbm_ratio_bf16_over_packed_both": b["bf16"] / b["packed_both"],
            "arith_intensity_bf16": flops / b["bf16"],
            "arith_intensity_packed_w": flops / b["packed_w"],
        })
        log(f"{f'{m},{k},{n}':>18s} | {b['bf16'] / 1e6:9.2f} "
            f"{b['packed_w'] / 1e6:9.2f} {b['packed_both'] / 1e6:9.2f} | "
            f"{t_b * 1e6:8.1f}us {t_p * 1e6:7.1f}us "
            f"{flops / b['bf16']:8.1f} {flops / b['packed_w']:8.1f}")

    # correctness spot-check + measured wall-time through the public
    # wrappers (xla oracle path; interpret mode for bit-exactness)
    rng = np.random.default_rng(0)
    m, k, n = 128, 512, 256
    x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
    w = rng.choice([-1.0, 1.0], size=(k, n)).astype(np.float32)
    wp = PackedArray.pack(jnp.asarray(w), axis=0)
    alpha = jnp.ones((n,), jnp.float32)
    t0 = time.time()
    y1 = binary_dense(x, wp, alpha, backend="interpret")
    y2 = binary_dense(x, wp, alpha, backend="xla")
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-4,
                               atol=1e-3)
    spot_s = time.time() - t0
    log(f"kernel-vs-oracle spot check OK ({spot_s:.2f}s, interpret mode)")

    ws = rng.choice([-1.0, 1.0], size=(n, k)).astype(np.float32)
    wrow = PackedArray.pack(jnp.asarray(ws), axis=-1)
    xp = binarize_pack(x, backend="xla")
    measured = {
        "host_backend": jax.default_backend(),
        "shape": {"m": m, "k": k, "n": n},
        "binarize_pack_xla_s": _wall(binarize_pack, x, backend="xla"),
        "binary_dense_xla_s": _wall(binary_dense, x, wp, alpha,
                                    backend="xla"),
        "binary_binary_dense_xla_s": _wall(binary_binary_dense, xp, wrow,
                                           backend="xla"),
    }
    log("measured (this host, xla oracle path): " +
        ", ".join(f"{k_}={v * 1e3:.2f}ms" for k_, v in measured.items()
                  if k_.endswith("_s")))

    out = {"env": _env(), "peak_rates_of": kind,
           "hbm_bw_model": hbm_bw,
           "peak_flops_model": peak_rates(kind)["bf16_flops_per_s"],
           "roofline": rows,
           "spot_check_s": spot_s, "measured": measured}
    if out_json:
        with open(out_json, "w") as f:
            json.dump(out, f, indent=1)
        log(f"wrote {out_json}")
    return out


def run_fused(log=print, out_json=FUSED_OUT, smoke=False):
    """Fused threshold->pack epilogue vs the unfused two-kernel chain.

    Three claims, per shape (ISSUE 2 acceptance):
      * output bytes: the fused path writes uint32 [M, N/32] where the
        unfused path writes int32 [M, N], re-reads it, and writes the
        packed words — >= 8x (structurally 32x write + re-read) less
        inter-layer HBM traffic;
      * the Harley-Seal CSA inner loop beats the [M, N, K/32] XNOR-cube
        baseline in measured wall time (jnp twins of the two kernel
        inner-loop structures — on TPU the same harness times the
        Pallas kernels themselves);
      * fused and unfused results are BIT-IDENTICAL on every backend
        available on this host (raises on divergence — the CI smoke
        gate runs exactly this in interpret mode).
    """
    # deep-K shapes: the CSA win is a K-reduction restructuring, so the
    # benchmark sweeps the regime where the XNOR cube blows the cache
    # (K/32 >= 64 words — the hidden-layer widths BNN MLPs actually use)
    shapes = [(64, 256, 128)] if smoke else \
        [(256, 2048, 512), (128, 4096, 1024), (256, 8192, 512)]
    backends = ["xla", "interpret"]
    if jax.default_backend() == "tpu":
        backends.append("pallas")
    log(f"\n== Fused threshold->pack epilogue "
        f"(backends checked: {backends}) ==")
    rows = []
    for m, k, n in shapes:
        rng = np.random.default_rng(m + n)
        xs = rng.choice([-1.0, 1.0], size=(m, k)).astype(np.float32)
        ws = rng.choice([-1.0, 1.0], size=(n, k)).astype(np.float32)
        xp = PackedArray.pack(jnp.asarray(xs))
        wp = PackedArray.pack(jnp.asarray(ws))

        # -- bit-identity: fused vs unfused chain, across backends ---- #
        words = {}
        for be in backends:
            fused = binary_binary_dense(xp, wp, threshold=0,
                                        pack_out=True, backend=be)
            y = binary_binary_dense(xp, wp, threshold=0, backend=be)
            unfused = binarize_pack(y.astype(jnp.float32), backend=be)
            np.testing.assert_array_equal(
                np.asarray(fused.words), np.asarray(unfused.words),
                err_msg=f"fused != unfused on backend {be}")
            words[be] = np.asarray(fused.words)
        for be in backends[1:]:
            np.testing.assert_array_equal(
                words[be], words[backends[0]],
                err_msg=f"backend {be} diverges from {backends[0]}")

        # -- byte model: inter-layer activation traffic --------------- #
        out_unfused = 4 * m * n * 2 + m * n // 8   # write+reread int32,
        out_fused = m * n // 8                     # then packed words
        ratio = out_unfused / out_fused

        # -- CSA vs XNOR-cube inner loop, measured -------------------- #
        cube = jax.jit(functools.partial(ref.popcount_gemm_ref, k=k))
        csa = jax.jit(functools.partial(ref.popcount_gemm_csa_ref, k=k))
        np.testing.assert_array_equal(
            np.asarray(cube(xp.words, wp.words)),
            np.asarray(csa(xp.words, wp.words)))
        t_cube = _wall(cube, xp.words, wp.words)
        t_csa = _wall(csa, xp.words, wp.words)

        rows.append({
            "m": m, "k": k, "n": n,
            "out_bytes_unfused": out_unfused,
            "out_bytes_fused": out_fused,
            "out_bytes_ratio": ratio,
            "t_cube_s": t_cube, "t_csa_s": t_csa,
            "csa_speedup": t_cube / t_csa,
            "bit_identical_backends": backends,
        })
        log(f"{f'{m},{k},{n}':>16s} | out bytes {out_unfused:>9d} -> "
            f"{out_fused:>7d} ({ratio:.0f}x) | cube {t_cube * 1e3:7.2f}ms "
            f"csa {t_csa * 1e3:7.2f}ms ({t_cube / t_csa:.2f}x) | "
            f"bit-identical OK")

    out = {"env": _env(), "host_backend": jax.default_backend(),
           "backends_checked": backends,
           "smoke": smoke,
           "fused": rows}
    if out_json:
        with open(out_json, "w") as f:
            json.dump(out, f, indent=1)
        log(f"wrote {out_json}")
    return out


def run_conv(log=print, out_json=CONV_OUT, smoke=False):
    """Packed binary conv2d: byte model + bit-identity + schedule race.

    Three claims, per BinaryNet-shaped layer (ISSUE 3 acceptance):
      * bytes: channel-packed NHWC activations + packed filters move
        ~16x fewer HBM bytes than the bf16 NHWC equivalent, and the
        direct (im2col-free) schedule skips the patch-matrix write +
        re-read that the im2col fallback pays (fused_vs_im2col ratio);
      * direct kernel, word-level im2col fallback, and the jnp
        sign-conv oracle are BIT-IDENTICAL on every backend available
        on this host, fused pack_out epilogue included (raises on
        divergence — the CI bench-smoke gate runs exactly this);
      * wall time: the im2col-free schedule vs the patch-materializing
        schedule, jnp twins jitted on this host (on TPU the same
        harness times the Pallas kernels themselves).
    Also emits the whole-workload byte model from packed_cnn_traffic.
    """
    from repro.core.workloads import alexnet_imagenet, binarynet_cifar10
    from repro.kernels.ops import binary_conv2d
    from repro.kernels.packed_conv import im2col_words, pad_words_spatial
    from repro.models.layers import packed_cnn_traffic

    # (name, nb, h, w, c, f, k): BinaryNet CIFAR-10 body layers
    shapes = [("smoke", 2, 6, 6, 64, 64, 3)] if smoke else \
        [("binarynet_conv3", 2, 16, 16, 128, 256, 3),
         ("binarynet_conv5", 2, 8, 8, 256, 512, 3)]
    backends = ["xla", "interpret"]
    if jax.default_backend() == "tpu":
        backends.append("pallas")
    log(f"\n== Packed binary conv2d (backends checked: {backends}) ==")
    rows = []
    for name, nb, h, w, c, f, k in shapes:
        rng = np.random.default_rng(h * c + f)
        x = rng.choice([-1.0, 1.0], size=(nb, h, w, c)).astype(np.float32)
        wts = rng.choice([-1.0, 1.0], size=(k, k, c, f)).astype(np.float32)
        xp = PackedArray.pack(jnp.asarray(x), axis=-1)
        wf = PackedArray.pack(jnp.asarray(wts), axis=2)

        # -- bit-identity: direct / im2col / oracle, fused epilogue --- #
        words = {}
        for be in backends:
            impls = ["direct", "im2col"] if be != "xla" else ["direct"]
            for impl in impls:
                got = binary_conv2d(xp, wf, threshold=0, pack_out=True,
                                    backend=be, impl=impl)
                words[(be, impl)] = np.asarray(got.words)
        base = words[("xla", "direct")]
        for key, got in words.items():
            np.testing.assert_array_equal(
                got, base, err_msg=f"{key} diverges from the xla oracle")

        # -- byte model ----------------------------------------------- #
        c32 = (c + 31) // 32
        m = nb * h * w                       # stride 1, same pad
        k32 = k * k * c32
        act_p, act_b = nb * h * w * c // 8, 2 * nb * h * w * c
        w_p, w_b = k * k * c * f // 8, 2 * k * k * c * f
        out_p, out_b = m * f // 8, 2 * m * f
        packed_bytes = act_p + w_p + out_p
        bf16_bytes = act_b + w_b + out_b
        im2col_extra = 2 * 4 * m * k32       # patch write + re-read
        fused_vs_im2col = (packed_bytes + im2col_extra) / packed_bytes

        # -- schedule race ------------------------------------------- #
        # on TPU this times the direct Pallas kernel itself; elsewhere
        # the xla oracle is the only meaningfully-timeable direct form
        # (interpret mode measures the python interpreter, not the
        # schedule)
        kb = "pallas" if jax.default_backend() == "tpu" else "xla"
        direct = jax.jit(lambda a, b: binary_conv2d(
            a, b, threshold=0, pack_out=True, backend=kb,
            impl="direct").words)
        xw = pad_words_spatial(xp.words, (k - 1) // 2, (k - 1) // 2)

        def im2col_path(xw_, ww_):
            patches = im2col_words(xw_, k, k, 1, h, w)
            pc = ref.popcount_gemm_ref(patches, ww_, k * k * c)
            dec = jnp.where(pc >= 0, 1.0, -1.0)
            return PackedArray.pack(dec, axis=-1).words

        ww = wf.words.reshape(k32, f).T
        im2col = jax.jit(im2col_path)
        np.testing.assert_array_equal(
            np.asarray(im2col(xw, ww)).reshape(base.shape), base)
        t_direct = _wall(direct, xp, wf)
        t_im2col = _wall(im2col, xw, ww)

        rows.append({
            "name": name, "nb": nb, "h": h, "w": w, "c": c, "f": f, "k": k,
            "packed_bytes": packed_bytes, "bf16_bytes": bf16_bytes,
            "packed_vs_bf16_bytes_ratio": bf16_bytes / packed_bytes,
            "im2col_extra_bytes": im2col_extra,
            "fused_vs_im2col_bytes_ratio": fused_vs_im2col,
            "t_direct_s": t_direct, "t_im2col_s": t_im2col,
            "timed_backend": kb,
            "direct_speedup": t_im2col / t_direct,
            "bit_identical": sorted(f"{b}:{i}" for b, i in words),
        })
        log(f"{name:>16s} | bytes bf16 {bf16_bytes / 1e6:7.2f}MB -> packed "
            f"{packed_bytes / 1e6:6.2f}MB ({bf16_bytes / packed_bytes:.1f}x)"
            f" | im2col pays {fused_vs_im2col:.2f}x bytes | direct "
            f"{t_direct * 1e3:7.2f}ms im2col {t_im2col * 1e3:7.2f}ms "
            f"({t_im2col / t_direct:.2f}x) | bit-identical OK")

    workloads = {
        wl.name: packed_cnn_traffic(wl, batch=1)
        for wl in (binarynet_cifar10(), alexnet_imagenet())}
    for nm, tr in workloads.items():
        log(f"{nm}: whole-net forward {tr['bf16_bytes'] / 1e6:.1f}MB bf16 "
            f"-> {tr['packed_bytes'] / 1e6:.1f}MB packed "
            f"({tr['ratio_bf16_over_packed']:.1f}x)")

    out = {"env": _env(), "host_backend": jax.default_backend(),
           "backends_checked": backends, "smoke": smoke,
           "conv": rows, "workload_traffic": workloads}
    if out_json:
        with open(out_json, "w") as f_:
            json.dump(out, f_, indent=1)
        log(f"wrote {out_json}")
    return out


def run_compile(log=print, out_json=COMPILE_OUT, smoke=False):
    """The graph compiler front door (ISSUE 4 acceptance).

    Per paper workload: the compiled plan's lowering decisions, the
    launch count vs the legacy layer-by-layer chain, the HBM byte
    model, and the Table III reproduction from the same spec.  Gate:
    on a small spec, the compiled executable must be BIT-IDENTICAL
    across every backend available on this host AND between the fused
    plan and a fully-chained plan (vmem_budget=0 disables megakernel
    segmentation) — raises on divergence (the CI smoke job runs
    exactly this)."""
    from repro import graph
    from repro.core.mapping import table3_rows
    from repro.core.workloads import alexnet_imagenet, binarynet_cifar10

    backends = ["xla", "interpret"]
    if jax.default_backend() == "tpu":
        backends.append("pallas")
    log(f"\n== compile(spec) pipeline (backends checked: {backends}) ==")

    # -- bit-identity gate on a small spec ---------------------------- #
    spec = graph.BNNSpec("bench_small", (8, 8, 32), (
        graph.Binarize("b"),
        graph.BinaryConv("c1", 3, 3, 32, 64, 8, 8, 8, 8, 1, 1),
        graph.BNThreshold("c1.bn", 64),
        graph.MaxPool("p1", 2, 2),
        graph.BinaryDense("d1", 4 * 4 * 64, 64),
        graph.BNThreshold("d1.bn", 64),
        graph.BinaryDense("d2", 64, 64),
        graph.BNThreshold("d2.bn", 64),
        graph.BinaryDense("d3", 64, 16),
        graph.Logits("logits", 16)))
    params = graph.compile(spec).init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 8, 32),
                          jnp.float32)
    outs = {}
    for be in backends:
        fused = graph.compile(spec, backend=be, batch=2)
        chained = graph.compile(spec, backend=be, batch=2,
                                vmem_budget=0)
        assert any(s.kind == "fused_stack" for s in fused.plan)
        assert not any(s.kind == "fused_stack" for s in chained.plan)
        a = np.asarray(fused.apply(params, x))
        b = np.asarray(chained.apply(params, x))
        np.testing.assert_array_equal(
            a, b, err_msg=f"fused plan != chained plan on {be}")
        outs[be] = a
    for be in backends[1:]:
        np.testing.assert_array_equal(
            outs[be], outs[backends[0]],
            err_msg=f"compiled path diverges on {be}")
    log(f"bit-identity gate OK (fused vs chained plan, {backends})")

    # -- per-workload plan decisions + byte model --------------------- #
    rows = []
    for wl in (binarynet_cifar10(), alexnet_imagenet()):
        cb = graph.compile(wl)
        tr = cb.traffic(batch=1)
        t3_ok = cb.table3_rows() == table3_rows(wl)
        assert t3_ok, f"{wl.name}: tulip_mapping diverges from Table III"
        row = {
            "name": wl.name,
            "launches_compiled": cb.launch_count(),
            "launches_legacy": cb.legacy_launch_count(),
            "plan": [str(s) for s in cb.plan],
            "conv_impls": [s.args["impl"] for s in cb.plan
                           if s.kind == "binary_conv"],
            "hbm_packed_bytes": tr["packed_bytes"],
            "hbm_bf16_bytes": tr["bf16_bytes"],
            "hbm_ratio": tr["ratio_bf16_over_packed"],
            "table3_matches_mapping": t3_ok,
            "tuning_keys_prefetched": len(cb.tuning_keys),
        }
        if wl.name == "BinaryNet" and not smoke:
            p = cb.init(jax.random.PRNGKey(2))
            img = jax.random.normal(jax.random.PRNGKey(3),
                                    (1, 32, 32, 3), jnp.float32)
            cbx = graph.compile(wl, backend="xla")
            row["forward_xla_s"] = _wall(cbx.apply, p, img)
        rows.append(row)
        log(f"{wl.name:>10s} | {row['launches_compiled']} launches "
            f"(legacy {row['launches_legacy']}) | HBM "
            f"{tr['packed_bytes'] / 1e6:.1f}MB packed vs "
            f"{tr['bf16_bytes'] / 1e6:.1f}MB bf16 "
            f"({tr['ratio_bf16_over_packed']:.1f}x) | Table III OK | "
            f"{row['tuning_keys_prefetched']} autotune keys")

    out = {"env": _env(), "host_backend": jax.default_backend(),
           "backends_checked": backends, "smoke": smoke,
           "workloads": rows}
    if out_json:
        with open(out_json, "w") as f:
            json.dump(out, f, indent=1)
        log(f"wrote {out_json}")
    return out


def run_serve(log=print, out_json=SERVE_OUT, smoke=False):
    """The serving engine over compile() (ISSUE 5 + ISSUE 6 acceptance).

    Claims, in order:
      * bit-identity gates: (a) BNNServer output on a multi-virtual-
        device data mesh equals plain single-device CompiledBNN.apply
        EXACTLY — float logits for BinaryNet, packed words for a dense
        stack; (b) the ragged-masked forward apply(..., valid_rows=r)
        equals the unmasked forward's first r rows bit-for-bit; raises
        on divergence (the CI bench-smoke step runs exactly this under
        XLA_FLAGS=--xla_force_host_platform_device_count=4);
      * throughput vs batch size through the bucketed dispatch path,
        with the jit-trace count pinned to the ragged dispatch grid;
      * device-count scaling: the same fixed compute-dominated batch
        on a 1-device vs whole-host mesh, through the production
        apply_batch path — the full (tracked) run GATES on
        speedup > 1;
      * continuous-batching stream: a request stream through the
        started worker (admission window + dispatch-ahead) vs the same
        requests applied synchronously back-to-back;
      * ragged-padding overhead: each ragged row count vs a jit traced
        at EXACTLY that shape — the honest denominator — with the
        full-bucket wall recorded as the cost masking avoids; the full
        run GATES on overhead_vs_exact < 1.5 at every point.
    """
    from repro import graph
    from repro.core.workloads import binarynet_cifar10
    from repro.kernels.ops import binarize_pack
    from repro.serving import (BNNServer, bucket_for, data_mesh,
                               ragged_valid, shard_batch)

    n_dev = len(jax.devices())
    mesh = data_mesh() if n_dev > 1 else None
    log(f"\n== BNNServer over compile() ({n_dev} devices, mesh "
        f"{'data=' + str(n_dev) if mesh is not None else 'none'}) ==")
    rng = np.random.default_rng(0)

    # smoke keeps CI fast; the full run uses a compute-dominated model
    # (per-dispatch work >> partition/dispatch overhead) because that
    # is the regime where serving a mesh is supposed to win
    d0, hidden, max_batch = ((128, [128, 64], 8) if smoke
                             else (2048, [2048, 2048, 1024], 128))
    spec = graph.from_dense_stack(d0, hidden, name="serve_mlp")
    cb = graph.compile(spec, backend="xla", batch=max_batch)
    params = cb.init(jax.random.PRNGKey(0))

    def packed(rows):
        return binarize_pack(jnp.asarray(
            rng.normal(size=(rows, d0)).astype(np.float32)),
            backend="xla")

    # -- bit-identity gate: sharded vs single-device ------------------ #
    xp = packed(11)
    ref = cb.apply(params, xp)
    srv = BNNServer(cb, params, max_batch=max_batch, mesh=mesh)
    got = srv.apply_batch(xp)
    np.testing.assert_array_equal(
        np.asarray(got.words), np.asarray(ref.words),
        err_msg="sharded server diverges from single-device apply")

    wl = binarynet_cifar10()
    cbn = graph.compile(wl, backend="xla", batch=4)
    bp = cbn.init(jax.random.PRNGKey(1))
    img = jax.random.normal(jax.random.PRNGKey(2), (3, 32, 32, 3),
                            jnp.float32)
    ref_logits = cbn.apply(bp, img)
    bsrv = BNNServer(cbn, bp, max_batch=4, mesh=mesh)
    got_logits = bsrv.apply_batch(img)
    np.testing.assert_array_equal(
        np.asarray(got_logits), np.asarray(ref_logits),
        err_msg="sharded BinaryNet logits diverge from single-device")

    # -- bit-identity gate: masked vs unmasked forward ---------------- #
    xm = packed(max_batch)
    full_words = np.asarray(cb.apply(params, xm).words)
    for r in (3, max_batch // 2 + 1, max_batch):
        masked = cb.apply(params, xm, valid_rows=r)
        np.testing.assert_array_equal(
            np.asarray(masked.words), full_words[:r],
            err_msg=f"masked forward (valid_rows={r}) diverges from "
                    f"the unmasked forward's first {r} rows")
    log(f"bit-identity gates OK (sharded words + logits on {n_dev} "
        f"virtual devices; masked == unmasked on valid rows)")

    # -- throughput vs batch size ------------------------------------- #
    batches = [1, 4, 8] if smoke else [1, 8, 32, max_batch]
    tsrv = BNNServer(cb, params, max_batch=max_batch, mesh=mesh)
    thr_rows = []
    for b in batches:
        xb = packed(b)
        t = _wall(tsrv.apply_batch, xb)
        thr_rows.append({"batch": b, "wall_s": t, "rows_per_s": b / t})
        log(f"batch {b:>3d}: {t * 1e3:7.2f}ms  {b / t:9.1f} rows/s")
    assert tsrv.jit_traces() <= tsrv.trace_bound(), \
        "bucketed dispatch exceeded its ragged trace bound"

    # -- device-count scaling on the same fixed batch ----------------- #
    bfix = batches[-1]
    xf = packed(bfix)
    s1 = BNNServer(cb, params, max_batch=bfix, mesh=None)
    t1 = _wall(s1.apply_batch, xf)
    scaling = {"batch": bfix, "devices_1_wall_s": t1}
    if mesh is not None:
        sn = BNNServer(cb, params, max_batch=bfix, mesh=mesh)
        tn = _wall(sn.apply_batch, xf)
        scaling.update({"devices_n": n_dev, "devices_n_wall_s": tn,
                        "speedup": t1 / tn})
        log(f"device scaling @batch={bfix}: 1 dev {t1 * 1e3:.2f}ms vs "
            f"{n_dev} dev {tn * 1e3:.2f}ms ({t1 / tn:.2f}x)")

    # -- continuous-batching stream vs synchronous loop --------------- #
    # many small same-kind requests: exactly the traffic the admission
    # window exists for — the worker coalesces them into a few large
    # dispatches (and overlaps host prep with device compute) where
    # the sync loop pays one small dispatch per request
    n_req, rows_each = (8, 2) if smoke else (32, 8)
    payloads = [packed(rows_each) for _ in range(n_req)]

    def sync_loop():
        for x in payloads:
            tsrv.apply_batch(x)

    t_sync = _wall(sync_loop, iters=3)
    ssrv = BNNServer(cb, params, max_batch=max_batch, mesh=mesh).start()
    try:
        def stream():
            futs = [ssrv.submit(x) for x in payloads]
            for f in futs:
                f.result(timeout=600)

        t_stream = _wall(stream, iters=3)
    finally:
        ssrv.stop()
    stream_stats = ssrv.stats()
    runs = 4                          # 1 warmup + 3 timed repeats
    rows_total = n_req * rows_each
    stream_row = {
        "requests": n_req, "rows_each": rows_each,
        "rows_total": rows_total,
        "sync_wall_s": t_sync, "stream_wall_s": t_stream,
        "pipeline_speedup": t_sync / t_stream,
        "rows_per_s_stream": rows_total / t_stream,
        "dispatches_per_run": stream_stats["batches"] / runs,
        "inflight_peak": stream_stats["inflight_peak"],
    }
    log(f"stream of {n_req} x {rows_each}-row requests: sync "
        f"{t_sync * 1e3:.2f}ms vs pipelined {t_stream * 1e3:.2f}ms "
        f"({t_sync / t_stream:.2f}x), coalesced into "
        f"{stream_stats['batches'] / runs:.1f} dispatches/run, "
        f"inflight peak {stream_stats['inflight_peak']}")

    # -- ragged-padding overhead vs an exact-shape jit ---------------- #
    psrv = BNNServer(cb, params, max_batch=max_batch, mesh=mesh)
    exact_cache = {}

    def exact_jit_wall(rows):
        """Denominator: a jit traced at EXACTLY this row count, same
        params placement and sharding — zero padding by construction."""
        if rows not in exact_cache:
            f = jax.jit(lambda p, x: cb.apply(p, x))
            xs = shard_batch(packed(rows), mesh)
            exact_cache[rows] = _wall(f, psrv.params, xs)
        return exact_cache[rows]

    ragged = []
    for rows in ([3, 5] if smoke else [3, 5, 9, 33, 66]):
        xr = packed(rows)
        t_r = _wall(psrv.apply_batch, xr)
        bucket = bucket_for(rows, max_batch)
        valid = ragged_valid(rows, bucket)
        t_exact = exact_jit_wall(rows)
        ragged.append({
            "rows": rows, "bucket": bucket, "valid": valid,
            "wall_s": t_r, "exact_jit_wall_s": t_exact,
            "bucket_jit_wall_s": exact_jit_wall(bucket),
            "occupancy": rows / bucket,
            "compute_occupancy": rows / valid,
            "overhead_vs_exact": t_r / t_exact})
        log(f"rows {rows:>3d} -> bucket {bucket:>3d} masked to "
            f"{valid:>3d}: wall {t_r * 1e3:7.2f}ms = "
            f"{t_r / t_exact:.2f}x exact-shape jit (full bucket would "
            f"cost {exact_jit_wall(bucket) / t_exact:.2f}x)")

    # -- the ISSUE 6 perf gates (full runs only: smoke shapes are too  #
    #    small to measure anything but dispatch overhead) ------------- #
    if not smoke:
        if "speedup" in scaling:
            assert scaling["speedup"] > 1.0, (
                f"{n_dev}-device serving is SLOWER than 1 device "
                f"({scaling['speedup']:.2f}x) — scaling gate failed")
        for r in ragged:
            assert r["overhead_vs_exact"] < 1.5, (
                f"ragged rows={r['rows']} pays "
                f"{r['overhead_vs_exact']:.2f}x over the exact-shape "
                f"jit — padding gate failed")
        log("perf gates OK (speedup > 1, every padding point < 1.5x)")

    out = {"env": _env(), "host_backend": jax.default_backend(),
           "devices": n_dev, "smoke": smoke,
           "model": {"d0": d0, "hidden": hidden, "max_batch": max_batch},
           "throughput": thr_rows, "scaling": scaling,
           "stream": stream_row, "padding": ragged,
           "server_stats": stream_stats,
           "bit_identity": "sharded == single-device (words + logits); "
                           "masked == unmasked on valid rows"}
    if out_json:
        with open(out_json, "w") as f:
            json.dump(out, f, indent=1)
        log(f"wrote {out_json}")
    return out


def run_faults(log=print, out_json=FAULTS_OUT, smoke=False):
    """Fault injection + chaos recovery (ISSUE 7 acceptance).

    Two halves, mirroring src/repro/robustness/:
      * data faults — seeded SEU bit flips into the packed weight
        words and per-channel threshold perturbation (the analog-
        margin noise of the mixed-signal threshold neuron), swept over
        a Logits-terminated network to produce flips-vs-degradation
        curves (full runs sweep BinaryNet CIFAR-10; smoke a small
        conv+FC spec).  Gate: zero injection is bit-identical.
      * system faults — a seeded ChaosMonkey driving BNNServer's
        recovery ladder end to end.  Gates, raised on violation:
        a poisoned request fails alone with PoisonRequest while its
        coalesced neighbors resolve bit-identically; a backend-faulted
        flight re-executes on the fallback backend bit-identically to
        the healthy path; and under a storm of rate faults + latency
        spikes + killed worker threads + an expired deadline, every
        submitted future resolves (zero lost futures).
    """
    from repro import graph
    from repro.core.workloads import binarynet_cifar10
    from repro.robustness import (ChaosConfig, ChaosMonkey, seu_curve,
                                  threshold_curve)
    from repro.serving import BNNServer, PoisonRequest, RequestTimeout

    log("\n== fault injection: SEU bit flips + threshold noise ==")
    if smoke:
        spec = graph.BNNSpec("faults_small", (8, 8, 32), (
            graph.Binarize("b"),
            graph.BinaryConv("c1", 3, 3, 32, 64, 8, 8, 8, 8, 1, 1),
            graph.BNThreshold("c1.bn", 64),
            graph.MaxPool("p1", 2, 2),
            graph.BinaryDense("d1", 4 * 4 * 64, 64),
            graph.BNThreshold("d1.bn", 64),
            graph.BinaryDense("d2", 64, 16),
            graph.Logits("logits", 16)))
        model_name, rows_x = spec.name, 4
        cb = graph.compile(spec, backend="xla", batch=rows_x)
        x = jax.random.normal(jax.random.PRNGKey(1),
                              (rows_x, 8, 8, 32), jnp.float32)
        flip_counts = [0, 1, 4, 16, 64]
        sigmas = [0.0, 1.0, 2.0]
    else:
        wl = binarynet_cifar10()
        model_name, rows_x = wl.name, 8
        cb = graph.compile(wl, backend="xla", batch=rows_x)
        x = jax.random.normal(jax.random.PRNGKey(1),
                              (rows_x, 32, 32, 3), jnp.float32)
        flip_counts = [0, 1, 2, 4, 8, 16, 32, 64, 128]
        sigmas = [0.0, 0.5, 1.0, 2.0, 4.0]
    params = cb.init(jax.random.PRNGKey(0))
    seu = seu_curve(cb, params, x, flip_counts, seed=0)
    assert seu[0]["argmax_match"] == 1.0, "0-flip forward diverged"
    assert seu[0]["max_abs_logit_delta"] == 0.0
    for r in seu:
        log(f"  SEU {r['n_flips']:>4d} flips | argmax match "
            f"{r['argmax_match']:.2f} | mean |dlogit| "
            f"{r['mean_abs_logit_delta']:.3f}")
    thr = threshold_curve(cb, params, x, sigmas, seed=0)
    assert thr[0]["argmax_match"] == 1.0, "sigma=0 forward diverged"
    for r in thr:
        log(f"  thr sigma {r['sigma']:4.1f} | argmax match "
            f"{r['argmax_match']:.2f} | mean |dlogit| "
            f"{r['mean_abs_logit_delta']:.3f}")

    # -- chaos recovery through the server --------------------------- #
    log("== chaos recovery gates (BNNServer ladder) ==")
    mspec = graph.from_dense_stack(256, [128, 64], name="chaos_mlp")
    mcb = graph.compile(mspec, backend="xla", batch=4)
    mparams = mcb.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)

    def packed(rows):
        xr = rng.standard_normal((rows, 256)).astype(np.float32)
        return binarize_pack(jnp.asarray(xr), backend="xla")

    # (a) poison isolation in one coalesced flight
    chaos = ChaosMonkey()
    srv = BNNServer(mcb, mparams, max_batch=8, chaos=chaos,
                    retry_backoff_s=0.0)
    good = [packed(2) for _ in range(3)]
    bad = packed(2)
    refs = [mcb.apply(mparams, g) for g in good]
    chaos.poison(bad)
    futs = [srv.submit(good[0]), srv.submit(bad), srv.submit(good[1]),
            srv.submit(good[2])]
    srv.flush()
    poison_isolated = isinstance(futs[1].exception(), PoisonRequest)
    assert poison_isolated, "poisoned request did not get PoisonRequest"
    for f, ref in zip([futs[0], futs[2], futs[3]], refs):
        np.testing.assert_array_equal(
            np.array(f.result().words), np.array(ref.words),
            err_msg="healthy neighbor diverged after bisection")
    iso_stats = srv.stats()["faults"]
    log(f"  poison isolated in {iso_stats['bisections']} bisections; "
        f"neighbors bit-identical")

    # (b) backend fallback bit-identity
    from repro.serving.errors import BackendFault
    chaos_fb = ChaosMonkey()
    srv_fb = BNNServer(mcb, mparams, max_batch=8, chaos=chaos_fb,
                       retry_backoff_s=0.0)
    xq = packed(5)
    ref = mcb.apply(mparams, xq)
    chaos_fb.fail_next(BackendFault("injected kernel-launch failure"))
    fut = srv_fb.submit(xq)
    srv_fb.flush()
    np.testing.assert_array_equal(
        np.array(fut.result().words), np.array(ref.words),
        err_msg="fallback path diverged from the healthy path")
    fallback_identical = True
    assert srv_fb.stats()["faults"]["backend_fallbacks"] == 1
    log("  backend fallback bit-identical to the healthy path")

    # (c) the storm: rate faults + latency spikes + thread kills +
    #     an expired deadline, through the worker threads
    n_req = 16 if smoke else 64
    chaos_st = ChaosMonkey(ChaosConfig(
        seed=2, fault_rate=0.3, latency_spike_rate=0.3,
        latency_spike_s=0.002 if smoke else 0.01))
    srv_st = BNNServer(mcb, mparams, max_batch=8, chaos=chaos_st,
                       retry_backoff_s=0.001,
                       supervise_interval_s=0.01).start()
    chaos_st.kill("dispatcher")
    chaos_st.kill("completer")
    t0 = time.perf_counter()
    futs = [srv_st.submit(packed(1 + i % 4)) for i in range(n_req)]
    expired = srv_st.submit(packed(2), deadline_s=0.0)
    for f in futs:
        f.result(timeout=300)
    srv_st.stop()
    storm_wall = time.perf_counter() - t0
    zero_lost = all(f.done() for f in futs) and expired.done()
    assert zero_lost, "a submitted future never resolved"
    assert isinstance(expired.exception(), RequestTimeout)
    st = srv_st.stats()
    sf = st["faults"]
    assert sf["thread_restarts"] >= 2, "supervisor missed a dead loop"
    log(f"  storm: {n_req} requests in {storm_wall:.2f}s | "
        f"{sf['flights']} faulted flights, "
        f"{sf['backend_fallbacks']} fallbacks, {sf['retries']} retries, "
        f"{sf['thread_restarts']} thread restarts, "
        f"{chaos_st.events['spikes']} spikes | zero lost futures")

    chaos_row = {
        "requests": n_req,
        "zero_lost_futures": zero_lost,
        "poison_isolated": poison_isolated,
        "fallback_bit_identical": fallback_identical,
        "flight_faults": sf["flights"],
        "backend_fallbacks": sf["backend_fallbacks"],
        "retries": sf["retries"],
        "bisections": iso_stats["bisections"],
        "poisoned_requests": iso_stats["poisoned_requests"],
        "timeouts": sf["timeouts"],
        "thread_restarts": sf["thread_restarts"],
        "latency_spikes": chaos_st.events["spikes"],
        "straggler_flags": len(st["straggler_flags"]),
        "storm_wall_s": storm_wall,
    }
    out = {"env": _env(), "host_backend": jax.default_backend(),
           "smoke": smoke,
           "model": {"name": model_name, "rows": rows_x,
                     "flip_counts": flip_counts, "sigmas": sigmas},
           "seu": seu, "thresholds": thr, "chaos": chaos_row}
    if out_json:
        with open(out_json, "w") as f:
            json.dump(out, f, indent=1)
        log(f"wrote {out_json}")
    return out


def run_train(log=print, out_json=TRAIN_OUT, smoke=False):
    """The closed train->fold->compile->serve loop (ISSUE 8).

    STE-trains each model on the deterministic synthetic image stream
    (data/images.py), then walks the whole export contract with hard
    gates, raised on violation:

      * learning — held-out eval accuracy must beat chance by the
        model's margin (the synthetic task is separable by
        construction, so failing this means the loop is broken);
      * fold bit-consistency — the folded packed CompiledBNN forward
        must be EXACTLY equal to the training eval forward
        (check_sign_identity);
      * serve bit-consistency — the same equality end to end through
        BNNServer.apply_batch;
      * checkpoint round-trip — (params, bn) through the sha256
        checkpointer come back bit-identical.

    Full runs train the binary MLP and the BinaryNet CIFAR-10
    topology; smoke trains a tiny MLP only.
    """
    import shutil
    import tempfile

    from repro import graph, train
    from repro.checkpoint import restore, save
    from repro.core.workloads import binarynet_cifar10
    from repro.data import ImageDataConfig
    from repro.data.images import eval_batch_at
    from repro.serving import BNNServer
    from repro.train.export import _serving_input

    log("\n== STE training -> fold -> compile -> serve ==")
    jobs = []
    if smoke:
        d = ImageDataConfig(4, 8, 8, 2, global_batch=16, seed=0,
                            flip_prob=0.02)
        s = graph.from_dense_stack(d.n_pixels, [64, d.num_classes],
                                   logits=True, name="train_mlp_smoke")
        jobs.append((s, d, train.TrainConfig(steps=40, lr=0.05,
                                             log_every=10), 2, 0.15))
    else:
        d = ImageDataConfig(10, 16, 16, 3, global_batch=32, seed=0,
                            flip_prob=0.02)
        s = graph.from_dense_stack(d.n_pixels, [256, d.num_classes],
                                   logits=True, name="train_mlp")
        jobs.append((s, d, train.TrainConfig(steps=120, lr=0.05,
                                             log_every=20), 4, 0.4))
        db = ImageDataConfig(10, 32, 32, 3, global_batch=8, seed=0,
                             flip_prob=0.02)
        sb = graph.from_workload(binarynet_cifar10())
        jobs.append((sb, db, train.TrainConfig(steps=60, lr=0.02,
                                               log_every=10), 4, 0.15))

    models = []
    for spec, dcfg, tcfg, eval_batches, margin in jobs:
        chance = 1.0 / dcfg.num_classes
        log(f"-- {spec.name}: {tcfg.steps} steps x batch "
            f"{dcfg.global_batch} on {dcfg.height}x{dcfg.width}x"
            f"{dcfg.channels}/{dcfg.num_classes}-class images")
        t0 = time.perf_counter()
        out = train.fit(spec, dcfg, tcfg, log_fn=lambda m: log("   " + m))
        wall = time.perf_counter() - t0
        params, bn = out["params"], out["bn"]

        ev = train.evaluate(spec, params, bn, dcfg,
                            n_batches=eval_batches)
        ev_latent = train.evaluate(spec, params, bn, dcfg,
                                   n_batches=eval_batches,
                                   binarize=False)
        assert ev["acc"] > chance + margin, (
            f"{spec.name}: eval acc {ev['acc']:.3f} does not beat "
            f"chance {chance:.2f} + margin {margin:.2f}")

        # fold + serve bit-consistency on a held-out batch
        x = eval_batch_at(dcfg, eval_batches + 1)["image"]
        if len(spec.input_shape) == 1:
            x = x.reshape(x.shape[0], -1)
        cb, sparams = train.export_compiled(spec, params, bn,
                                            backend="xla",
                                            batch=x.shape[0])
        stats = train.check_sign_identity(spec, params, bn, x,
                                          cb=cb, sparams=sparams)
        fold_ok = stats["max_abs_logit_delta"] == 0.0 \
            and stats["argmax_agreement"] == 1.0
        srv = BNNServer(cb, sparams, max_batch=x.shape[0])
        served = srv.apply_batch(_serving_input(spec, x, cb.backend))
        eval_logits, _ = train.train_forward(spec, params, bn,
                                             jnp.asarray(x), train=False)
        serve_ok = bool(np.array_equal(
            np.asarray(served, np.float32),
            np.asarray(eval_logits, np.float32)))
        assert fold_ok and serve_ok, \
            f"{spec.name}: fold/serve bit-consistency violated"

        # sha256 checkpoint round-trip, bit-identical
        tmp = tempfile.mkdtemp(prefix="bench_train_ckpt_")
        try:
            save(tmp, out["step"], (params, bn),
                 extra={"step": out["step"]})
            (p2, b2), _meta = restore(tmp, (params, bn))
            flat_a = jax.tree.leaves((params, bn))
            flat_b = jax.tree.leaves((p2, b2))
            ckpt_ok = all(np.array_equal(np.asarray(a), np.asarray(b))
                          for a, b in zip(flat_a, flat_b))
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
        assert ckpt_ok, f"{spec.name}: checkpoint round-trip diverged"

        losses = out["losses"]
        stride = max(1, len(losses) // 20)
        log(f"   loss {losses[0]:.3f} -> {losses[-1]:.3f} | eval acc "
            f"{ev['acc']:.3f} (latent {ev_latent['acc']:.3f}, chance "
            f"{chance:.2f}) | fold/serve/ckpt bit-identical | "
            f"{wall:.1f}s ({tcfg.steps / wall:.2f} steps/s)")
        models.append({
            "name": spec.name,
            "steps": tcfg.steps,
            "global_batch": dcfg.global_batch,
            "num_classes": dcfg.num_classes,
            "chance": chance,
            "margin": margin,
            "first_train_loss": losses[0],
            "final_train_loss": losses[-1],
            "loss_curve": losses[::stride],
            "train_acc_final": out["accs"][-1],
            "eval_acc": ev["acc"],
            "eval_loss": ev["loss"],
            "eval_rows": ev["rows"],
            "latent_eval_acc": ev_latent["acc"],
            "binarization_gap": ev_latent["acc"] - ev["acc"],
            "fold_bit_consistent": fold_ok,
            "serve_bit_consistent": serve_ok,
            "ckpt_roundtrip_exact": ckpt_ok,
            "sign_identity_rows": stats["rows"],
            "wall_train_s": wall,
            "steps_per_s": tcfg.steps / wall,
        })

    out = {"env": _env(), "host_backend": jax.default_backend(),
           "smoke": smoke, "models": models}
    if out_json:
        with open(out_json, "w") as f:
            json.dump(out, f, indent=1)
        log(f"wrote {out_json}")
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None,
                    help="output json path ('' to skip writing; default "
                         "BENCH_kernels.json / BENCH_fused.json / "
                         "BENCH_conv.json)")
    ap.add_argument("--fused", action="store_true",
                    help="benchmark the fused threshold->pack epilogue "
                         "(fails on any fused/unfused or cross-backend "
                         "divergence)")
    ap.add_argument("--conv", action="store_true",
                    help="benchmark the packed binary conv2d datapath "
                         "(fails on any direct/im2col/oracle divergence)")
    ap.add_argument("--compile", action="store_true",
                    help="benchmark the graph compile(spec) pipeline "
                         "(fails on fused-vs-chained or cross-backend "
                         "divergence, or a Table III mismatch)")
    ap.add_argument("--serve", action="store_true",
                    help="benchmark BNNServer bucketed+sharded serving "
                         "on a 4-virtual-device CPU mesh (fails on "
                         "sharded-vs-single-device divergence)")
    ap.add_argument("--faults", action="store_true",
                    help="fault-injection curves (SEU bit flips, "
                         "threshold noise) + chaos recovery gates "
                         "(fails on poison leakage, fallback "
                         "divergence, or any lost future)")
    ap.add_argument("--train", action="store_true",
                    help="STE-train, fold, compile, and serve the image "
                         "models end to end (fails when eval accuracy "
                         "does not beat chance by the margin, or on any "
                         "fold/serve/checkpoint bit-inconsistency)")
    ap.add_argument("--dse", action="store_true",
                    help="cycle-accurate TULIP-PE mesh simulation + "
                         "design-space Pareto sweep (fails on "
                         "simulator-vs-oracle divergence, a Table III "
                         "cycle mismatch, or an energy advantage "
                         "below the paper's 3x claim)")
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes for CI (with --fused/--conv/"
                         "--compile/--serve/--faults/--train/--dse)")
    args = ap.parse_args()
    use_compile_cache()

    def dest_for(default):
        """Default output path; --smoke writes BENCH_*_smoke.json so a
        smoke run (CI or local) never clobbers the tracked full-run
        artifacts."""
        if args.out is not None:
            return args.out or None
        if args.smoke:
            return default.replace(".json", "_smoke.json")
        return default

    if args.fused:
        run_fused(out_json=dest_for(FUSED_OUT), smoke=args.smoke)
    elif args.conv:
        run_conv(out_json=dest_for(CONV_OUT), smoke=args.smoke)
    elif args.compile:
        run_compile(out_json=dest_for(COMPILE_OUT), smoke=args.smoke)
    elif args.serve:
        run_serve(out_json=dest_for(SERVE_OUT), smoke=args.smoke)
    elif args.faults:
        run_faults(out_json=dest_for(FAULTS_OUT), smoke=args.smoke)
    elif args.train:
        run_train(out_json=dest_for(TRAIN_OUT), smoke=args.smoke)
    elif args.dse:
        # imported here: the sim package pulls the graph compiler in,
        # which the other benchmark modes never need
        from repro.sim.dse import run_dse

        run_dse(out_json=dest_for(DSE_OUT), smoke=args.smoke)
    else:
        run(out_json=dest_for(DEFAULT_OUT))
