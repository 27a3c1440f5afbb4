"""Chip smoke test: serve full-width BinaryNet CIFAR-10 on a TPU.

The main path, through the entry points a user calls:
``graph.compile(binarynet_cifar10())`` -> ``BNNServer`` -> ``submit()``
-> ``Future.result()``, on the ``pallas`` backend (the TPU default), with
backend fallback off so no answer can come from anywhere but the
compiled kernels.  Weights are random, from ``--seed``; images are the
seeded synthetic CIFAR-shaped stream of ``repro.data.images``.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # only the 4-chip data-mesh path

One chip: checks that every planned launch is a Pallas kernel in the
compiled program, then serves requests of 1, 7 and 32 rows (buckets 1,
8 and 32; 7 rows is a ragged level of bucket 8) twice and compares
every answer bit for bit with the ``xla`` backend on the same chip.
``--chips 4``: serves the same requests through
``BNNServer(mesh=data_mesh())`` over four chips and compares them bit
for bit with the one-chip server.  Either way every fault counter of
``stats()`` must stay zero.

Earlier lines print compile seconds, per-request walls (measured on
the chip, ``block_until_ready`` inside ``Future.result``), ``describe()``
and ``stats()``.  The last line is ``{"ok": true, "device": {...}}``.
Without a TPU, or on any failed check, the script exits nonzero before
printing that line.  One process; it starts no other.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

REQUEST_ROWS = (1, 7, 32)
MAX_BATCH = 32
RESULT_TIMEOUT_S = 600


def _images(seed):
    from repro.data.images import ImageDataConfig, image_batch_at

    cfg = ImageDataConfig(num_classes=10, height=32, width=32, channels=3,
                          global_batch=sum(REQUEST_ROWS), seed=seed)
    x = image_batch_at(cfg, 0)["image"]
    cuts = np.cumsum(REQUEST_ROWS)[:-1]
    return [jnp.asarray(r) for r in np.split(x, cuts)]


def _serve(srv, requests, label):
    """Submit each request alone and wait for it; returns the logits
    and prints each request's wall."""
    outs = []
    for x in requests:
        t0 = time.perf_counter()
        y = srv.submit(x).result(timeout=RESULT_TIMEOUT_S)
        wall = time.perf_counter() - t0
        print(f"{label}: {x.shape[0]:>2d} rows  wall {wall:.6f} s")
        outs.append(np.asarray(y))
    return outs


def _check_faults(srv, label):
    st = srv.stats()
    print(f"{label} stats(): {json.dumps(st, default=str)}")
    bad = {k: v for k, v in st["faults"].items() if v}
    if bad:
        raise AssertionError(f"{label}: nonzero fault counters {bad}")


def _check_logits(got, rows, label):
    if got.shape != (rows, 10) or got.dtype != np.float32:
        raise AssertionError(f"{label}: logits {got.shape} {got.dtype}, "
                             f"want ({rows}, 10) float32")
    if not np.all(np.isfinite(got)):
        raise AssertionError(f"{label}: non-finite logits")


def one_chip(seed):
    from repro import graph
    from repro.core.workloads import binarynet_cifar10
    from repro.kernels.packed import get_backend
    from repro.serving import BNNServer

    t0 = time.perf_counter()
    cb = graph.compile(binarynet_cifar10(), batch=MAX_BATCH)
    params = cb.init(jax.random.PRNGKey(seed))
    backend = get_backend(cb.backend).name
    if backend != "pallas":
        raise AssertionError(f"resolved backend is {backend!r}, not pallas")
    print(cb.describe())
    x0 = jnp.zeros((MAX_BATCH, 32, 32, 3), jnp.float32)
    hlo = jax.jit(cb.apply).lower(params, x0).compile().as_text()
    kernels = hlo.count('custom_call_target="tpu_custom_call"')
    print(f"compiled forward at batch {MAX_BATCH}: {kernels} Pallas "
          f"kernels for {cb.launch_count()} planned launches; compile "
          f"{time.perf_counter() - t0:.3f} s")
    if kernels != cb.launch_count():
        raise AssertionError("the plan's launches are not all Pallas "
                             "kernels in the compiled program")

    requests = _images(seed)
    srv = BNNServer(cb, params, max_batch=MAX_BATCH,
                    fallback_backend=None).start()
    cold = _serve(srv, requests, "pallas cold (incl. jit compile)")
    warm = _serve(srv, requests, "pallas warm")
    srv.stop()
    _check_faults(srv, "pallas server")

    ref = jax.jit(graph.compile(binarynet_cifar10(),
                                backend="xla").apply)
    for x, a, b in zip(requests, cold, warm):
        want = np.asarray(ref(params, x))
        _check_logits(a, x.shape[0], "pallas")
        np.testing.assert_array_equal(a, want)
        np.testing.assert_array_equal(b, want)
    print(f"pallas logits == xla logits, bit for bit, for rows "
          f"{REQUEST_ROWS} (cold and warm)")


def four_chips(seed):
    from repro import graph
    from repro.core.workloads import binarynet_cifar10
    from repro.serving import BNNServer, data_mesh

    cb = graph.compile(binarynet_cifar10(), batch=MAX_BATCH)
    params = cb.init(jax.random.PRNGKey(seed))
    requests = _images(seed)
    mesh = data_mesh()
    print(f"mesh {dict(mesh.shape)} over {mesh.size} chips")
    single = BNNServer(cb, params, max_batch=MAX_BATCH,
                       fallback_backend=None).start()
    want = _serve(single, requests, "1 chip")
    single.stop()
    _check_faults(single, "1-chip server")
    sharded = BNNServer(cb, params, max_batch=MAX_BATCH, mesh=mesh,
                        fallback_backend=None).start()
    cold = _serve(sharded, requests, f"{mesh.size} chips cold "
                                     f"(incl. jit compile)")
    warm = _serve(sharded, requests, f"{mesh.size} chips warm")
    sharded.stop()
    _check_faults(sharded, f"{mesh.size}-chip server")
    for x, w, a, b in zip(requests, want, cold, warm):
        _check_logits(w, x.shape[0], "1 chip")
        np.testing.assert_array_equal(a, w)
        np.testing.assert_array_equal(b, w)
    print(f"{mesh.size}-chip data-mesh logits == 1-chip logits, bit for "
          f"bit, for rows {REQUEST_ROWS}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 4-chip data-mesh path against "
                         "the 1-chip server")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{dev.platform!r}); nothing was run")
    if len(devices) != args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX sees "
                 f"{len(devices)} devices")
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}, "
          f"jax {jax.__version__}")

    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    (four_chips if args.chips == 4 else one_chip)(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
