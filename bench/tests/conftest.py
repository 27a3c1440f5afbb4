"""The benchmark's own tests run on the CPU with four virtual devices
(the 4-chip data mesh in miniature).  Run them from the repo root:

    python -m pytest bench/
"""
import os
import sys

if "jax" not in sys.modules:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=4").strip()

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import pytest  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture
def on_cpu(monkeypatch, tmp_path):
    """The harness without its look for a chip: the test cells of
    data/bench.json on the CPU's devices with the v5e's peaks, and the
    compile cache in a directory of the test's own."""
    import jax
    import run
    from repro.launch import cache

    monkeypatch.setattr(run, "BENCHMARK_JSON", os.path.join(DATA, "bench.json"))
    monkeypatch.setattr(run, "TRAFFIC_DIR", os.path.join(DATA, "traffic"))
    monkeypatch.setattr(run, "WORKLOADS_DIR", os.path.join(DATA, "workloads"))
    monkeypatch.setattr(cache, "CACHE_DIR", str(tmp_path / "jax_cache"))
    peaks = run._load_json(os.path.join(run.BENCH, "peaks.json"))["kinds"]["TPU v5 lite"]
    monkeypatch.setattr(run, "chips_for", lambda n: (jax.devices()[:n], peaks))
    return monkeypatch
