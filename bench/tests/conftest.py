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
