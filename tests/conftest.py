"""Test harness config.

Forces JAX onto the host CPU backend with 8 virtual devices *before* jax is
imported anywhere, so mesh/sharding tests exercise real multi-device SPMD
without TPU hardware (mirrors the reference's trick of simulating an N-host
Rabit cluster with N local processes — test/unit/test_distributed.py:25-31).
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# the in-process suite stays hermetic: entry points arm the persistent
# compile cache (utils/compile_cache.py), and a warm cache from an earlier
# run would skip the compiles some tests count
import jax  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
