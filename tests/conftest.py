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

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def sketch_as_mesh(monkeypatch):
    """``sketch_as_mesh(shards, groups=None)``: from then on a ONE-device
    session sketches as a `data` mesh of ``shards`` would: its rows cut into
    the mesh session's shards (contiguous, ``ceil(n / shards)`` rows, the tail
    padded; with ``groups`` the group-partitioned layout of distributed
    ranking), each sketched, the candidates merged. A mesh job's cuts are the
    merge of its shards' sketches, not the uncut matrix's sketch, so a test
    that holds a mesh forest to a one-device forest trains both under the
    mesh session's cuts. A session that has shards of its own is untouched."""
    from sagemaker_xgboost_container_tpu.models import booster

    real = booster.sketch_shards

    def arm(shards, groups=None):
        def as_mesh(features, weights, max_bin, devices=(None,), merge=None):
            if len(features) != 1 or max_bin is None:
                return real(features, weights, max_bin, devices, merge=merge)
            x, w = np.asarray(features[0], np.float32), weights[0]
            n = x.shape[0]
            if groups is None:
                rows = -(-n // shards)
                take = np.arange(rows * shards)
                take[n:] = -1
            else:
                from sagemaker_xgboost_container_tpu.ops.ranking import (
                    build_sharded_group_layout,
                )

                take, _layout, rows = build_sharded_group_layout(
                    np.asarray(groups, np.int64), shards
                )

            def block(values, fill, s):
                t = take[s * rows : (s + 1) * rows]
                out = np.full((rows,) + values.shape[1:], fill, np.float32)
                out[t >= 0] = values[t[t >= 0]]
                return out

            return real(
                [block(x, np.nan, s) for s in range(shards)],
                [None if w is None else block(np.asarray(w, np.float32), 0.0, s)
                 for s in range(shards)],
                max_bin,
            )

        monkeypatch.setattr(booster, "sketch_shards", as_mesh)

    return arm
