"""Seeded direct calls of ``ops/lossguide.py::build_tree_lossguide`` and the
sha256 of what each returns (the padded tree arrays and ``row_out``).

``python tests/lossguide_cases.py`` prints ``{case: digest}`` for the package
on ``sys.path``: run against the commit before the split-step loop was rolled
it gave the digests ``tests/test_lossguide_rolled.py`` pins. Beside them the
depth-wise build's nine mesh cases (``depthwise_cases``, printed under
``depthwise.<case>``), read off the parent of PR 45 and pinned in
``tests/test_hist_comm.py``.
"""

import hashlib
import json
import os
import sys
from functools import partial

import numpy as np

N_ROWS, N_FEATURES, NUM_BINS = 640, 8, 17
TREE_FIELDS = (
    "feature", "bin", "default_left", "is_leaf", "leaf_value", "base_weight",
    "gain", "sum_hess", "left", "right",
)


def seeded_inputs(seed=5, n=N_ROWS, d=N_FEATURES, num_bins=NUM_BINS):
    """(bins u8 [n, d] with a few missing, grad, hess, num_cuts)."""
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, num_bins - 1, size=(n, d)).astype(np.uint8)
    bins[rng.rand(n, d) < 0.05] = num_bins - 1  # the missing bin
    signal = (bins[:, 0] > 7).astype(np.float32) - 0.3 * (bins[:, 3] > 4) + 0.1 * bins[:, 5]
    grad = (rng.randn(n) * 0.5 + signal - signal.mean()).astype(np.float32)
    hess = (0.05 + rng.rand(n)).astype(np.float32)
    num_cuts = np.full(d, num_bins - 1, np.int32)
    return bins, grad, hess, num_cuts


def _variants():
    sets = np.zeros((2, N_FEATURES), bool)
    sets[0, :4] = True
    sets[1, 3:] = True
    return {
        "plain": {},
        "bynode": {"colsample_bynode": 0.6, "rng_seed": 11},
        "bylevel": {"colsample_bylevel": 0.7, "rng_seed": 12},
        "sets": {"interaction_sets": sets},
        "mcw": {"min_child_weight": 12.0},
        "depth3": {"max_depth": 3},
        "gamma": {"gamma": 0.4, "alpha": 0.1, "max_delta_step": 0.5},
    }


def cases():
    """{name: (mesh shape or None, subtraction on, builder kwargs)}."""
    out = {}
    variants = _variants()
    for leaves in (2, 8, 31):
        for subtract in (True, False):
            for name, kw in variants.items():
                if leaves != 8 and name in ("bylevel", "gamma"):
                    continue
                key = "l{}.{}.{}".format(leaves, "sub" if subtract else "nosub", name)
                out[key] = (None, subtract, dict(kw, max_leaves=leaves))
    out["l8.sub.kernel"] = (None, True, {"max_leaves": 8, "kernel": True})
    # on a mesh; "psum" in the names is the collective there is (up to PR 44
    # a second lowering had the same cases under its own name)
    for subtract in (True, False):
        for name in ("plain", "bynode", "sets"):
            key = "data4.psum.{}.{}".format("sub" if subtract else "nosub", name)
            out[key] = ((4,), subtract, dict(variants[name], max_leaves=8))
    for name in ("plain", "bynode", "sets"):
        out["data2xfeature2.psum.{}".format(name)] = (
            (2, 2), True, dict(variants[name], max_leaves=8)
        )
    return out


def depthwise_cases():
    """The depth-wise build (``ops/tree_build.py::build_tree``, depth 3) under
    ``shard_map``, the same tuples: a `data` mesh of 4 with and without
    sibling subtraction, and a 2 x 2 data x feature mesh."""
    out = {}
    variants = _variants()
    for name in ("plain", "bynode", "sets"):
        for subtract in (True, False):
            key = "data4.{}.{}".format("sub" if subtract else "nosub", name)
            out[key] = ((4,), subtract, dict(variants[name], max_depth=3))
        out["data2xfeature2.{}".format(name)] = ((2, 2), True, dict(variants[name], max_depth=3))
    return out


def run_case(mesh_shape, subtract, kw, depthwise=False):
    """The builder's (tree dict, row_out) as numpy, for one case:
    ``build_tree_lossguide``, or ``build_tree`` where ``depthwise``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from sagemaker_xgboost_container_tpu.ops import histogram as hist_mod
    from sagemaker_xgboost_container_tpu.ops.histogram import resolve_hist_knobs
    from sagemaker_xgboost_container_tpu.ops.lossguide import build_tree_lossguide
    from sagemaker_xgboost_container_tpu.ops.tree_build import build_tree

    builder = build_tree if depthwise else build_tree_lossguide
    kw = dict(kw)
    rng_seed = kw.pop("rng_seed", None)
    knobs = None
    if kw.pop("kernel", False):
        knobs = resolve_hist_knobs()._replace(backend="tpu")
    sets = kw.pop("interaction_sets", None)
    bins, grad, hess, num_cuts = seeded_inputs()
    cap = hist_mod.SUBTRACT_CACHE_MAX_BYTES
    hist_mod.SUBTRACT_CACHE_MAX_BYTES = cap if subtract else 0
    try:
        common = dict(
            num_bins=NUM_BINS, eta=0.3, knobs=knobs,
            interaction_sets=None if sets is None else jnp.asarray(sets), **kw
        )
        rng = None if rng_seed is None else jax.random.PRNGKey(rng_seed)
        if mesh_shape is None:
            fn = jax.jit(
                lambda b, g, h, c: builder(b, g, h, c, rng=rng, **common)
            )
            tree, row_out = fn(bins, grad, hess, num_cuts)
        else:
            names = ("data", "feature")[: len(mesh_shape)]
            devices = np.asarray(jax.devices()[: int(np.prod(mesh_shape))])
            mesh = Mesh(devices.reshape(mesh_shape), names)
            feature = len(mesh_shape) == 2
            build = partial(
                builder,
                axis_name="data",
                feature_axis_name="feature" if feature else None,
                n_feature_shards=mesh_shape[1] if feature else 1,
                d_global=N_FEATURES,
                **common
            )
            feat = P("feature") if feature else P()
            fn = jax.jit(
                jax.shard_map(
                    lambda b, g, h, c: build(b, g, h, c, rng=rng),
                    mesh=mesh,
                    in_specs=(
                        P("data", "feature") if feature else P("data", None),
                        P("data"), P("data"), feat,
                    ),
                    out_specs=(P(), P("data")),
                    check_vma=False,
                )
            )
            tree, row_out = fn(bins, grad, hess, num_cuts)
        return {k: np.asarray(v) for k, v in tree.items()}, np.asarray(row_out)
    finally:
        hist_mod.SUBTRACT_CACHE_MAX_BYTES = cap


def digest(tree, row_out):
    sha = hashlib.sha256()
    for field in TREE_FIELDS:
        arr = np.ascontiguousarray(tree[field])
        sha.update(field.encode() + str(arr.dtype).encode() + str(arr.shape).encode())
        sha.update(arr.tobytes())
    sha.update(np.ascontiguousarray(row_out).tobytes())
    return sha.hexdigest()[:16]


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    sys.path.insert(0, os.environ.get("LOSSGUIDE_PACKAGE_ROOT", os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
    digests = {name: digest(*run_case(*case)) for name, case in cases().items()}
    for name, case in depthwise_cases().items():
        digests["depthwise." + name] = digest(*run_case(*case, depthwise=True))
    print(json.dumps(digests, indent=1))
