"""The step replay: a loss-guided tree's evaluation rows taken through its
splits in the order they were made (``ops/tree_build.py::predict_binned_steps``,
PR 44), held to the pointer walk it replaced in every session
(``predict_binned``, the general walk over explicit child ids, kept as the
oracle).

* The same leaf and the same margin for every row, to the bit: full trees,
  trees that stopped early, a one-leaf tree, rows whose every cell is the
  missing bin under both ``default_left``, ``uint8`` and ``uint16`` bins, both
  lowerings of the leaf's node-table lookup, under ``vmap`` over a stack of
  trees and under ``shard_map`` over virtual devices.
* The replay's program: no ``while``, and inside its one loop no gather and no
  reduction over row-length data.
* A loss-guided ``train()`` with a validation set logs the lines and returns
  the forest it did with the pointer walk (sha256 read off the parent commit:
  ``LOSSGUIDE_PACKAGE_ROOT=<parent checkout> python tests/test_eval_step_replay.py``).
"""

import functools
import hashlib
import json
import os
import sys

import numpy as np
import pytest

# jax, the package and ``tests.lossguide_cases`` are imported inside the
# functions: run as a script this file sets the platform and the package root
# (another checkout's, for the parent's digests) before anything imports them
N_EVAL = 333  # not a multiple of the 8 sublanes or of the mesh

# name -> (case of tests/lossguide_cases.py or builder kwargs, internal nodes)
TREES = {
    "full_31_leaves": ("l31.sub.plain", 30),
    "stopped_early": ("l31.sub.mcw", 17),  # 13 steps could not split: unused slots
    "depth_capped": ("l8.sub.depth3", 7),
    "two_leaves": ("l2.sub.plain", 1),
    "one_leaf": ({"max_leaves": 8, "min_child_weight": 1e9}, 0),
}


@functools.lru_cache(maxsize=None)
def _tree(name):
    """(tree arrays on the device, the build's ``row_out``); built once a process."""
    import jax.numpy as jnp

    from tests import lossguide_cases

    case, internal = TREES[name]
    if isinstance(case, dict):
        case = (None, True, case)
    else:
        case = lossguide_cases.cases()[case]
    tree, row_out = lossguide_cases.run_case(*case)
    assert int((~tree["is_leaf"]).sum()) == internal
    return {k: jnp.asarray(tree[k]) for k in lossguide_cases.TREE_FIELDS}, row_out


def _eval_bins(dtype, seed=9):
    from tests import lossguide_cases

    bins = lossguide_cases.seeded_inputs(seed=seed, n=N_EVAL)[0].astype(dtype)
    bins[:5] = lossguide_cases.NUM_BINS - 1  # missing in every column
    return bins


def _bits(values):
    return np.asarray(values).view(np.int32)


def _leaf_ids_as_values(tree):
    """The tree with every node's value its own id: a walk then returns the
    leaf's id (two leaves may hold one value)."""
    import jax.numpy as jnp

    ids = jnp.arange(tree["left"].shape[0], dtype=jnp.float32)
    return dict(tree, leaf_value=ids)


def _pointer(tree, bins, route_impl="gather"):
    from tests import lossguide_cases
    from sagemaker_xgboost_container_tpu.ops.tree_build import predict_binned

    steps = (tree["left"].shape[-1] - 1) // 2
    return predict_binned(tree, bins, steps, lossguide_cases.NUM_BINS, route_impl=route_impl)


def _replay(tree, bins, table_backend="cpu"):
    from tests import lossguide_cases
    from sagemaker_xgboost_container_tpu.ops.tree_build import predict_binned_steps

    return predict_binned_steps(tree, bins, lossguide_cases.NUM_BINS, table_backend=table_backend)


@pytest.mark.parametrize("table_backend", ["tpu", "cpu"], ids=["select", "gather"])
@pytest.mark.parametrize("dtype", [np.uint8, np.uint16], ids=["u8", "u16"])
@pytest.mark.parametrize("flip_default", [False, True], ids=["as_built", "default_left_flipped"])
@pytest.mark.parametrize("name", sorted(TREES))
def test_replay_is_the_pointer_walk_bit_for_bit(name, flip_default, dtype, table_backend):
    import jax
    import jax.numpy as jnp

    tree, row_out = _tree(name)
    if flip_default:  # the all-missing rows then take every split's other side
        tree = dict(tree, default_left=~tree["default_left"])
    bins = jnp.asarray(_eval_bins(dtype))
    replay = jax.jit(lambda t, b: _replay(t, b, table_backend))
    for route_impl in ("gather", "dense"):
        np.testing.assert_array_equal(
            _bits(replay(tree, bins)), _bits(_pointer(tree, bins, route_impl))
        )
    ids = _leaf_ids_as_values(tree)
    leaves = np.asarray(replay(ids, bins)).astype(np.int64)
    np.testing.assert_array_equal(leaves, np.asarray(_pointer(ids, bins)).astype(np.int64))
    assert np.asarray(tree["is_leaf"])[leaves].all()
    if name != "one_leaf":
        assert len(set(leaves[:5])) == 1 and len(set(leaves)) > 1
    if not flip_default:  # and the build's own routing of the rows it was grown on
        from tests import lossguide_cases

        train_bins = jnp.asarray(lossguide_cases.seeded_inputs()[0].astype(dtype))
        np.testing.assert_array_equal(_bits(replay(tree, train_bins)), _bits(row_out))


def _stack(names):
    import jax.numpy as jnp

    trees = [_tree(name)[0] for name in names]
    return {k: jnp.stack([t[k] for t in trees]) for k in trees[0]}, trees


@pytest.mark.parametrize("table_backend", ["tpu", "cpu"], ids=["select", "gather"])
@pytest.mark.parametrize("dtype", [np.uint8, np.uint16], ids=["u8", "u16"])
def test_replay_under_vmap_over_a_stack_of_trees(dtype, table_backend):
    """Bagged or class trees of a loss-guided round: the scalar column is one
    column a tree, and a stack of stacks as ``_apply_packed_tree`` maps it."""
    import jax
    import jax.numpy as jnp

    stacked, trees = _stack(["full_31_leaves", "stopped_early"])
    bins = jnp.asarray(_eval_bins(dtype))
    one = lambda t: _replay(t, bins, table_backend)  # noqa: E731
    want = np.stack([_bits(_pointer(t, bins)) for t in trees])
    np.testing.assert_array_equal(_bits(jax.jit(jax.vmap(one))(stacked)), want)
    twice = {k: jnp.stack([v, v[::-1]]) for k, v in stacked.items()}  # [P, C, nodes]
    got = _bits(jax.jit(jax.vmap(jax.vmap(one)))(twice))
    np.testing.assert_array_equal(got, np.stack([want, want[::-1]]))


@pytest.mark.parametrize("shards", [2, 4])
@pytest.mark.parametrize("name", ["full_31_leaves", "stopped_early"])
def test_replay_under_shard_map_is_local_to_a_shards_rows(name, shards):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    tree, _ = _tree(name)
    bins = _eval_bins(np.uint8)[: N_EVAL - N_EVAL % shards]
    mesh = Mesh(np.asarray(jax.devices()[:shards]), ("data",))
    mapped = jax.jit(
        jax.shard_map(
            lambda t, b: _replay(t, b, "tpu"), mesh=mesh,
            in_specs=(P(), P("data", None)), out_specs=P("data"), check_vma=False,
        )
    )
    text = mapped.lower(tree, jnp.asarray(bins)).as_text()
    assert "all_reduce" not in text and "all_gather" not in text and "collective" not in text
    np.testing.assert_array_equal(
        _bits(mapped(tree, jnp.asarray(bins))), _bits(_pointer(tree, jnp.asarray(bins)))
    )


# ------------------------------------------------------- the replay's program
def _equations(jaxpr, inside_loop=False):
    """(primitive name, equation, inside a loop's body) over every nested jaxpr."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name, eqn, inside_loop
        below = inside_loop or eqn.primitive.name in ("scan", "while")
        for value in eqn.params.values():
            for inner in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner, below)


def _row_length(var, n):
    return n in getattr(var.aval, "shape", ())


@pytest.mark.parametrize("leaves", [16, 255])
def test_replays_program_holds_no_while_and_no_row_length_gather(leaves):
    """Read off the jaxpr, in the chip's lowering of the leaf lookup: one
    loop of a static trip count (no ``while``, so no condition on the data),
    inside it no gather and no reduction over row-length data; outside it no
    gather whose operand or indices are row-length either. And the program
    does not grow with the tree."""
    import jax
    import jax.numpy as jnp

    from sagemaker_xgboost_container_tpu.ops.tree_build import _TREE_FIELDS, tree_from_packed

    n, nodes = 4096 + 7, 2 * leaves - 1

    def program(packed, bins):
        return _replay(tree_from_packed(packed), bins, "tpu")

    jaxpr = jax.make_jaxpr(program)(
        jax.ShapeDtypeStruct((len(_TREE_FIELDS), nodes), jnp.float32),
        jax.ShapeDtypeStruct((n, 28), jnp.uint16),
    ).jaxpr
    found = list(_equations(jaxpr))
    names = [name for name, _eqn, _inside in found]
    assert "while" not in names and "cond" not in names
    loops = [eqn for name, eqn, _inside in found if name == "scan"]
    assert len(loops) == 1 and loops[0].params["length"] == leaves - 1
    assert loops[0].params["unroll"] == 1  # rolled: one body whatever max_leaves
    for name, eqn, inside in found:
        if name == "gather":
            assert not any(_row_length(v, n) for v in eqn.invars), eqn
        if inside:
            assert name != "gather", eqn
            if name.startswith(("reduce_", "arg", "cum")) or name == "sort":
                assert not any(_row_length(v, n) for v in eqn.invars), eqn
        if name == "dynamic_slice" and inside and _row_length(eqn.outvars[0], n):
            assert eqn.outvars[0].aval.shape == (n, 1)  # one column, every row
    if leaves == 255:
        small = jax.make_jaxpr(program)(
            jax.ShapeDtypeStruct((len(_TREE_FIELDS), 31), jnp.float32),
            jax.ShapeDtypeStruct((n, 28), jnp.uint16),
        ).jaxpr
        assert len(list(_equations(small))) == len(found)


# ------------------------------------- a loss-guided train(), parent's digests
def _train_problem():
    rng = np.random.RandomState(44)
    X = rng.rand(1500, 6).astype(np.float32)
    X[rng.rand(1500, 6) < 0.08] = np.nan
    score = np.nan_to_num(X[:, 0]) + 0.6 * np.nan_to_num(X[:, 3]) - 0.4 * np.nan_to_num(X[:, 5])
    return X, score


LOSSGUIDE = {"grow_policy": "lossguide", "max_depth": 0, "max_leaves": 12, "max_bin": 32, "eta": 0.3}
TRAIN_CASES = {
    # name -> (params, classes, mesh shards)
    "fused_k2": (dict(LOSSGUIDE, objective="binary:logistic", _rounds_per_dispatch=2), 2, None),
    "apply_program_k1": (dict(LOSSGUIDE, objective="binary:logistic"), 2, None),
    "depth_cap_4": (
        dict(LOSSGUIDE, objective="binary:logistic", max_depth=4, _rounds_per_dispatch=2), 2, None,
    ),
    "three_class_vmap": (
        dict(LOSSGUIDE, objective="multi:softprob", num_class=3, _rounds_per_dispatch=2), 3, None,
    ),
    "bagged_vmap": (
        dict(LOSSGUIDE, objective="binary:logistic", num_parallel_tree=2, subsample=0.8,
             _rounds_per_dispatch=2), 2, None,
    ),
    "data_mesh_of_4": (dict(LOSSGUIDE, objective="binary:logistic", _rounds_per_dispatch=2), 2, 4),
}
# sha256[:16] of the logged lines and of the forest, read off the parent
# commit (9034790: every loss-guided session took the pointer walk)
PARENT_TRAIN_DIGESTS = {
    "apply_program_k1": ["fe51b69a4bd12d32", "18ef1be9b9cb356d"],
    "bagged_vmap": ["955f7b532e08491e", "58549e9c43b42f24"],
    "data_mesh_of_4": ["2a16252fcecdc355", "d6e4ca527a4b25ed"],
    "depth_cap_4": ["d0b7e22be02b5157", "f053d91b6b3469f5"],
    "fused_k2": ["dad0c43406e1ba47", "18ef1be9b9cb356d"],
    "three_class_vmap": ["4287ea71ed2254b7", "e2e74680d196a4b1"],
}


def run_train_case(name):
    """(sha256[:16] of the validation metric's logged values, of the forest)."""
    import jax
    from jax.sharding import Mesh

    from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
    from sagemaker_xgboost_container_tpu.models import train

    params, classes, shards = TRAIN_CASES[name]
    X, score = _train_problem()
    y = np.digitize(score, np.quantile(score, np.arange(1, classes) / classes)).astype(np.float32)
    dtrain, dval = DataMatrix(X[:1200], labels=y[:1200]), DataMatrix(X[1200:], labels=y[1200:])
    logged = {}

    class Rec:
        def after_iteration(self, model, epoch, evals_log):
            logged.update({k: {m: list(v) for m, v in d.items()} for k, d in evals_log.items()})
            return False

    mesh = None if shards is None else Mesh(np.asarray(jax.devices()[:shards]), ("data",))
    forest = train(
        params, dtrain, num_boost_round=4, evals=[(dtrain, "train"), (dval, "validation")],
        callbacks=[Rec()], verbose_eval=False, mesh=mesh,
    )
    lines = json.dumps(
        {k: {m: [float(v).hex() for v in vs] for m, vs in d.items()} for k, d in logged.items()},
        sort_keys=True,
    )
    sha = hashlib.sha256()
    for tree in forest.trees:
        for field in ("feature", "threshold", "default_left", "left", "right", "value",
                      "base_weight", "gain", "sum_hess"):
            arr = np.ascontiguousarray(getattr(tree, field))
            sha.update(field.encode() + str(arr.dtype).encode() + str(arr.shape).encode())
            sha.update(arr.tobytes())
    assert len(logged["validation"]) >= 1 and len(forest.trees) >= 4
    return hashlib.sha256(lines.encode()).hexdigest()[:16], sha.hexdigest()[:16]


def test_every_train_case_has_its_digests():
    assert set(TRAIN_CASES) == set(PARENT_TRAIN_DIGESTS)


@pytest.mark.parametrize("name", sorted(TRAIN_CASES))
def test_loss_guided_train_logs_the_parents_lines_and_returns_its_forest(name):
    assert list(run_train_case(name)) == PARENT_TRAIN_DIGESTS[name]


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.environ.get("LOSSGUIDE_PACKAGE_ROOT", here))
    print(json.dumps({name: run_train_case(name) for name in sorted(TRAIN_CASES)}, indent=1))
