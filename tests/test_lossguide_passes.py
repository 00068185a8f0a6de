"""A loss-guided pass histograms several open leaves at once (PR 43).

``ops/lossguide.py``: a split step whose pick has no histogram in the store
runs one pass over the rows for the pick and the next-best open leaves (the
``PASS_SLOTS`` node slots of one kernel call); the steps after it commit from
the store.

* The program's tree is still the plain float64 grower's (the loop and the
  scan of ``benchmark/reference/leafwise_reference.py``, here as a judge of
  every step), at enough leaves for many passes and under every option that
  decides a split at commit time: column draws by node and by level,
  interaction sets, a depth cap, no subtraction.
* On a data mesh and a data x feature mesh the forest is the one-device one.
* The order of expansion does not depend on the width of a pass.
* A pass runs only for a pick that can split and has no entry yet, and
  deals a large leaf's rows round several of its slots.
* The counters, pinned on two seeded cases.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
from sagemaker_xgboost_container_tpu.models import train
from sagemaker_xgboost_container_tpu.ops import histogram as hist_mod
from sagemaker_xgboost_container_tpu.ops import lossguide as lossguide_mod
from sagemaker_xgboost_container_tpu.ops.lossguide import build_tree_lossguide
from sagemaker_xgboost_container_tpu.telemetry import REGISTRY

from tests import lossguide_cases
from tests.test_lossguide_rolled import _grower_inputs

MAX_LEAVES = 40
SETS = np.zeros((2, 6), bool)
SETS[0, :4] = True
SETS[1, 3:] = True


def _build(inputs, subtract=True, max_leaves=MAX_LEAVES, jit=True, **kw):
    bins, grad, hess, num_cuts, num_bins = inputs
    cap = hist_mod.SUBTRACT_CACHE_MAX_BYTES
    hist_mod.SUBTRACT_CACHE_MAX_BYTES = cap if subtract else 0
    try:
        fn = lambda b, g, h, c: build_tree_lossguide(  # noqa: E731
            b, g, h, c, max_leaves=max_leaves, num_bins=num_bins, reg_lambda=1.0, eta=0.1, **kw
        )
        tree, row_out = (jax.jit(fn) if jit else fn)(bins, grad, hess, num_cuts)
    finally:
        hist_mod.SUBTRACT_CACHE_MAX_BYTES = cap
    return {k: np.asarray(v) for k, v in tree.items()}, np.asarray(row_out)


# ------------------------------------------------ against the plain grower
def _replay_plain(tree, inputs, max_leaves, lam, eta, min_child_weight=1.0, max_depth=0,
                  rng=None, colsample_bynode=1.0, colsample_bylevel=1.0, sets=None,
                  rtol=2e-4):
    """The plain grower as a judge of ``tree`` (padded arrays, expansion
    order): ``leafwise_reference.grow``'s loop by row index lists in float64,
    every open leaf's candidate scanned from its own rows by the reference's
    ``_best_split`` over the columns the node may use (a column that is not
    allowed has no cuts), and at every step the program's pick and split held
    to the grower's: the picked leaf's best gain is the best of all open
    leaves, the split taken is (one of) its best, to ``rtol`` of the gain
    (float32 sums over subtracted histograms resolve a near-tie otherwise,
    with one leaf at a time as with eight; an exact comparison of 40-leaf
    trees fails on the parent commit too). Rows then follow the program's
    split. The draws are the program's streams: by node
    ``fold_in(rng, 7919 + t)`` for the two children of step t, by level
    ``fold_in(rng, depth)``; a set stays alive below a split on one of its
    columns. Returns the number of steps."""
    from benchmark.reference import leafwise_reference as ref

    bins, g, h, num_cuts, num_bins = inputs
    bins = np.asarray(bins, np.int64)
    g, h = np.asarray(g, np.float64), np.asarray(h, np.float64)
    d = bins.shape[1]
    depth_cap = max_depth if max_depth > 0 else max_leaves

    def level_draw(depth):
        if colsample_bylevel >= 1.0:
            return np.ones(d, bool)
        key = jax.random.fold_in(rng, min(depth, depth_cap))
        return np.asarray(jax.random.uniform(key, (d,)) < colsample_bylevel)

    def node_draws(t):
        if colsample_bynode >= 1.0:
            return np.ones((2, d), bool)
        key = jax.random.fold_in(rng, 7919 + t)
        return np.asarray(jax.random.uniform(key, (2, d)) < colsample_bynode)

    def best_gain(rows, allowed, depth):
        if max_depth > 0 and depth >= max_depth:
            return -np.inf
        return ref._best_split(
            bins, rows, g, h, np.where(allowed, num_cuts, 0), num_bins, lam, min_child_weight
        )[0]

    def gain_of(rows, f, b, default_left):
        col = bins[rows, f]
        left = (col <= b) & (col != num_bins - 1) | (col == num_bins - 1) & default_left
        G, H = g[rows].sum(), h[rows].sum()
        gl, hl = g[rows][left].sum(), h[rows][left].sum()
        assert min(hl, H - hl) >= min_child_weight * (1 - 1e-6)
        return 0.5 * (ref._score(gl, hl, lam) + ref._score(G - gl, H - hl, lam)
                      - ref._score(G, H, lam))

    def of_sets(alive):
        return np.ones(d, bool) if sets is None else sets[alive].any(axis=0)

    alive = {0: np.ones(0 if sets is None else len(sets), bool)}
    rows_of, depth = {0: np.arange(len(bins))}, {0: 0}
    allowed = {0: level_draw(0) & of_sets(alive[0])}
    best = {0: best_gain(rows_of[0], allowed[0], 0)}
    steps = 0
    for t in range(max_leaves - 1):
        a_id, b_id = 2 * t + 1, 2 * t + 2
        parents = np.flatnonzero(~tree["is_leaf"] & (tree["left"] == a_id))
        top = max(best.values())
        if len(parents) == 0:  # growth stopped: nothing was left worth a split
            assert not top > ref.MIN_SPLIT_LOSS * (1 + rtol)
            assert tree["is_leaf"][a_id:].all()
            break
        pick = int(parents[0])
        steps += 1
        assert pick in best and tree["right"][pick] == b_id
        f, b, dl = int(tree["feature"][pick]), int(tree["bin"][pick]), bool(tree["default_left"][pick])
        taken = gain_of(rows_of[pick], f, b, dl)
        slack = rtol * abs(top) + 1e-9
        assert allowed[pick][f] and b < num_cuts[f]
        assert best[pick] >= top - slack, (t, pick, best[pick], top)      # best-first
        assert taken >= best[pick] - slack, (t, pick, taken, best[pick])  # its best split
        np.testing.assert_allclose(tree["gain"][pick], taken, rtol=rtol)
        rows = rows_of.pop(pick)
        del best[pick]
        col = bins[rows, f]
        right = np.where(col == num_bins - 1, not dl, col > b)
        draws = node_draws(t)
        for i, (child, child_rows) in enumerate(((a_id, rows[~right]), (b_id, rows[right]))):
            rows_of[child], depth[child] = child_rows, depth[pick] + 1
            alive[child] = alive[pick] & (sets[:, f] if sets is not None else True)
            allowed[child] = draws[i] & level_draw(depth[child]) & of_sets(alive[child])
            best[child] = best_gain(child_rows, allowed[child], depth[child])
    for leaf, rows in rows_of.items():
        G, H = g[rows].sum(), h[rows].sum()
        assert tree["is_leaf"][leaf]
        np.testing.assert_allclose(tree["leaf_value"][leaf], -eta * G / (H + lam), rtol=0, atol=1e-5)
        np.testing.assert_allclose(tree["sum_hess"][leaf], H, rtol=1e-5)
    if max_depth > 0:
        assert max(depth.values()) <= max_depth
    return steps


GROWER_CASES = {
    "plain": {},
    "min_child_weight": {"min_child_weight": 9.0},
    "max_depth_5": {"max_depth": 5},
    "bynode": {"colsample_bynode": 0.6},
    "bylevel": {"colsample_bylevel": 0.7},
    "bynode_bylevel_depth_6": {"colsample_bynode": 0.7, "colsample_bylevel": 0.8, "max_depth": 6},
    "sets": {"sets": SETS},
    "sets_bynode": {"sets": SETS, "colsample_bynode": 0.7},
}


@pytest.mark.parametrize("subtract", [True, False], ids=["subtraction", "both_children"])
@pytest.mark.parametrize("case", sorted(GROWER_CASES))
def test_program_tree_is_the_plain_growers_over_many_passes(case, subtract):
    """What a pass prepares before its leaves are committed (their rows' way,
    their children's sums) gives the picks, splits and ids of a grower that
    looks at one leaf at a time, under every option that decides a split at
    commit time."""
    extra = dict(GROWER_CASES[case])
    inputs = _grower_inputs(7)
    rng = jax.random.PRNGKey(21)
    sets = extra.pop("sets", None)
    tree, _row_out = _build(
        inputs, subtract=subtract, rng=rng,
        interaction_sets=None if sets is None else jnp.asarray(sets), **extra
    )
    steps = _replay_plain(tree, inputs, MAX_LEAVES, 1.0, 0.1, rng=rng, sets=sets, **extra)
    passes, filled, used = tree["hist_passes"]
    kids = 1 if subtract else 2
    assert used >= kids * steps and steps > 20  # a large leaf is dealt round several slots
    assert 1 < passes < steps  # many passes, and far fewer than steps
    assert used <= filled <= lossguide_mod.PASS_SLOTS * passes


def _as_padded(plain):
    internal = plain["left"] >= 0
    ids = np.arange(len(internal))
    return {
        "is_leaf": ~internal, "left": np.where(internal, plain["left"], ids),
        "right": np.where(internal, plain["right"], ids), "feature": plain["feature"],
        "bin": plain["bin"], "default_left": plain["default_left"], "gain": plain["gain"],
        "leaf_value": plain["value"], "sum_hess": plain["sum_hess"],
    }


def test_the_judge_passes_the_benchmarks_grower_and_no_other_tree():
    from benchmark.reference import leafwise_reference

    inputs = _grower_inputs(3)
    bins, grad, hess, num_cuts, _num_bins = inputs
    kw = dict(min_child_weight=8.0, max_depth=4)
    plain = leafwise_reference.grow(bins, num_cuts, grad, hess, 12, lam=1.0, eta=0.1, **kw)
    tree = _as_padded(plain)
    assert _replay_plain(tree, inputs, 12, 1.0, 0.1, **kw) == int((plain["left"] >= 0).sum())
    # another cut at the root; the two last steps taken in the other order
    moved = dict(tree, bin=tree["bin"].copy())
    moved["bin"][0] += 2
    with pytest.raises(AssertionError):
        _replay_plain(moved, inputs, 12, 1.0, 0.1, **kw)
    # the two last steps taken in the other order: their children trade ids
    last = int(plain["left"].max())  # the last step made ``last`` and ``last + 1``
    a, b = (int(np.flatnonzero(plain["left"] == i)[0]) for i in (last - 2, last))
    assert b < last - 2 and plain["gain"][a] > plain["gain"][b] * 1.01
    perm = np.arange(len(plain["left"]))
    perm[last - 2 : last + 2] = [last, last + 1, last - 2, last - 1]
    swapped = {k: v[perm] for k, v in tree.items()}
    for side in ("left", "right"):
        swapped[side] = np.where(swapped["is_leaf"], np.arange(len(perm)), perm[swapped[side]])
    assert swapped["left"][a] == last and swapped["left"][b] == last - 2
    with pytest.raises(AssertionError, match=str(b)):
        _replay_plain(swapped, inputs, 12, 1.0, 0.1, **kw)


# ------------------------------------------------------------------ meshes
@pytest.mark.parametrize("mesh_shape", [(4,), (2, 2)], ids=["data4", "data2xfeature2"])
@pytest.mark.parametrize("variant", ["plain", "bynode", "sets"])
def test_mesh_forest_is_the_one_device_forest(mesh_shape, variant):
    """Every shard holds the same candidate store, so every shard runs the
    same passes; under feature sharding the owners' go-left decisions for a
    pass's leaves go through one ``psum``."""
    # 24 leaves: at 31 the sets case meets a near-tie of two cuts that a
    # shard's partial sums resolve otherwise (on the parent commit too)
    kw = dict(lossguide_cases.cases()["l31.sub." + variant][2], max_leaves=24)
    one, out_one = lossguide_cases.run_case(None, True, kw)
    mesh, out_mesh = lossguide_cases.run_case(mesh_shape, True, kw)
    assert (~one["is_leaf"]).sum() == 23
    # (not ``default_left``: where a node's rows all have a value both ways
    # gain the same, and the last bit of a sum decides)
    for field in ("feature", "bin", "is_leaf", "left", "right"):
        assert np.array_equal(one[field], mesh[field]), field
    np.testing.assert_allclose(one["leaf_value"], mesh["leaf_value"], rtol=2e-5)
    np.testing.assert_allclose(out_one, out_mesh, rtol=2e-5)
    # the same passes: the pick is never read off a shard's own sums alone
    assert np.array_equal(one["hist_passes"], mesh["hist_passes"])


# ------------------------------------------- the width of a pass is no input
@pytest.mark.parametrize("subtract", [True, False], ids=["subtraction", "both_children"])
@pytest.mark.parametrize("slots", [2, 4])
def test_forest_does_not_depend_on_the_width_of_a_pass(monkeypatch, slots, subtract):
    inputs = _grower_inputs(5)
    kw = dict(colsample_bynode=0.7, rng=jax.random.PRNGKey(3), min_child_weight=4.0)
    wide, out_wide = _build(inputs, subtract=subtract, **kw)
    monkeypatch.setattr(lossguide_mod, "PASS_SLOTS", slots)
    narrow, out_narrow = _build(inputs, subtract=subtract, **kw)
    # the same splits in the same order; the sums' last bits follow the slots
    # a leaf was dealt round
    for field in ("feature", "bin", "is_leaf", "left", "right"):
        assert np.array_equal(wide[field], narrow[field]), field
    np.testing.assert_allclose(wide["leaf_value"], narrow["leaf_value"], rtol=2e-5, atol=1e-7)
    np.testing.assert_allclose(out_wide, out_narrow, rtol=2e-5, atol=1e-7)
    assert narrow["hist_passes"][0] >= wide["hist_passes"][0]
    if slots == 2 and not subtract:  # a leaf a pass: every step runs one, as before PR 43
        assert narrow["hist_passes"][0] == (~narrow["is_leaf"]).sum()


def test_pass_slots_are_the_widest_call_on_one_operand_tile():
    assert lossguide_mod.PASS_SLOTS == 8
    assert hist_mod._operand_rows(8) == hist_mod._operand_rows(1)
    assert hist_mod._operand_rows(16) > hist_mod._operand_rows(1)
    prec, lanes = hist_mod.HIST_PRECISIONS[0], hist_mod._bin_lanes(257)
    assert hist_mod._bin_fold(hist_mod._operand_rows(8), lanes, prec) == hist_mod._bin_fold(
        hist_mod._operand_rows(1), lanes, prec
    )


def test_the_gate_counts_the_store_beside_the_cache():
    cap = hist_mod.SUBTRACT_CACHE_MAX_BYTES
    d, bins, leaves = 28, 257, 255
    one = (2 * leaves - 1) * d * bins * 4  # one [max_nodes, d, B] f32 array
    assert lossguide_mod._subtraction_enabled(leaves, d, bins) == (4 * one <= cap)
    wide = cap // (4 * (2 * leaves - 1) * bins * 4)  # the widest that still subtracts
    assert lossguide_mod._subtraction_enabled(leaves, wide, bins)
    assert not lossguide_mod._subtraction_enabled(leaves, wide + 1, bins)


# ------------------------------------------------- when a pass runs at all
def _eager_build_counting_calls(monkeypatch, **kw):
    """The build run eagerly (``jax.disable_jit``: the ``cond`` takes one
    branch in Python), every ``level_histogram`` call noted with the node
    slots it built and the rows it labelled."""
    calls = []
    real = lossguide_mod.level_histogram

    def counted(bins, grad, hess, node_local, num_nodes, *args, **kwargs):
        calls.append((num_nodes, np.asarray(node_local)))
        return real(bins, grad, hess, node_local, num_nodes, *args, **kwargs)

    monkeypatch.setattr(lossguide_mod, "level_histogram", counted)
    with jax.disable_jit():
        tree, _row_out = _build(_grower_inputs(3, n=300), jit=False, **kw)
    return tree, calls


@pytest.mark.parametrize("subtract", [True, False], ids=["subtraction", "both_children"])
def test_a_pass_runs_only_for_a_pick_without_an_entry(monkeypatch, subtract):
    tree, calls = _eager_build_counting_calls(monkeypatch, max_leaves=14, subtract=subtract)
    passes, filled, used = (int(v) for v in tree["hist_passes"])
    steps = int((~tree["is_leaf"]).sum())
    assert steps == 13
    # the kernel ran once for the root and once a pass, never once a step
    assert [c[0] for c in calls] == [1] + [8] * passes and passes < steps
    # and no pass went by without filling a slot some row sits in
    assert all((node_local >= 0).any() for _w, node_local in calls)
    assert filled == sum(len(np.unique(nl[nl >= 0])) for _w, nl in calls[1:])


def test_a_large_leaf_is_dealt_round_as_many_slots_as_it_holds_shares(monkeypatch):
    """The kernel sums a W = 8 call in an eighth of a W = 1 call's row chunks
    on the premise that a level's nodes share the rows; a pass keeps it: the
    root (all of the hessian sum) takes all eight slots, its left child's rows
    dealt round them in row order, and a later pass of small leaves a slot
    each."""
    tree, calls = _eager_build_counting_calls(monkeypatch, max_leaves=14)
    bins = _grower_inputs(3, n=300)[0]
    f, b, dl = int(tree["feature"][0]), int(tree["bin"][0]), bool(tree["default_left"][0])
    col = bins[:, f].astype(np.int64)
    goes_left = np.where(col == 12, dl, col <= b)
    _width, first_pass = calls[1]
    assert np.array_equal(first_pass >= 0, goes_left)
    assert np.array_equal(first_pass[goes_left], np.arange(300)[goes_left] & 7)
    # the sums of the eight slots, added up, are the left child's
    hess = _grower_inputs(3, n=300)[2].astype(np.float64)
    np.testing.assert_allclose(tree["sum_hess"][1], hess[goes_left].sum(), rtol=1e-6)
    # leaves under an eighth of the weight share a later pass, a slot each
    _width, last_pass = calls[-1]
    in_slot = [np.flatnonzero(last_pass == s) for s in range(8)]
    assert sum(len(rows) > 0 for rows in in_slot) >= 4
    assert all(hess[rows].sum() <= hess.sum() / 8 for rows in in_slot)


def test_a_step_that_cannot_split_runs_no_pass(monkeypatch):
    # no node of 300 rows has two children of 200 hessian-weight: the root's
    # candidate is -inf and all 13 steps are idle
    tree, calls = _eager_build_counting_calls(
        monkeypatch, max_leaves=14, min_child_weight=200.0
    )
    assert tree["is_leaf"].all()
    assert [c[0] for c in calls] == [1]
    assert tree["hist_passes"].tolist() == [0, 0, 0]
    # growth that stops early: passes only while a step can split
    tree, calls = _eager_build_counting_calls(
        monkeypatch, max_leaves=14, min_child_weight=40.0
    )
    steps = int((~tree["is_leaf"]).sum())
    assert 0 < steps < 13
    assert len(calls) - 1 == tree["hist_passes"][0] <= steps


# ---------------------------------------------------------------- counters
# [passes, slots filled, slots used] of seeded builds, read off this program
PINNED_COUNTS = {"l31.sub.plain": [12, 73, 58], "l31.nosub.plain": [13, 104, 76]}


@pytest.mark.parametrize("name", sorted(PINNED_COUNTS))
def test_pass_counters_of_seeded_builds(name):
    tree, _row_out = lossguide_cases.run_case(*lossguide_cases.cases()[name])
    assert tree["hist_passes"].tolist() == PINNED_COUNTS[name]


def _counter(name):
    for metric, _kind, _help, family in REGISTRY.collect():
        if metric == name:
            return sum(s.value for s in family)
    return 0.0


@pytest.mark.parametrize(
    "params, trees",
    [
        ({"objective": "binary:logistic", "grow_policy": "lossguide", "max_depth": 0,
          "max_leaves": 24}, 1),
        ({"objective": "multi:softmax", "num_class": 3, "grow_policy": "lossguide",
          "max_depth": 0, "max_leaves": 12}, 3),
        ({"objective": "binary:logistic", "grow_policy": "lossguide", "max_depth": 0,
          "max_leaves": 10, "num_parallel_tree": 2, "subsample": 0.8}, 2),
        ({"objective": "binary:logistic", "max_depth": 3}, 0),
    ],
    ids=["loss_guided", "loss_guided_three_class", "loss_guided_bagged", "depth_wise"],
)
def test_pass_counters_reach_the_registry_with_the_dispatch(params, trees):
    """The counters ride the dispatch's one packed array (an eleventh row of
    a loss-guided tree's) and are counted where the trees are committed."""
    rng = np.random.RandomState(4)
    X = rng.randn(600, 5).astype(np.float32)
    classes = int(params.get("num_class", 2))
    y = (np.abs(X[:, 0] * 3 + X[:, 1]).astype(int) % classes).astype(np.float32)
    names = ("tree_hist_passes_total", "tree_hist_pass_slots_total",
             "tree_hist_pass_slots_used_total")
    before = [_counter(n) for n in names]
    forest = train(
        dict(params, max_bin=16, _rounds_per_dispatch=2), DataMatrix(X, labels=y),
        num_boost_round=4,
    )
    passes, filled, used = (_counter(n) - b for n, b in zip(names, before))
    splits = sum(int((t.left >= 0).sum()) for t in forest.trees)
    last_round = _counter("round_hist_passes")
    if not trees:
        assert (passes, filled, used, last_round) == (0, 0, 0, 0)
        return
    assert used >= splits  # every committed split took its children from the store
    assert len(forest.trees) == 4 * trees <= passes
    assert used <= filled <= lossguide_mod.PASS_SLOTS * passes
    if trees == 1:
        assert passes < splits  # under the class vmap a pass runs every step
    assert 0 < last_round <= passes
