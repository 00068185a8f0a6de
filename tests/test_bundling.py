"""The bundled layout (PR 46): a sparse ``DataMatrix`` stays CSR, mutually
exclusive columns share a dense bin column, and the forest is the densified
path's.

At small sizes on the CPU: the sparse matrix's own operations, the plan's
invariants, the tie to the whole (the sparse and the densified path grow the
same forest for a binary, a multi-class and a ranking objective), the system
against the sparse float64 reference, the shape rule and what it leaves on
today's path, the spans, gauges and stages, and that no array of rows x
columns is ever made.
"""

import os
import sys

import numpy as np
import pytest
import scipy.sparse as sp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from sagemaker_xgboost_container_tpu.data import bundling  # noqa: E402
from sagemaker_xgboost_container_tpu.data.binning import (  # noqa: E402
    apply_cut_points,
    compute_cut_points,
)
from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix  # noqa: E402
from tests.sparse_cases import densified, one_hot_csr  # noqa: E402

MAX_BIN = 256


# ------------------------------------------------------- the sparse matrix
def test_sparse_matrix_is_not_densified_until_asked():
    x, y = one_hot_csr(200, 0)
    dm = DataMatrix(x, labels=y)
    assert dm.is_sparse and dm._dense is None
    assert (dm.num_row, dm.num_col) == x.shape
    block = dm.float_block(10, 50)
    assert dm._dense is None
    np.testing.assert_array_equal(block, densified(x)[10:50])
    np.testing.assert_array_equal(dm.features, densified(x))  # the bridge for readers of all floats


@pytest.mark.parametrize("operation", ["slice", "pad_features", "concat", "concat_narrower"])
def test_sparse_matrix_operations_keep_csr_and_the_densified_meaning(operation):
    x, y = one_hot_csr(120, 1)
    w = np.linspace(0.5, 1.5, 120).astype(np.float32)
    sparse, dense = DataMatrix(x, labels=y, weights=w), DataMatrix(densified(x), labels=y, weights=w)
    if operation == "slice":
        rows = np.array([5, 3, 77, 3])
        got, want = sparse.slice(rows), dense.slice(rows)
    elif operation == "pad_features":
        got, want = sparse.pad_features(x.shape[1] + 7), dense.pad_features(x.shape[1] + 7)
    else:
        other = one_hot_csr(40, 2)[0]
        if operation == "concat_narrower":
            other = other[:, : x.shape[1] - 11]
        got = sparse.concat(DataMatrix(other))
        want = dense.concat(DataMatrix(densified(other)))
    assert got.is_sparse and got._dense is None
    np.testing.assert_array_equal(got.features, want.features)
    for field in ("labels", "weights"):
        a, b = getattr(got, field), getattr(want, field)
        assert (a is None and b is None) or np.array_equal(a, b)


def test_a_dense_matrix_is_what_it_was():
    dm = DataMatrix(np.arange(12, dtype=np.float64).reshape(4, 3))
    assert not dm.is_sparse and dm.features.dtype == np.float32
    np.testing.assert_array_equal(dm.float_block(1, 3), dm.features[1:3])


# ---------------------------------------------------------------- the plan
def _bundled(x, others=(), weights=None, max_bin=MAX_BIN):
    return bundling.bundle_matrices(
        [x, *others], weights, max_bin,
        lambda block: compute_cut_points(block, weights, max_bin),
        lambda block, cuts, name: apply_cut_points(block, cuts, max_bin, name=name),
        ["train"] + ["eval{}".format(i) for i in range(len(others))],
    )


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_plan_invariants(seed):
    x, _y = one_hot_csr(3000, seed)
    xv, _yv = one_hot_csr(700, 100 + seed)
    out = _bundled(x, [xv])
    plan = out.plan
    tables = plan.tables
    assert out.conflict_rows == 0 and out.cells_present == x.nnz
    # every column with a training value is in exactly one bundle, the rest in none
    filled = np.diff(x.tocsc().indptr) > 0
    members = [f for cols in plan.members for f in cols]
    assert sorted(members) == list(np.flatnonzero(filled))
    assert np.array_equal(plan.bundle_of >= 0, filled)
    # at most max_bin positions a bundle, and fewer bin columns than columns
    used = [sum(int(plan.bins_of[f]) for f in cols) for cols in plan.members]
    assert max(used) <= MAX_BIN and plan.num_bundles < x.shape[1] // 4
    assert plan.bins_used == sum(used)
    # the cuts are the densified path's, column by column
    for f, want in enumerate(compute_cut_points(densified(x), None, MAX_BIN)):
        if filled[f]:
            np.testing.assert_array_equal(plan.cut_points[f], want)
    # no row of either matrix holds two members of a bundle, and every present
    # cell sits at its member's offset + its own bin
    for matrix, bins in zip((x, xv), out.bins):
        assert bins.shape == (matrix.shape[0], plan.num_bundles)
        coo = matrix.tocoo()
        keep = filled[coo.col]
        rows, cols, vals = coo.row[keep], coo.col[keep], coo.data[keep]
        cell = rows.astype(np.int64) * plan.num_bundles + plan.bundle_of[cols]
        assert len(np.unique(cell)) == len(cell)
        local = np.array(
            [np.searchsorted(plan.cut_points[f], v, side="right") for f, v in zip(cols, vals)]
        )
        np.testing.assert_array_equal(
            np.asarray(bins)[rows, plan.bundle_of[cols]], plan.offset_of[cols] + local
        )
        assert int((np.asarray(bins) != MAX_BIN).sum()) == len(cell)
    # position -> (column, own bin) and back
    for f in members:
        for local in range(int(plan.bins_of[f])):
            b, p = plan.position_of(f, local)
            assert tables.column[b, p] == f and p - tables.lo[b, p] == local
            assert tables.hi[b, p] - tables.lo[b, p] == plan.bins_of[f]
            assert tables.legal[b, p] == (local < len(plan.cut_points[f]))
    # the same rows give the same plan
    again = _bundled(x, [xv]).plan
    assert again.members == plan.members and np.array_equal(again.bins_of, plan.bins_of)


def test_a_one_hot_column_takes_one_position_and_a_held_out_value_widens_it():
    x = sp.csr_matrix(
        np.array([[1, 0, 0], [1, 0, 0], [0, 1, 0], [0, 1, 0], [0, 0, 3.0], [0, 0, 5.0]], np.float32)
    )
    # the evaluation rows hold 7.0 in column 0, above its one cut (2.0)
    xv = sp.csr_matrix(np.array([[7.0, 0, 0], [0, 1, 0]], np.float32))
    alone, both = _bundled(x).plan, _bundled(x, [xv]).plan
    assert list(alone.bins_of) == [1, 1, 2] and list(both.bins_of) == [2, 1, 2]
    assert alone.num_bundles == 1 and both.num_bundles == 1  # no row holds two of them


def test_a_popular_one_hot_column_shares_its_bundle_with_its_group():
    """Filled in most rows, but one value: no dense column of its own (which
    would cost the kernel a bin column for two positions)."""
    rng = np.random.default_rng(4)
    code = (rng.random(400) < 0.3).astype(int)          # value 0 in 70 % of the rows
    dense = np.zeros((400, 3), np.float32)
    dense[np.arange(400), code] = 1.0
    dense[:, 2] = rng.normal(size=400)                   # a numeric column, every row
    plan = _bundled(sp.csr_matrix(dense)).plan
    assert plan.dense_columns == [2] and plan.num_bundles == 2
    assert sorted(plan.members[1]) == [0, 1] and list(plan.bins_of) == [1, 1, 255]


def test_columns_that_meet_only_in_an_evaluation_row_do_not_share_a_bundle():
    x = sp.csr_matrix(np.array([[1, 0], [0, 1], [0, 0], [0, 0], [0, 0]], np.float32))
    xv = sp.csr_matrix(np.array([[1, 1]], np.float32))
    assert _bundled(x).plan.num_bundles == 1
    met = _bundled(x, [xv])
    assert met.plan.num_bundles == 2 and met.conflict_rows == 0


def test_the_conflict_count_is_taken_from_the_finished_matrix(monkeypatch):
    """A plan that shares a bundle between columns one row holds loses a
    cell; the count finds the row."""
    x, _y = one_hot_csr(500, 3)
    monkeypatch.setattr(bundling._OpenBundle, "fits", lambda self, *_a: True)
    out = _bundled(x)
    assert out.conflict_rows > 0


# ------------------------------------------------------ the tie to the whole
def _same_forest(sparse, dense, margin_tolerance, x):
    assert len(sparse.trees) == len(dense.trees)
    for a, b in zip(sparse.trees, dense.trees):
        np.testing.assert_array_equal(a.feature, b.feature)
        np.testing.assert_array_equal(a.threshold, b.threshold)
        np.testing.assert_array_equal(a.default_left, b.default_left)
        np.testing.assert_allclose(a.value, b.value, rtol=0, atol=1e-6)
    np.testing.assert_allclose(
        sparse.predict_margin(x), dense.predict_margin(x), rtol=0, atol=margin_tolerance
    )


CASES = {
    "binary": dict(objective="binary:logistic", eval_metric="logloss", min_child_weight=20),
    "binary_colsample": dict(
        objective="binary:logistic", eval_metric="logloss", min_child_weight=20,
        colsample_bytree=0.5,
    ),
    "binary_one_round_a_dispatch": dict(
        objective="binary:logistic", eval_metric="logloss", min_child_weight=20,
        _rounds_per_dispatch=1,
    ),
    "softmax": dict(
        objective="multi:softmax", num_class=3, eval_metric="mlogloss", min_child_weight=20
    ),
    "rank": dict(objective="rank:ndcg", eval_metric="ndcg@5", min_child_weight=5),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_sparse_and_densified_paths_grow_the_same_forest(case):
    """Column, threshold and default direction equal at every node; leaf
    values and margins within 1e-6 (float32: the bundled scan adds a member's
    left sums by a masked dot where the dense scan takes a running sum)."""
    from sagemaker_xgboost_container_tpu import models

    x, y = one_hot_csr(6000, 11)
    xv, yv = one_hot_csr(1500, 12)
    extra = {}
    if case == "softmax":
        y = (y + (x[:, 1].toarray()[:, 0] > 0)).astype(np.float32)
        yv = (yv + (xv[:, 1].toarray()[:, 0] > 0)).astype(np.float32)
    if case == "rank":
        y = np.clip(y * 2 + (x[:, 0].toarray()[:, 0] > 0), 0, 4).astype(np.float32)
        yv = np.clip(yv * 2 + (xv[:, 0].toarray()[:, 0] > 0), 0, 4).astype(np.float32)
        extra = {"groups": np.full(300, 20)}
        extra_v = {"groups": np.full(75, 20)}
    else:
        extra_v = {}
    params = dict({"max_depth": 4, "eta": 0.3, "max_bin": MAX_BIN, "_rounds_per_dispatch": 4},
                  **CASES[case])
    forests = []
    for form in (lambda m: m, densified):
        train = DataMatrix(form(x), labels=y, **extra)
        valid = DataMatrix(form(xv), labels=yv, **extra_v)
        forests.append(
            models.train(params, train, num_boost_round=4, evals=[(valid, "validation")],
                         verbose_eval=False)
        )
        if form is not densified:
            assert train._dense is None and valid._dense is None  # never densified
    _same_forest(forests[0], forests[1], 1e-6, densified(xv))


def test_training_goes_on_from_a_model_over_sparse_rows():
    """The warm start's margins come from row blocks of floats."""
    from sagemaker_xgboost_container_tpu import models

    x, y = one_hot_csr(4000, 21)
    params = dict(CASES["binary"], max_depth=3, eta=0.3, _rounds_per_dispatch=2)
    first = models.train(params, DataMatrix(densified(x), labels=y), num_boost_round=2,
                         verbose_eval=False)
    import copy

    forests = [
        models.train(params, DataMatrix(form(x), labels=y), num_boost_round=2,
                     xgb_model=copy.deepcopy(first), verbose_eval=False)
        for form in (lambda m: m, densified)
    ]
    assert forests[0].num_boosted_rounds == 4
    _same_forest(forests[0], forests[1], 1e-6, densified(x))


# ------------------------------------------------- the session and its rule
def _session(x, y, evals=(), mesh=None, bundles=True, **params):
    from sagemaker_xgboost_container_tpu.models.booster import (
        Forest,
        TrainConfig,
        _TrainingSession,
    )

    cfg = TrainConfig(dict({"objective": "binary:logistic", "max_depth": 3}, **params))
    forest = Forest(objective_name=cfg.objective, base_score=cfg.base_score, num_feature=x.shape[1])
    return _TrainingSession(
        cfg, DataMatrix(x, labels=y), [(DataMatrix(m, labels=l), n) for m, l, n in evals],
        forest, mesh=mesh, metric_names=["logloss"], bundles=bundles,
    )


def test_no_array_of_rows_by_columns_exists_in_a_bundled_session():
    x, y = one_hot_csr(3000, 5)
    xv, yv = one_hot_csr(800, 6)
    session = _session(x, y, [(xv, yv, "validation")])
    plan = session.bundle
    assert plan is not None and plan.num_bundles < 40 < x.shape[1]
    assert session.bins.shape == (3000, plan.num_bundles)
    assert session.eval_bins[0].shape == (800, plan.num_bundles)
    assert session._train_floats is None and not session._eval_floats
    assert session._dtrain._dense is None and session.eval_sets[0][1]._dense is None
    assert len(session.cuts) == x.shape[1]
    from sagemaker_xgboost_container_tpu.toolkit import exceptions as exc

    with pytest.raises(exc.AlgorithmError):
        session.train_binned.bins  # bundle positions are no per-column bins


@pytest.mark.parametrize(
    "why, params",
    [
        ("lossguide", {"grow_policy": "lossguide", "max_leaves": 8, "max_depth": 0}),
        ("approx", {"tree_method": "approx"}),
        ("exact", {"tree_method": "exact"}),
        ("monotone", {"monotone_constraints": [1, 0]}),
        ("interaction", {"interaction_constraints": [[0, 1], [2, 3]]}),
        ("colsample_bylevel", {"colsample_bylevel": 0.5}),
        ("colsample_bynode", {"colsample_bynode": 0.5}),
    ],
)
def test_what_the_bundled_scan_does_not_cover_keeps_the_densified_path(why, params):
    x, y = one_hot_csr(600, 7, groups=(3, 5, 9, 40))
    session = _session(x, y, **params)
    assert session.bundle is None and session.bins.shape[1] == x.shape[1]


def test_the_shape_rule_and_the_callers_that_do_not_ask():
    x, y = one_hot_csr(600, 8, groups=(3, 5, 9, 40))
    assert _session(x, y).bundle is not None
    assert _session(x, y, bundles=False).bundle is None          # dart's session
    assert _session(densified(x), y).bundle is None               # dense input
    full = sp.csr_matrix(np.random.default_rng(0).normal(size=(300, 6)).astype(np.float32))
    assert _session(full, y[:300]).bundle is None                 # over a quarter full
    assert not bundling.takes_bundled_layout([DataMatrix(x), DataMatrix(densified(x))])


def test_a_data_mesh_keeps_the_densified_path():
    import jax
    from jax.sharding import Mesh

    if len(jax.devices()) < 4:
        pytest.skip("needs four virtual devices")
    x, y = one_hot_csr(600, 9, groups=(3, 5, 9, 40))
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    session = _session(x, y, mesh=mesh)
    assert session.bundle is None and session.bins.shape[1] == x.shape[1]


# ----------------------------------------------- spans, gauges and stages
def test_spans_and_gauges_of_a_bundled_session():
    from sagemaker_xgboost_container_tpu.telemetry import REGISTRY

    def read(metric, phase=None):
        for name, _kind, _help, family in REGISTRY.collect():
            if name == metric:
                for s in family:
                    if phase is None or (s.labels or {}).get("phase") == phase:
                        return s
        return None

    before = {
        phase: getattr(read("training_phase_seconds", phase), "count", 0)
        for phase in ("setup.bundle_plan", "setup.sketch", "setup.bin_apply")
    }
    x, y = one_hot_csr(3000, 13)
    xv, yv = one_hot_csr(500, 14)
    session = _session(x, y, [(xv, yv, "validation")])
    plan = session.bundle
    # the CSC forms, the dense columns' float block and the conflict search;
    # the dense block's sketch and the sparse columns' cuts; a dense and a
    # scatter part for each matrix
    assert read("training_phase_seconds", "setup.bundle_plan").count - before["setup.bundle_plan"] == 3
    assert read("training_phase_seconds", "setup.sketch").count - before["setup.sketch"] == 2
    assert read("training_phase_seconds", "setup.bin_apply").count - before["setup.bin_apply"] == 4
    want = {
        "train_cells_present": x.nnz,
        "train_cells_total": x.shape[0] * x.shape[1],
        "train_cells_missing": x.shape[0] * x.shape[1] - x.nnz,
        "train_columns_total": x.shape[1],
        "train_bundle_columns": plan.num_bundles,
        "bundle_bins_used": plan.bins_used,
        "bundle_bin_slots": plan.num_bundles * MAX_BIN,
        "bundle_conflict_rows": 0,
    }
    for name, value in want.items():
        assert read(name).value == value, name
    assert 0 < plan.bins_used <= plan.num_bundles * MAX_BIN


def test_the_bundled_scan_and_the_range_test_read_under_their_stages():
    """The scan's masked dots under ``split_scan``, the range word's read and
    the range test under ``route_rows``; in the walk under the caller's
    ``eval_apply``."""
    import jax
    import jax.numpy as jnp

    from sagemaker_xgboost_container_tpu.ops.histogram import resolve_hist_knobs
    from sagemaker_xgboost_container_tpu.ops.tree_build import build_tree

    x, _y = one_hot_csr(400, 15, groups=(3, 5, 9))
    out = _bundled(x)
    tables = out.plan.tables
    knobs = resolve_hist_knobs()._replace(backend="cpu")
    rows = jax.ShapeDtypeStruct((400,), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda bins, g, h, cuts: build_tree(
            bins, g, h, cuts, max_depth=2, num_bins=MAX_BIN + 1, knobs=knobs, bundle=tables
        )
    )(jnp.asarray(out.bins[0]), rows, rows, jnp.zeros(x.shape[1], jnp.int32))

    def stacks(primitive, eqns):
        for e in eqns:
            if e.primitive.name == primitive:
                yield str(e.source_info.name_stack)
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from stacks(primitive, sub.eqns)

    dots = list(stacks("dot_general", jaxpr.jaxpr.eqns))
    assert dots and all("split_scan" in s for s in dots), dots
    shifts = list(stacks("shift_right_arithmetic", jaxpr.jaxpr.eqns))
    assert shifts and all("route_rows" in s for s in shifts), shifts


def test_a_dense_build_traces_nothing_of_the_bundled_branches():
    import jax
    import jax.numpy as jnp

    from sagemaker_xgboost_container_tpu.ops.histogram import resolve_hist_knobs
    from sagemaker_xgboost_container_tpu.ops.tree_build import build_tree

    knobs = resolve_hist_knobs()._replace(backend="cpu")
    rows = jax.ShapeDtypeStruct((64,), jnp.float32)
    text = str(
        jax.make_jaxpr(
            lambda bins, g, h, cuts: build_tree(
                bins, g, h, cuts, max_depth=2, num_bins=9, knobs=knobs
            )
        )(jax.ShapeDtypeStruct((64, 3), jnp.uint8), rows, rows, jnp.full(3, 7, jnp.int32))
    )
    assert "dot_general" not in text


# ------------------------------------- the system against the sparse reference
def test_a_bundled_forest_against_the_sparse_float64_reference():
    from benchmark.kinds.train_window import plain_rounds
    from benchmark.reference import gbt_reference, sparse_gbt_reference
    from sagemaker_xgboost_container_tpu import models

    x, y = one_hot_csr(8000, 31)
    params = dict(CASES["binary"], max_depth=4, eta=0.3, _rounds_per_dispatch=3)
    log = {}

    class Keep:
        def after_iteration(self, forest, rnd, evals_log):
            log.update(evals_log)
            return False

    dm = DataMatrix(x, labels=y)
    forest = models.train(params, dm, num_boost_round=3, evals=[(dm, "train")],
                          callbacks=[Keep()], verbose_eval=False)
    rounds = plain_rounds(forest, 3)
    args = ([0, 2], y, "binary:logistic", 0.5, 0.3, 1.0, 4, log["train"]["logloss"])
    got = sparse_gbt_reference.check_rounds(rounds, args[0], x, *args[1:])
    assert got["direct_hess_err"] < 2e-4 and got["gain_err_median"] < 1e-5
    assert got["leaf_value_err"] < 1e-4 and got["loss_abs"] < 8e-5
    # the sparse reference reads what the dense one reads of the same rows
    want = gbt_reference.check_rounds(rounds, args[0], densified(x), *args[1:])
    for name in want:
        assert got[name] == pytest.approx(want[name], rel=1e-9, abs=1e-15), name
    trees = [t for rnd in rounds for _c, t in rnd]
    assert sparse_gbt_reference.splits_off_own_cuts(trees, x) == 0
    # a split on a one-hot column whose threshold is no cut of it, one that
    # names a neighbour's midpoint, one off the matrix: each is counted
    bad = dict(trees[0])
    internal = np.flatnonzero(bad["left"] >= 0)
    bad["threshold"] = bad["threshold"].copy()
    bad["threshold"][internal[0]] += np.float32(0.125)
    assert sparse_gbt_reference.splits_off_own_cuts([bad], x) == 1
    off = dict(trees[0], feature=trees[0]["feature"].copy())
    off["feature"][internal[0]] = x.shape[1]
    assert sparse_gbt_reference.splits_off_own_cuts([off], x) == 1


# ------------------------------------------------------ the job's entry point
def test_training_entry_on_a_libsvm_channel_reaches_the_bundled_layout(tmp_path):
    """A libsvm channel is read as CSR and handed over as it is: the job's
    session bundles it, says so in its log, and writes a model that speaks
    original column ids."""
    import json
    import subprocess

    from sagemaker_xgboost_container_tpu.models import Forest

    x, y = one_hot_csr(1500, 41)
    channel = tmp_path / "train"
    channel.mkdir()
    with open(channel / "rows.libsvm", "w") as f:
        for i in range(x.shape[0]):
            row = x.getrow(i)
            cells = " ".join("{}:{:.9g}".format(c, v) for c, v in zip(row.indices, row.data))
            f.write("{:g} {}\n".format(y[i], cells))
    conf = tmp_path / "config"
    conf.mkdir()
    model_dir, output_dir = tmp_path / "model", tmp_path / "output"
    model_dir.mkdir(), output_dir.mkdir()
    (conf / "hyperparameters.json").write_text(json.dumps({
        "num_round": "4", "objective": "binary:logistic", "max_depth": "3",
        "eval_metric": "logloss", "min_child_weight": "5",
    }))
    (conf / "inputdataconfig.json").write_text(json.dumps({
        "train": {"ContentType": "libsvm", "TrainingInputMode": "File",
                  "S3DistributionType": "FullyReplicated"},
    }))
    env = dict(
        os.environ,
        SM_INPUT_TRAINING_CONFIG_FILE=str(conf / "hyperparameters.json"),
        SM_INPUT_DATA_CONFIG_FILE=str(conf / "inputdataconfig.json"),
        SM_CHECKPOINT_CONFIG_FILE=str(conf / "checkpointconfig.json"),
        SM_CHANNEL_TRAIN=str(channel), SM_MODEL_DIR=str(model_dir),
        SM_OUTPUT_DATA_DIR=str(output_dir), SM_HOSTS='["algo-1"]', SM_CURRENT_HOST="algo-1",
        JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
        # one device, as the cell's chip: a `data` mesh keeps the densified path
        XLA_FLAGS="--xla_force_host_platform_device_count=1",
    )
    result = subprocess.run(
        [sys.executable, "-m", "sagemaker_xgboost_container_tpu.training.entry"],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert result.returncode == 0, result.stderr[-3000:]
    lines = [l for l in result.stdout.splitlines() if "bundled layout:" in l]
    assert len(lines) == 1 and " 0 conflict rows" in lines[0], result.stdout[-2000:]
    forest = Forest.load_model(str(model_dir / "xgboost-model"))
    assert forest.num_boosted_rounds == 4
    used = np.concatenate([t.feature[t.left >= 0] for t in forest.trees])
    assert len(used) and used.max() < x.shape[1]
    # the model file's trees are the densified path's
    from sagemaker_xgboost_container_tpu import models

    dense = models.train(
        {"objective": "binary:logistic", "max_depth": 3, "eval_metric": "logloss",
         "min_child_weight": 5, "_rounds_per_dispatch": 8},
        DataMatrix(densified(x), labels=y), num_boost_round=4, verbose_eval=False,
    )
    for a, b in zip(forest.trees, dense.trees):
        split = b.left >= 0
        np.testing.assert_array_equal(a.feature, b.feature)
        np.testing.assert_array_equal(a.threshold[split], b.threshold[split])
        np.testing.assert_array_equal(a.default_left[split], b.default_left[split])
