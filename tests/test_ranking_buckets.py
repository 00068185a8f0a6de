"""Query groups bucketed by size (ops/ranking.py): the layout, the LambdaMART
gradient over it against a float64 reference, the grouped NDCG on the device
against the host's, and a ranking job that logs its metric every round of a
fused dispatch."""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import lambdamart_reference  # noqa: E402
from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix  # noqa: E402
from sagemaker_xgboost_container_tpu.models import eval_metrics, train  # noqa: E402
from sagemaker_xgboost_container_tpu.models.device_metrics import (  # noqa: E402
    all_supported,
    make_device_metric,
    ndcg_cutoffs,
)
from sagemaker_xgboost_container_tpu.ops import ranking  # noqa: E402

# three groups in the narrowest bucket, one in each of 64 and 256, and one over the
# widest doubled bucket (512)
RAGGED = np.asarray([1, 2, 7, 33, 130, 600])


def on_device(layout, labels, weights=None, cutoffs=(0,)):
    """The layout as a session puts it on the device: with its slot columns."""
    labels = jnp.asarray(labels)
    weights = jnp.ones_like(labels) if weights is None else jnp.asarray(weights)
    return ranking.with_slot_columns(layout, labels, weights, cutoffs=cutoffs)


def documents(sizes, seed=0, tie_every=5):
    """Margins with ties (every ``tie_every``-th document repeats its
    neighbour's margin), graded labels, unit weights."""
    rng = np.random.default_rng(seed)
    n = int(np.sum(sizes))
    margins = rng.normal(size=n).astype(np.float32)
    margins[tie_every::tie_every] = margins[tie_every - 1:-1:tie_every]
    labels = rng.integers(0, 5, n).astype(np.float32)
    return margins, labels, np.ones(n, np.float32)


# ------------------------------------------------------------------ layout
@pytest.mark.parametrize(
    "largest,widths",
    [
        (6, [8]),
        (32, [32]),
        (33, [32, 40]),
        (130, [32, 64, 128, 256]),
        (600, [32, 64, 128, 256, 512, 640]),
        (1251, [32, 64, 128, 256, 512, 768, 1024, 1280]),
        (2049, [32, 64, 128, 256, 512, 768, 1024, 1280, 1536, 1792, 2048, 2176]),
    ],
)
def test_bucket_widths_are_read_from_the_largest_group_alone(largest, widths):
    assert ranking.bucket_widths(largest) == widths


def test_layout_holds_every_row_once_and_truncates_no_group():
    sizes = np.asarray([3, 0, 1251, 1, 64, 65, 0, 700])
    layout = ranking.build_group_layout(sizes)
    assert [index.shape for index in layout.indices] == [
        (2, 32), (1, 64), (1, 128), (1, 768), (1, 1280)
    ]
    assert float(layout.empty_groups) == 2.0
    flat = np.concatenate([index.reshape(-1) for index in layout.indices])
    held = flat[flat >= 0]
    assert sorted(held.tolist()) == list(range(int(sizes.sum())))
    # the largest group is whole, in row order, in the widest bucket
    first = int(sizes[:2].sum())
    assert layout.indices[-1][0, :1251].tolist() == list(range(first, first + 1251))
    assert (layout.indices[-1][0, 1251:] == -1).all()
    # row_slot is the inverse of the concatenated flat indices
    assert (flat[layout.row_slot] == np.arange(int(sizes.sum()))).all()


def test_layout_refuses_a_widest_bucket_under_the_largest_group():
    with pytest.raises(ValueError):
        ranking.build_group_layout([5, 40], widths=[32])
    with pytest.raises(ValueError):
        ranking.build_sharded_group_layout([5, 40, 7], 2, max_group_size=32)


def test_pair_slots_count_the_padding_of_widths_and_chunks():
    layout = ranking.build_group_layout([1, 2, 7, 33, 130, 600])
    assert ranking.pair_slots(layout) == 3 * 32 ** 2 + 64 ** 2 + 256 ** 2 + 640 ** 2
    # three groups of width 32 in steps of at most one group's pairs: no padding;
    # in two steps of two groups, one all-padding group is added
    assert ranking.pair_slots(layout, 32 ** 2) == ranking.pair_slots(layout)
    assert ranking._chunking(3, 32, 2 * 32 ** 2) == (2, 2)


def test_sharded_layout_gives_each_shard_one_index_and_its_row_slots():
    sizes = np.asarray([5, 9, 3, 7, 2])
    perm, layout, rps = ranking.build_sharded_group_layout(sizes, 2)
    (index,) = layout.indices
    assert index.shape[0] == 2 and layout.row_slot.shape == (2 * rps,)
    assert ranking.pair_slots(layout) == 2 * index.shape[1] * index.shape[2] ** 2
    for shard in range(2):
        flat = index[shard].reshape(-1)
        slots = layout.row_slot[shard * rps:(shard + 1) * rps]
        rows = np.flatnonzero(perm[shard * rps:(shard + 1) * rps] >= 0)
        assert (flat[slots[rows]] == rows).all() and (np.delete(slots, rows) == -1).all()


# ---------------------------------------------------------------- gradient
def reference_grad_hess(scheme, margins, labels, sizes):
    """float64, group by group. ``ndcg`` is the benchmark's reference; the
    other two schemes differ from it in the pair weight alone."""
    if scheme == "ndcg":
        return lambdamart_reference.grad_hess(margins, labels, sizes)
    g, h = np.zeros(len(margins)), np.zeros(len(margins))
    row = 0
    for size in sizes:
        s = margins[row:row + size].astype(np.float64)
        y = labels[row:row + size].astype(np.float64)
        rho = 1.0 / (1.0 + np.exp(s[:, None] - s[None, :]))
        weight = map_swap_gain(s, y) if scheme == "map" else np.ones((size, size))
        prefer = y[:, None] > y[None, :]
        lam = np.where(prefer, rho * weight, 0.0)
        hess = np.where(prefer, rho * (1.0 - rho) * weight, 0.0)
        g[row:row + size] = -lam.sum(axis=1) + lam.sum(axis=0)
        h[row:row + size] = np.maximum(hess.sum(axis=1) + hess.sum(axis=0), 1e-16)
        row += size
    return g, h


def map_swap_gain(scores, labels):
    """|delta AP| of swapping every pair of documents, by recomputing AP."""
    order = np.argsort(-scores, kind="stable")
    rel = (labels > 0).astype(np.float64)[order]

    def average_precision(r):
        hits = np.cumsum(r)
        return float((hits / np.arange(1, len(r) + 1) * r).sum() / max(r.sum(), 1.0))

    base = average_precision(rel)
    gain = np.zeros((len(rel), len(rel)))
    for a in range(len(rel)):
        for b in range(a + 1, len(rel)):
            if rel[a] != rel[b]:
                swapped = rel.copy()
                swapped[[a, b]] = swapped[[b, a]]
                gain[a, b] = gain[b, a] = abs(average_precision(swapped) - base)
    position = np.empty(len(rel), np.int64)
    position[order] = np.arange(len(rel))
    return gain[np.ix_(position, position)]


@pytest.mark.parametrize("scheme", ["pairwise", "ndcg", "map"])
def test_bucketed_gradient_matches_the_float64_reference(scheme):
    # the brute-force |delta AP| is cubic in a group's size: `map` leaves the
    # largest group to the comparison of layouts below
    sizes = RAGGED[:-1] if scheme == "map" else RAGGED
    # AP recomputed after a swap knows no ties: `map` gets distinct margins
    margins, labels, weights = documents(
        sizes, seed=3, tie_every=10 ** 6 if scheme == "map" else 5
    )
    layout = ranking.build_group_layout(sizes)
    assert len(layout.indices) == len(sizes) - 2  # the groups of 1, 2 and 7 share a bucket
    g, h = ranking.lambdarank_grad_hess(
        jnp.asarray(margins), on_device(layout, labels, weights), scheme
    )
    g_ref, h_ref = reference_grad_hess(scheme, margins, labels, sizes)
    # float32 sums over up to 600 pairs a document against float64 sums: a few
    # units of 1e-7 of the largest term a sum holds
    scale_g = np.abs(g_ref).max()
    np.testing.assert_allclose(np.asarray(g), g_ref, rtol=2e-5, atol=2e-6 * scale_g)
    np.testing.assert_allclose(np.asarray(h), h_ref, rtol=2e-5, atol=2e-6 * h_ref.max())


@pytest.mark.parametrize("scheme", ["pairwise", "ndcg", "map"])
def test_bucketed_gradient_equals_the_one_bucket_layout(scheme):
    margins, labels, weights = documents(RAGGED, seed=4)
    bucketed = ranking.build_group_layout(RAGGED)
    one = ranking.build_group_layout(RAGGED, widths=[640])
    assert len(bucketed.indices) == 4 and len(one.indices) == 1
    g_b, h_b = ranking.lambdarank_grad_hess(
        jnp.asarray(margins), on_device(bucketed, labels, weights), scheme
    )
    g_1, h_1 = ranking.lambdarank_grad_hess(
        jnp.asarray(margins), on_device(one, labels, weights), scheme
    )
    # only padding differs, and padding adds exact zeros; but a sum over 640
    # slots and one over 32 need not add their terms in the same order, so
    # the two are held to float32 rounding and not to the bit
    np.testing.assert_allclose(np.asarray(g_b), np.asarray(g_1), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(h_b), np.asarray(h_1), rtol=1e-5, atol=1e-7)


def test_rank_descending_is_the_stable_argsort_of_argsort():
    rng = np.random.default_rng(5)
    scores = np.round(rng.normal(size=(4, 9)), 1).astype(np.float32)  # ties
    valid = np.ones((4, 9), bool)
    valid[1, 6:] = False
    valid[3, 1:] = False
    got = np.asarray(ranking.rank_descending(jnp.asarray(scores), jnp.asarray(valid)))
    key = np.where(valid, -scores, np.inf)
    want = np.argsort(np.argsort(key, axis=1, kind="stable"), axis=1, kind="stable") + 1
    assert (got == want).all()


# ------------------------------------------------------------ device ndcg
@pytest.mark.parametrize("name", ["ndcg", "ndcg@1", "ndcg@10"])
@pytest.mark.parametrize(
    "case", ["ragged_with_ties", "all_zero_labels", "one_document_groups", "an_empty_group"]
)
def test_device_ndcg_matches_the_host(name, case):
    sizes = {
        "ragged_with_ties": RAGGED,
        "all_zero_labels": np.asarray([4, 40, 9]),
        "one_document_groups": np.asarray([1, 1, 1, 12, 1]),
        "an_empty_group": np.asarray([5, 0, 33, 0, 2]),
    }[case]
    margins, labels, _w = documents(sizes, seed=6, tie_every=3)
    if case == "all_zero_labels":
        labels[4:44] = 0.0  # the middle group has no relevant document: counted as 1
    fn = make_device_metric(name, "rank:ndcg")
    assert fn.needs_groups
    layout = on_device(ranking.build_group_layout(sizes), labels, cutoffs=ndcg_cutoffs([name]))
    got = float(fn.finalize(fn.partial(jnp.asarray(margins), None, None, layout)))
    want = eval_metrics.evaluate(name, margins, labels, groups=sizes)
    assert got == pytest.approx(want, abs=2e-6)


def test_grouped_metrics_need_a_layout_to_be_supported():
    assert all_supported(["ndcg@10"], "rank:ndcg", 1) is None
    assert all_supported(["ndcg@10"], "rank:ndcg", 1, grouped=True) is not None
    assert all_supported(["map"], "rank:ndcg", 1, grouped=True) is None  # host path


# ------------------------------------------------------------- a ranking job
def ranking_sets(seed):
    rng = np.random.default_rng(seed)
    sizes = rng.integers(1, 90, 40)
    sizes[:2] = 1, 140
    n = int(sizes.sum())
    x = rng.normal(size=(n, 6)).astype(np.float32)
    y = np.clip(np.round(1.2 * x[:, 0] + 0.5 * x[:, 1] + 0.5 * rng.normal(size=n) + 1), 0, 4)
    return DataMatrix(x, labels=y.astype(np.float32), groups=sizes)


class KeepLog:
    def after_iteration(self, model, epoch, evals_log):
        self.log = {k: {m: list(v) for m, v in d.items()} for k, d in evals_log.items()}
        return False


def train_ranker(k, rounds=8):
    dtrain, dval = ranking_sets(1), ranking_sets(2)
    keep = KeepLog()
    forest = train(
        {"objective": "rank:ndcg", "max_depth": 4, "eta": 0.3, "eval_metric": "ndcg@10",
         "_rounds_per_dispatch": k, "seed": 3},
        dtrain, num_boost_round=rounds,
        evals=[(dtrain, "train"), (dval, "validation")], callbacks=[keep], verbose_eval=False,
    )
    return forest, keep.log, (dtrain, dval)


def test_fused_ranking_job_logs_every_round_from_the_device_and_keeps_the_forest():
    one, log_1, _sets = train_ranker(1)       # the host evaluates every round
    fused, log_k, (dtrain, dval) = train_ranker(4)  # the device does, inside the scan
    for name in ("train", "validation"):
        assert len(log_k[name]["ndcg@10"]) == 8
        np.testing.assert_allclose(log_k[name]["ndcg@10"], log_1[name]["ndcg@10"], atol=2e-6)
    # and equal to the host's metric of the forest as it stood at the last round
    for name, dm in (("train", dtrain), ("validation", dval)):
        host = eval_metrics.evaluate(
            "ndcg@10", fused.predict(dm.features, output_margin=True), dm.labels, groups=dm.groups
        )
        assert log_k[name]["ndcg@10"][-1] == pytest.approx(host, abs=2e-6)
    # the same forest as K = 1: the same splits; the leaf values to a float32
    # unit in the last place, since the scan and the single round are two
    # programs and fuse the gradient's sums differently
    assert len(one.trees) == len(fused.trees) == 8
    for a, b in zip(one.trees, fused.trees):
        for field in ("feature", "left", "right", "default_left"):
            assert np.array_equal(getattr(a, field), getattr(b, field))
        np.testing.assert_allclose(a.threshold, b.threshold, rtol=0, atol=0)
        np.testing.assert_allclose(a.value, b.value, rtol=1e-5, atol=1e-7)


def test_ranking_job_without_groups_on_a_set_keeps_the_host_cadence():
    dtrain = ranking_sets(1)
    dval = ranking_sets(2)
    dval.groups = None  # one group of all its rows: the host evaluates it
    keep = KeepLog()
    train(
        {"objective": "rank:ndcg", "max_depth": 3, "eval_metric": "ndcg@10",
         "_rounds_per_dispatch": 4},
        dtrain, num_boost_round=8, evals=[(dtrain, "train"), (dval, "validation")],
        callbacks=[keep], verbose_eval=False,
    )
    assert len(keep.log["validation"]["ndcg@10"]) == 2  # once a dispatch


def test_ranking_round_program_names_its_three_stages_in_place_of_grad():
    from sagemaker_xgboost_container_tpu.telemetry import device

    device._reset_for_tests()
    train_ranker(2, rounds=2)
    stages = set(device.round_program_stages().values())
    assert {"rank_gather", "rank_pairs", "rank_scatter", "eval_metric", "hist"} <= stages
    assert "grad" not in stages  # every instruction of the gradient has a narrower name
    device._reset_for_tests()
