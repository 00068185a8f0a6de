"""A ranking round gathers the margins and nothing else (ops/ranking.py): the
labels, the weights and what follows from them alone are the layout's
``SlotColumns``, made once where the layout goes to the device. Held here
against the form the round had before, kept below as the plain three-gather
expression: the gradient to the bit over bucketed and sharded layouts, a
``train()`` to the forest's bytes and the metric's lines, the round's jaxpr to
its count of gathers, and the two gauges to what the shapes say."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
from sagemaker_xgboost_container_tpu.models import booster, device_metrics, train
from sagemaker_xgboost_container_tpu.ops import ranking
from sagemaker_xgboost_container_tpu.telemetry import REGISTRY
from tests.test_ranking_buckets import RAGGED, KeepLog

SCHEMES = ["pairwise", "ndcg", "map"]


def documents(sizes, seed):
    """Margins with ties, graded labels, weights that differ by group."""
    rng = np.random.default_rng(seed)
    n = int(np.sum(sizes))
    margins = rng.normal(size=n).astype(np.float32)
    margins[5::5] = margins[4:-1:5]
    labels = rng.integers(0, 5, n).astype(np.float32)
    weights = np.repeat(rng.uniform(0.5, 2.0, len(sizes)), sizes).astype(np.float32)
    return jnp.asarray(margins), jnp.asarray(labels), jnp.asarray(weights)


# ------------------------------------------------ the form the round had before
def ideal_dcg_at(labels, gains, valid, k=None):
    """DCG (at ``k``) of each group's documents in the order of their labels."""
    ideal_ranks = ranking.rank_descending(labels, valid)
    terms = gains * ranking.dcg_discount(ideal_ranks)
    if k:
        terms = jnp.where(ideal_ranks <= k, terms, 0.0)
    return terms.sum(axis=1)


def three_gather_block(S, Y, W, valid, scheme):
    """``_lambdarank_block`` with the gains and the ideal DCG made in place."""
    gains = ranking.dcg_gain(Y, valid)
    max_dcg = jnp.maximum(ideal_dcg_at(Y, gains, valid), 1e-12)
    return ranking._lambdarank_block(S, Y, W, valid, gains, max_dcg, scheme=scheme)


def three_gather_grad_hess(margins, labels, weights, layout, scheme,
                           pair_slots_per_step=ranking.PAIR_SLOTS_PER_STEP):
    """Margins, labels and weights from rows to slots, every call."""
    grads, hesses = [], []
    for index in layout.indices:
        index = index.reshape(index.shape[-2:])
        valid, S, Y, W = ranking.gather_groups(
            index, (margins, labels, weights), (0.0, -jnp.inf, 0.0)
        )
        g_mat, h_mat = ranking.map_group_chunks(
            lambda s, y, w, v: three_gather_block(s, y, w, v, scheme),
            (S, Y, W, valid),
            pair_slots_per_step,
            fills=(0.0, -jnp.inf, 0.0, False),
        )
        grads.append(g_mat.reshape(-1))
        hesses.append(h_mat.reshape(-1))
    return (
        ranking.slots_to_rows(jnp.concatenate(grads), layout.row_slot),
        ranking.slots_to_rows(jnp.concatenate(hesses), layout.row_slot),
    )


def two_gather_ndcg(name, k=None):
    """``grouped_ndcg`` with the labels gathered and the ideal DCG made a call."""
    def per_group(S, Y, valid):
        gains = ranking.dcg_gain(Y, valid)
        ranks = ranking.rank_descending(S, valid)
        terms = gains * ranking.dcg_discount(ranks)
        if k:
            terms = jnp.where(ranks <= k, terms, 0.0)
        dcg = terms.sum(axis=1)
        ideal = ideal_dcg_at(Y, gains, valid, k)
        ndcg = jnp.where(ideal > 0, dcg / jnp.where(ideal > 0, ideal, 1.0), 1.0)
        held = valid.any(axis=1)
        return jnp.where(held, ndcg, 0.0), held.astype(jnp.float32)

    def partial(m, y, w, layout):
        total = count = layout.empty_groups
        for index in layout.indices:
            valid, S, Y = ranking.gather_groups(index, (m, y), (0.0, 0.0))
            ndcg, held = ranking.map_group_chunks(
                per_group, (S, Y, valid), fills=(0.0, 0.0, False)
            )
            total = total + ndcg.sum()
            count = count + held.sum()
        return jnp.stack([total, count])

    return device_metrics.DeviceMetric(
        name, 2, partial, lambda s: s[0] / jnp.maximum(s[1], 1e-15), needs_groups=True
    )


def bits(array):
    return np.asarray(array).view(np.uint32)


# ------------------------------------------------------------------ gradient
@pytest.mark.parametrize("scheme", SCHEMES)
@pytest.mark.parametrize("steps", ["one_step", "chunked"])
def test_gradient_equals_the_three_gather_form_to_the_bit(scheme, steps):
    margins, labels, weights = documents(RAGGED, seed=11)
    host = ranking.build_group_layout(RAGGED)
    budget = ranking.PAIR_SLOTS_PER_STEP if steps == "one_step" else 2 * 32 ** 2
    layout = ranking.with_slot_columns(
        host, labels, weights, cutoffs=(0, 10), pair_slots_per_step=budget
    )
    assert [s.labels.shape for s in layout.slots] == [i.shape for i in host.indices]
    got = jax.jit(
        lambda m, lay: ranking.lambdarank_grad_hess(m, lay, scheme, budget)
    )(margins, layout)
    want = jax.jit(
        lambda m, y, w, lay: three_gather_grad_hess(m, y, w, lay, scheme, budget)
    )(margins, labels, weights, jax.tree_util.tree_map(jnp.asarray, host))
    for a, b in zip(got, want):
        assert np.array_equal(bits(a), bits(b))


@pytest.mark.multichip
@pytest.mark.parametrize("scheme", SCHEMES)
def test_sharded_gradient_equals_the_three_gather_form_to_the_bit(scheme):
    sizes = np.asarray([5, 9, 3, 17, 2, 40, 11, 1])
    shards = 4
    mesh = Mesh(np.array(jax.devices()[:shards]), axis_names=("data",))
    perm, host, rps = ranking.build_sharded_group_layout(sizes, shards)
    margins, labels, weights = (
        jnp.where(perm >= 0, column[np.maximum(perm, 0)], fill)
        for column, fill in zip(documents(sizes, seed=12), (0.0, 0.0, 0.0))
    )
    by_slot, by_group = P("data", None, None), P("data", None)
    bare = ranking.GroupLayout((by_slot,), P("data"), P())
    filled = bare._replace(
        slots=(ranking.SlotColumns(by_slot, by_slot, by_slot, by_slot, {0: by_group}),)
    )
    rows = P("data")

    def mapped(fn, in_specs, out_specs):
        return jax.jit(
            jax.shard_map(
                fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
            )
        )

    layout = mapped(ranking.with_slot_columns, (bare, rows, rows), filled)(
        host, labels, weights
    )
    (columns,) = layout.slots
    assert columns.labels.shape == host.indices[0].shape
    assert columns.ideal_dcg[0].shape == host.indices[0].shape[:2]
    got = mapped(
        lambda m, lay: ranking.lambdarank_grad_hess(m, lay, scheme),
        (rows, filled), (rows, rows),
    )(margins, layout)
    want = mapped(
        lambda m, y, w, lay: three_gather_grad_hess(m, y, w, lay, scheme),
        (rows, rows, rows, bare), (rows, rows),
    )(margins, labels, weights, host)
    for a, b in zip(got, want):
        assert np.array_equal(bits(a), bits(b))
        assert np.abs(np.asarray(a)).sum() > 0


# ------------------------------------------------------------ a ranking job
def ranking_set(seed):
    rng = np.random.default_rng(seed)
    sizes = rng.integers(1, 60, 30)
    sizes[:2] = 1, 140  # three buckets: 32, 64 and 256
    n = int(sizes.sum())
    x = rng.normal(size=(n, 5)).astype(np.float32)
    y = np.clip(np.round(1.2 * x[:, 0] + 0.5 * x[:, 1] + 0.5 * rng.normal(size=n) + 1), 0, 4)
    weights = np.repeat(rng.uniform(0.5, 2.0, len(sizes)), sizes).astype(np.float32)
    return DataMatrix(x, labels=y.astype(np.float32), weights=weights, groups=sizes)


def train_ranker(objective, k):
    dtrain, dval = ranking_set(21), ranking_set(22)
    keep = KeepLog()
    forest = train(
        {"objective": objective, "max_depth": 3, "eta": 0.3, "seed": 5,
         "eval_metric": ["ndcg@10", "ndcg"], "_rounds_per_dispatch": k},
        dtrain, num_boost_round=2 * k,
        evals=[(dtrain, "train"), (dval, "validation")], callbacks=[keep], verbose_eval=False,
    )
    return forest, keep.log


@pytest.mark.parametrize("objective", ["rank:pairwise", "rank:ndcg", "rank:map"])
@pytest.mark.parametrize("k", [1, 8])
def test_train_gives_the_three_gather_forms_forest_and_metric_lines(monkeypatch, objective, k):
    forest, log = train_ranker(objective, k)

    dtrain = ranking_set(21)
    labels = jnp.asarray(dtrain.labels)
    weights = jnp.asarray(dtrain.get_weight())
    assert len(np.unique(np.asarray(weights))) > 1  # the group weights, a row each

    def before(margins, layout, scheme):
        return three_gather_grad_hess(margins, labels, weights, layout, scheme)

    monkeypatch.setattr(booster, "lambdarank_grad_hess", before)
    monkeypatch.setattr(device_metrics, "grouped_ndcg", two_gather_ndcg)
    forest_before, log_before = train_ranker(objective, k)
    assert log == log_before
    assert len(forest.trees) == len(forest_before.trees) == 2 * k
    for a, b in zip(forest.trees, forest_before.trees):
        # all that a prediction reads: the same bytes
        for field in ("feature", "left", "right", "default_left", "threshold", "value"):
            assert getattr(a, field).tobytes() == getattr(b, field).tobytes(), field
        # the statistics beside it to a float32 unit in the last place: two
        # programs, and the CPU's compiler contracts a multiply and an add
        # into one rounding by what it fused them with (on the chip the
        # forests' sha256 are equal: PERF.md section 6, PR 38)
        for field in ("base_weight", "gain", "sum_hess"):
            np.testing.assert_allclose(
                getattr(a, field), getattr(b, field), rtol=3e-7, atol=0, err_msg=field
            )
    assert len(log["validation"]["ndcg@10"]) == 2 * k and len(log["train"]["ndcg"]) == 2 * k


# --------------------------------------------------------------- structure
def gathers(jaxpr):
    """The name stack of every ``gather`` in ``jaxpr`` and what it calls."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather":
            found.append(str(eqn.source_info.name_stack))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(gathers(sub))
    return found


@pytest.mark.parametrize("scheme", ["pairwise", "ndcg"])  # `map` sorts: gathers of its own
def test_a_round_gathers_once_a_bucket_and_caller(scheme):
    margins, labels, weights = documents(RAGGED, seed=13)
    layout = ranking.with_slot_columns(
        ranking.build_group_layout(RAGGED), labels, weights, cutoffs=(0, 10)
    )
    buckets = len(layout.indices)
    assert buckets == 4
    stacks = gathers(
        jax.make_jaxpr(lambda m, lay: ranking.lambdarank_grad_hess(m, lay, scheme))(
            margins, layout
        ).jaxpr
    )
    assert sum("rank_gather" in s for s in stacks) == buckets
    assert sum("rank_scatter" in s for s in stacks) == 2
    assert len(stacks) == buckets + 2
    # the form it replaces: three a bucket
    before = gathers(
        jax.make_jaxpr(lambda m, y, w, lay: three_gather_grad_hess(m, y, w, lay, scheme))(
            margins, labels, weights, layout
        ).jaxpr
    )
    assert len(before) == 3 * buckets + 2
    for name in ("ndcg", "ndcg@10"):
        partial = device_metrics.make_device_metric(name, "rank:ndcg").partial
        metric = gathers(
            jax.make_jaxpr(lambda m, lay: partial(m, None, None, lay))(margins, layout).jaxpr
        )
        assert len(metric) == buckets


def test_gauges_read_what_the_layouts_shapes_say():
    train_ranker("rank:ndcg", 8)
    train_buckets = [(12, 32), (17, 64), (1, 256)]
    layout = ranking.build_group_layout(ranking_set(21).groups)
    assert [i.shape for i in layout.indices] == train_buckets
    val = ranking.build_group_layout(ranking_set(22).groups)
    # the gradient's, then two metrics (`ndcg@10`, `ndcg`) over both sets
    want = len(train_buckets) * (1 + 2) + 2 * len(val.indices)
    assert REGISTRY.gauge("rank_row_gathers_per_round", "").value == want
    # labels, weights, gains (float32) and valid (bool) a slot; the ideal DCG
    # at the cutoffs 0 and 10 a group
    slot_bytes = sum(
        g * m * (3 * 4 + 1) + 2 * 4 * g
        for lay in (layout, val) for g, m in (i.shape for i in lay.indices)
    )
    assert REGISTRY.gauge("rank_slot_constant_bytes", "").value == slot_bytes
    # K = 1: the host evaluates, the round holds the gradient's gathers alone
    train_ranker("rank:ndcg", 1)
    assert REGISTRY.gauge("rank_row_gathers_per_round", "").value == len(train_buckets)
