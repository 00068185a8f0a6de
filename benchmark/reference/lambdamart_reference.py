"""Plain reference for LambdaMART (``rank:ndcg``) and NDCG@k: numpy,
float64, no jax, nothing of the program.

Classic all-pairs LambdaMART, as ``ops/ranking.py`` documents it and as the
package has computed it since PR 1. Inside each query group, for every pair
of documents with ``y_i > y_j``:

    rho    = 1 / (1 + exp(s_i - s_j))                      (sigma = 1)
    weight = |2^y_i - 2^y_j| * |1/log2(1+r_i) - 1/log2(1+r_j)| / maxDCG
    g_i   -= rho * weight          g_j += rho * weight
    h_i   += rho * (1 - rho) * weight, and the same for h_j

with ``r`` the 1-based rank of a document by score descending inside its
group, ``maxDCG`` the DCG of the group in the order of its labels (floor
1e-12), the hessian floored at 1e-16, no truncation of pairs or groups and
no normalisation. XGBoost 2 and later default to
``lambdarank_pair_method=topk`` with normalisation: that is another
objective, and not what this configuration states.

The check is teacher-forced like ``gbt_reference``, whose routing, node sums
and ``check_tree`` it imports: margins come from the reference's own
traversal of every earlier tree on the raw floats, rows are routed by the
judged tree's own splits, and the node sums, gains, leaf values and the
logged metric are compared.

**Ranks are discrete**, so one thing is the reference's to get right. The
margin a rank is taken from is accumulated as the program accumulates it:
float32 leaf values added to a float32 margin in tree order, starting from
the float32 base score. The order of documents is then the program's, bit
for bit, and ties break by position on both sides (a stable sort here, a
count of the documents ahead there). Everything after the ranks is float64:
the score differences, rho, the weights and the sums over pairs.
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark.reference import gbt_reference

THREADS = 8
PAIRS_PER_TASK = 1 << 21  # pairs of one task's [groups, size, size] float64 arrays
HESS_FLOOR = 1e-16  # the program's (xgboost's kRtEps)
MAX_DCG_FLOOR = 1e-12


def _over_equal_sizes(fn, sizes):
    """``fn(rows)`` over every batch of groups of one size, ``rows`` the
    int64 ``[groups, size]`` row numbers of the batch, on a few threads
    (numpy lets go of the interpreter lock inside its loops over large
    arrays). Groups of one size are taken together only so that the loop
    over 18,919 groups is numpy's and not python's: no group sees another.
    Returns the (rows, result) of each batch."""
    sizes = np.asarray(sizes, np.int64)
    starts = np.cumsum(sizes) - sizes
    tasks = []
    for size in np.unique(sizes[sizes > 0]):
        first = starts[sizes == size]
        step = max(1, PAIRS_PER_TASK // int(size * size))
        for lo in range(0, len(first), step):
            tasks.append(first[lo:lo + step, None] + np.arange(size)[None, :])
    with ThreadPoolExecutor(max_workers=THREADS) as pool:
        return list(zip(tasks, pool.map(fn, tasks)))


def ranks_descending(score):
    """1-based rank inside each row by score descending, ties broken by
    position (a stable sort)."""
    order = np.argsort(-score, axis=-1, kind="stable")
    ranks = np.empty(score.shape, np.int64)
    np.put_along_axis(ranks, order, np.arange(1, score.shape[-1] + 1), axis=-1)
    return ranks


def dcg(gains_in_order, k=None):
    """DCG (at ``k``) of each row of gains, given in the order ranked."""
    if k:
        gains_in_order = gains_in_order[..., :k]
    discount = np.log2(np.arange(2, gains_in_order.shape[-1] + 2))
    return np.sum(gains_in_order / discount, axis=-1)


def group_grad_hess(score32, label):
    """(g, h) of the documents of groups of one size (``[groups, size]``, or
    one group's ``[size]``): float64 sums over all pairs inside each group."""
    ranks = ranks_descending(score32)  # the program's order: float32 scores
    s = score32.astype(np.float64)
    gain = np.exp2(label) - 1.0
    discount = 1.0 / np.log2(1.0 + ranks)
    max_dcg = np.maximum(dcg(-np.sort(-gain, axis=-1)), MAX_DCG_FLOOR)
    rho = 1.0 / (1.0 + np.exp(s[..., :, None] - s[..., None, :]))
    weight = (
        np.abs(gain[..., :, None] - gain[..., None, :])
        * np.abs(discount[..., :, None] - discount[..., None, :])
        / max_dcg[..., None, None]
    )
    prefer = label[..., :, None] > label[..., None, :]
    lam = np.where(prefer, rho * weight, 0.0)
    hess = np.where(prefer, rho * (1.0 - rho) * weight, 0.0)
    g = -lam.sum(axis=-1) + lam.sum(axis=-2)
    h = np.maximum(hess.sum(axis=-1) + hess.sum(axis=-2), HESS_FLOOR)
    return g, h


def grad_hess(margin32, label, sizes):
    """Per document, float64, over groups of ``sizes`` contiguous rows."""
    label = label.astype(np.float64)
    g, h = np.zeros(len(margin32)), np.zeros(len(margin32))
    batches = _over_equal_sizes(lambda rows: group_grad_hess(margin32[rows], label[rows]), sizes)
    for rows, (g_rows, h_rows) in batches:
        g[rows], h[rows] = g_rows, h_rows
    return g, h


def ndcg(margin32, label, sizes, k=None):
    """Mean over groups of DCG@k over ideal DCG@k; a group without a
    relevant document counts as 1, and so does one without a document
    (``eval_metrics.ndcg``'s and xgboost's ``ndcg`` without the minus)."""
    label = label.astype(np.float64)

    def batch(rows):
        gain = np.exp2(label[rows]) - 1.0
        order = np.argsort(-margin32[rows], axis=1, kind="stable")
        ideal = dcg(-np.sort(-gain, axis=1), k)
        got = dcg(np.take_along_axis(gain, order, axis=1), k)
        return float(np.sum(np.where(ideal > 0, got / np.where(ideal > 0, ideal, 1.0), 1.0)))

    total = sum(value for _rows, value in _over_equal_sizes(batch, sizes))
    return (total + np.count_nonzero(np.asarray(sizes) == 0)) / len(sizes)


def add_tree_margin32(margin32, tree, x):
    """The program's accumulation: the float32 value of each row's leaf
    added to the float32 margin."""
    value = tree["value"].astype(np.float32)
    parts = gbt_reference._over_row_blocks(
        lambda lo, hi: value.take(gbt_reference.route(tree, x[lo:hi])[-1]), len(x)
    )
    margin32 += np.concatenate(parts)


def metric_k(name):
    """``ndcg`` -> None, ``ndcg@10`` -> 10."""
    base, _, suffix = name.partition("@")
    if base != "ndcg":
        raise ValueError("the reference has no metric {!r}".format(name))
    return int(suffix) if suffix else None


def check_rounds(rounds, check_at, sets, base_score, eta, lam, max_depth, metric, logged):
    """Judge rounds ``check_at`` of ``rounds`` (one tree a round).

    ``sets``: name -> (x, label, sizes), the set named ``train`` the one the
    trees were grown on; ``logged``: name -> the metric the program logged
    for each round. Returns the worst gap of each kind over the judged
    rounds: ``gbt_reference.check_tree``'s numbers against this reference's
    gradients, and ``ndcg_abs``, the logged metric of every set against the
    reference's at the same round."""
    k = metric_k(metric)
    margins = {
        name: np.full(len(x), np.float32(base_score), np.float32)
        for name, (x, _label, _sizes) in sets.items()
    }
    x, label, sizes = sets["train"]
    worst = {"ndcg_abs": 0.0}
    for r in range(max(check_at) + 1):
        ((_class_id, tree),) = rounds[r]
        if r in check_at:
            g, h = grad_hess(margins["train"], label, sizes)
            for key, v in gbt_reference.check_tree(tree, x, g, h, eta, lam, max_depth).items():
                worst[key] = max(worst.get(key, 0.0), v)
        for name, (xs, _label, _sizes) in sets.items():
            add_tree_margin32(margins[name], tree, xs)
        if r in check_at:
            for name, (_xs, labels, group_sizes) in sets.items():
                gap = abs(ndcg(margins[name], labels, group_sizes, k) - logged[name][r])
                worst["ndcg_abs"] = max(worst["ndcg_abs"], gap)
    return worst
