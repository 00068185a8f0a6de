"""``gbt_reference`` for trees that hold set-membership splits: numpy, float64,
no jax.

The same teacher-forced check and the same numbers (``gbt_reference`` says
what each is and why): rows are routed by the judged tree's own splits on the
raw floats, and every stored number is recomputed from the reference's own
margins, gradients and sums. Nothing of the program is imported or read but
the forest it returned and the loss it logged. A tree here is
``gbt_reference``'s dict with one key more, ``categories``: {node: the codes
that go right}. At such a node a row goes right iff the whole part of its
value is one of the codes; a NaN follows ``default_left``; a number that is
no code of the set (negative and unseen ones included) goes left.

Teacher forcing judges the sums of the splits the program chose, never
whether it *searched*. For set splits the reference therefore also runs **its
own partition scan**: at the judged rounds, for every set split, the float64
sums of each category over the rows the reference routed to that node, its
own order of the categories (by ``g / (h + lambda)``, the categories the node
holds), its own best candidate under the same rules (fewer than
``max_cat_to_onehot`` categories: one against the rest; else the first ``k``
of the order from both ends, ``k`` up to the held categories less one and at
most ``max_cat_threshold``; missing on both sides; ``min_child_weight`` in
both children), and

    cat_partition_regret = sum(best gain - gain of the program's set) / sum(best gain)

over the set splits of the judged rounds, both gains from the reference's
sums: the share of the gain there was to find that the program's sets left
behind. Zero where the program found the best set of every column it chose;
ties between categories and float32 sums in the program's order make it a
tolerance, not an equality. The worst node's own ratio is reported beside it
(``cat_partition_regret_max``) and not judged: a deep node whose best gain is
a rounding error of its sums reads any ratio at all
(``benchmark/README-categorical.md``).
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark.reference import gbt_reference
from benchmark.reference.gbt_reference import _score
from benchmark.reference.sparse_gbt_reference import path_sums, tree_gaps

BLOCK_ROWS = 1 << 17


def set_table(tree, width):
    """(is a set split [nodes], member [nodes, width]) of a tree's sets."""
    n_nodes = len(tree["left"])
    is_set = np.zeros(n_nodes, bool)
    member = np.zeros((n_nodes, max(width, 1)), bool)
    for node, codes in tree.get("categories", {}).items():
        is_set[node] = True
        codes = np.asarray(codes, np.int64)
        member[node, codes[(codes >= 0) & (codes < width)]] = True
    return is_set, member


def route(tree, x, table):
    """``gbt_reference.route`` with a set test where the node holds a set:
    the node of every row at each depth, the last entry being its leaf."""
    is_set, member = table
    width = member.shape[1]
    n, d = x.shape
    flat = x.reshape(-1)
    base = np.arange(n, dtype=np.int64) * d
    feature = np.maximum(tree["feature"], 0)
    node = np.zeros(n, np.int64)
    path = [node]
    while True:
        internal = tree["left"].take(node) >= 0
        if not internal.any():
            return path
        v = flat.take(base + feature.take(node))
        right = v >= tree["threshold"].take(node)
        at_set = is_set.take(node)
        if at_set.any():
            code = np.nan_to_num(v, nan=-1.0)
            valid = (code >= 0) & (code < width)
            code = np.where(valid, code, 0).astype(np.int64)
            in_set = valid & member[node, code]
            right = np.where(at_set, in_set, right)
        missing = np.isnan(v)
        if missing.any():
            right = np.where(missing, ~tree["default_left"].take(node), right)
        nxt = np.where(right, tree["right"].take(node), tree["left"].take(node))
        node = np.where(internal, nxt, node)
        path.append(node)


def category_sums(tree, path, x, g, h, cardinality):
    """{set node: float64 [3, C + 1]} over one block: per category the sums
    of ``g`` and ``h`` and the rows, of the rows ``path`` takes to the node;
    the last entry the rows whose value is missing or no category."""
    depth = gbt_reference.node_depths(tree)
    out = {}
    by_depth = {}
    for node in tree.get("categories", {}):
        by_depth.setdefault(int(depth[node]), []).append(int(node))
    for level, nodes in by_depth.items():
        if level >= len(path):
            continue
        at = path[level]
        order = np.argsort(at, kind="stable")
        sorted_at = at[order]
        for node in nodes:
            rows = order[np.searchsorted(sorted_at, node): np.searchsorted(sorted_at, node + 1)]
            C = int(cardinality[int(tree["feature"][node])])
            v = x[rows, int(tree["feature"][node])]
            code = np.nan_to_num(v, nan=-1.0)
            slot = np.where((code >= 0) & (code < C), code, C).astype(np.int64)
            out[node] = np.stack(
                [
                    np.bincount(slot, weights=w, minlength=C + 1)
                    for w in (g[rows], h[rows], np.ones(len(rows)))
                ]
            )
    return out


def best_partition(sums, lam, min_child_weight, max_cat_to_onehot, max_cat_threshold):
    """The reference's own best set of one node and column: the gain
    (``0.5 * (left + right - parent)`` of ``g^2 / (h + lambda)``) of the best
    legal candidate over ``sums`` (``category_sums``'s), or -inf."""
    g, h, rows = sums[0, :-1], sums[1, :-1], sums[2, :-1]
    g_m, h_m = sums[0, -1], sums[1, -1]
    G, H = sums[0].sum(), sums[1].sum()
    parent = _score(G, H, lam)
    C = len(g)
    if C < max_cat_to_onehot:
        g_sets, h_sets = g, h
    else:
        held = np.flatnonzero(rows > 0)
        order = held[np.argsort((g / (h + lam))[held], kind="stable")]
        k = min(len(order) - 1, max_cat_threshold)
        if k < 1:
            return -np.inf
        g_sets = np.concatenate([np.cumsum(g[order][:k]), np.cumsum(g[order][::-1][:k])])
        h_sets = np.concatenate([np.cumsum(h[order][:k]), np.cumsum(h[order][::-1][:k])])
    best = -np.inf
    for gr, hr in ((g_sets, h_sets), (g_sets + g_m, h_sets + h_m)):  # missing left, right
        gl, hl = G - gr, H - hr
        ok = (hl >= min_child_weight) & (hr >= min_child_weight)
        gain = 0.5 * (_score(gl, hl, lam) + _score(gr, hr, lam) - parent)
        if ok.any():
            best = max(best, float(gain[ok].max()))
    return best


def set_gain(sums, codes, default_left, lam):
    """The gain of sending ``codes`` right (and the missing rows where
    ``default_left`` says), from the reference's ``sums``."""
    codes = np.asarray(codes, np.int64)
    codes = codes[(codes >= 0) & (codes < sums.shape[1] - 1)]
    gr, hr = sums[0, codes].sum(), sums[1, codes].sum()
    if not default_left:
        gr, hr = gr + sums[0, -1], hr + sums[1, -1]
    G, H = sums[0].sum(), sums[1].sum()
    return 0.5 * (_score(G - gr, H - hr, lam) + _score(gr, hr, lam) - _score(G, H, lam))


def check_rounds(
    rounds, check_at, x, label, objective, base_score, eta, lam, max_depth, logged_loss,
    cardinality, min_child_weight, max_cat_to_onehot, max_cat_threshold,
):
    """``gbt_reference.check_rounds`` with set routing, and the partition
    scan's regret over the set splits of the judged rounds
    (``cat_partition_regret``: the gain left behind over the gain there was,
    summed over the nodes; ``_max``, ``_p99`` and ``_median``: the worst, the 99th
    in a hundred and the median node's own ratio; all 0 where no judged tree holds a set)."""
    n = len(x)
    last = max(check_at)
    num_group = max(1 + max(c for rnd in rounds for c, _t in rnd), 1)
    start = gbt_reference.base_margin(objective, base_score)
    label = label.astype(np.float64)
    width = int(max(cardinality, default=0))
    tables = [[set_table(t, width) for _c, t in rnd] for rnd in rounds[: last + 1]]

    def block(lo, hi):
        rows, y = x[lo:hi], label[lo:hi]
        margin = np.full((hi - lo, num_group), start, np.float64)
        sums, by_category, losses = {}, {}, {}
        for r, rnd in enumerate(rounds[: last + 1]):
            paths = [route(tree, rows, tables[r][i]) for i, (_c, tree) in enumerate(rnd)]
            if r in check_at:
                m = margin[:, 0] if num_group == 1 else margin
                g, h = gbt_reference.grad_hess(objective, m, y)
                for i, (c, tree) in enumerate(rnd):
                    gc, hc = (g, h) if num_group == 1 else (g[:, c], h[:, c])
                    sums[r, i] = path_sums(paths[i], len(tree["left"]), gc, hc)
                    by_category[r, i] = category_sums(tree, paths[i], rows, gc, hc, cardinality)
            for (c, tree), path in zip(rnd, paths):
                margin[:, c] += tree["value"].astype(np.float64).take(path[-1])
            if r in check_at:
                m = margin[:, 0] if num_group == 1 else margin
                losses[r] = (hi - lo) * gbt_reference.loss(objective, m, y)
        return sums, by_category, losses

    blocks = [(lo, min(lo + BLOCK_ROWS, n)) for lo in range(0, n, BLOCK_ROWS)]
    with ThreadPoolExecutor(max_workers=gbt_reference.THREADS) as pool:
        parts = list(pool.map(lambda b: block(*b), blocks))
    worst = {"loss_abs": 0.0}
    best_gains, lost, regrets = [], [], []
    for r in sorted(check_at):
        for i, (_c, tree) in enumerate(rounds[r]):
            sums = sum(p[0][r, i] for p in parts)
            for k, v in tree_gaps(tree, sums, eta, lam, max_depth).items():
                worst[k] = max(worst.get(k, 0.0), v)
            for node, codes in tree.get("categories", {}).items():
                of_node = sum(p[1][r, i][node] for p in parts if node in p[1][r, i])
                best = best_partition(
                    of_node, lam, min_child_weight, max_cat_to_onehot, max_cat_threshold
                )
                if not np.isfinite(best) or best <= 0:
                    # no legal candidate by the reference's sums: the program's
                    # set stands on a child at the edge of min_child_weight
                    continue
                got = set_gain(of_node, codes, bool(tree["default_left"][node]), lam)
                best_gains.append(best)
                lost.append(best - got)
                regrets.append((best - got) / best)
        loss = sum(p[2][r] for p in parts) / n
        worst["loss_abs"] = max(worst["loss_abs"], abs(loss - logged_loss[r]))
    worst["cat_partition_regret"] = float(sum(lost) / sum(best_gains)) if lost else 0.0
    worst["cat_partition_regret_max"] = float(max(regrets, default=0.0))
    worst["cat_partition_regret_median"] = float(np.median(regrets)) if regrets else 0.0
    worst["cat_partition_regret_p99"] = float(np.quantile(regrets, 0.99)) if regrets else 0.0
    return worst


def column_cardinalities(x, feature_types):
    """Categories of each column of the training rows, 0 for a numeric one:
    one more than the largest code it holds."""
    out = []
    for f, kind in enumerate(feature_types):
        column = x[:, f] if kind == "c" else None
        held = None if column is None else column[~np.isnan(column)]
        out.append(int(held.max()) + 1 if held is not None and len(held) else 0)
    return out


def exact_checks(trees, feature_types, cardinality, max_cat_to_onehot, max_cat_threshold):
    """What every tree of the forest holds exactly, as counts of faults:

    * ``ordinal_split_on_categorical``: threshold splits on a categorical
      column (a program that trains a category's code as a number);
    * ``cat_set_invalid``: sets that are empty, sit on a numeric column,
      hold a code that is none of their column's, hold every category of
      the column, or hold more than ``max_cat_threshold`` (the scanned side
      is the set);
    * ``cat_onehot_rule_broken``: sets of more than one category on a column
      of fewer than ``max_cat_to_onehot`` categories.
    """
    ordinal = invalid = onehot = 0
    for tree in trees:
        sets = tree.get("categories", {})
        for node in np.flatnonzero(tree["left"] >= 0):
            f = int(tree["feature"][node])
            is_cat = 0 <= f < len(feature_types) and feature_types[f] == "c"
            if node not in sets:
                ordinal += int(is_cat)
                continue
            codes = np.unique(np.asarray(sets[node], np.int64))
            C = int(cardinality[f]) if is_cat else 0
            invalid += int(
                not is_cat
                or len(codes) == 0
                or codes.min() < 0
                or codes.max() >= C
                or len(codes) >= C
                or len(codes) > max_cat_threshold
            )
            onehot += int(is_cat and C < max_cat_to_onehot and len(codes) != 1)
    return {
        "ordinal_split_on_categorical": ordinal,
        "cat_set_invalid": invalid,
        "cat_onehot_rule_broken": onehot,
    }
