"""Plain reference for loss-guided (leaf-wise, best-first) tree growth:
numpy, float64, no jax, nothing of the program.

Two halves.

**The judge** of a returned forest, teacher-forced as
``gbt_reference.check_tree`` is (rows are routed by the judged tree's own
splits on the raw floats; every stored number is recomputed from the
reference's own margins, gradients and sums), for trees that are not heaps
and have no ``max_depth``. In a loss-guided build *every* node's sums come
from a split step's histogram: one child of each sibling pair is summed
directly and the other is its parent's histogram less that, and the parent may
itself be a subtracted child, as many times over as the tree is deep. So the
histogram gaps (``direct_hess_err``, its 90th percentile and its widest) are
taken over every sibling pair of the tree, the better child of each, and
``gain_err_median`` over every split; a leaf's ``sum_hess`` and value are held
where the leaf is the directly summed child of its pair, and only reported
for the subtracted ones, which inherit the absolute error of their ancestors'
sums. Two checks are exact: no tree has more than ``max_leaves`` leaves, and
growth was best-first. The second reads the order of expansion off the node
ids, which xgboost's loss-guided updater (and ``compact_padded_tree`` since
PR 42) hands out as nodes are made: the split that made nodes ``2t + 1`` and
``2t + 2`` was step ``t``. A split taken while a leaf that was split later
held a strictly larger stored gain is a violation.

**A plain grower** (``grow``): best-first growth in float64 over a bin matrix
and cut counts handed to it, by lists of row indices, each candidate scanned
from its node's own rows: no histogram cache, no subtraction, no kernel. The
tests compare the program's tree with it.
"""

import numpy as np

from benchmark.reference.gbt_reference import (
    _over_row_blocks,
    _score,
    base_margin,
    grad_hess,
    loss,
    node_sums,
    tree_margin,
)

MIN_SPLIT_LOSS = 1e-6  # xgboost's kRtEps: a split has to win more than this

NUMBERS = (
    "leaf_sum_hess_rel", "leaf_value_err", "direct_hess_err", "direct_hess_err_p90",
    "direct_hess_err_max", "gain_err_median",
    "sum_hess_rel", "gain_err", "leaf_value_err_all", "leaf_sum_hess_rel_subtracted",
)


# ------------------------------------------------------------------ the judge
def leaves(tree):
    return int(np.count_nonzero(tree["left"] < 0))


def expansion_steps(tree):
    """Internal nodes in the order they were split, read off the ids of the
    children each made (step t made ``2t + 1`` and ``2t + 2``)."""
    internal = np.flatnonzero(tree["left"] >= 0)
    return internal[np.argsort(tree["left"][internal], kind="stable")]


def best_first_violations(tree):
    """Splits taken while a node that existed already, and was split later,
    held a strictly larger stored gain. Also counted: a split whose children
    are not the next two ids (the ids are then no order of expansion)."""
    order = expansion_steps(tree)
    gain = tree["gain"].astype(np.float64)
    violations = 0
    for t, node in enumerate(order):
        if tree["left"][node] != 2 * t + 1 or tree["right"][node] != 2 * t + 2:
            violations += 1
            continue
        later = order[t + 1:]
        # nodes made before step t carry ids up to 2t
        waiting = later[later <= 2 * t]
        if len(waiting) and gain[waiting].max() > gain[node]:
            violations += 1
    return violations


def check_tree(tree, x, g, h, eta, lam):
    """Gaps of one loss-guided tree's stored numbers against float64 sums of
    ``g`` and ``h`` over the rows its own splits send to each node (the
    module's docstring says which are judged and why)."""
    G, H, A, Q = node_sums(tree, x, g, h)
    is_leaf = tree["left"] < 0
    hess_gap = np.abs(tree["sum_hess"].astype(np.float64) - H)
    hess_rel = hess_gap / np.maximum(H, 1.0)
    leaf_gap = np.abs(tree["value"].astype(np.float64) + eta * G / (H + lam)) / (
        eta * (A + 1e-30) / (H + lam)
    )
    out = {name: 0.0 for name in NUMBERS}
    out["sum_hess_rel"] = float(np.max(hess_rel))
    out["leaf_value_err_all"] = float(np.max(leaf_gap[is_leaf]))
    internal = np.flatnonzero(~is_leaf)
    if not len(internal):
        return out
    left, right = tree["left"][internal], tree["right"][internal]
    ref_gain = 0.5 * (
        _score(G[left], H[left], lam)
        + _score(G[right], H[right], lam)
        - _score(G[internal], H[internal], lam)
    )
    scale = 0.5 * (
        _score(A[left], H[left], lam)
        + _score(A[right], H[right], lam)
        + _score(A[internal], H[internal], lam)
    )
    gain_gap = np.abs(tree["gain"][internal].astype(np.float64) - ref_gain) / scale
    out["gain_err"] = float(np.max(gain_gap))
    out["gain_err_median"] = float(np.median(gain_gap))
    # of each sibling pair, the child that agrees better: the one summed
    # directly, in units of the root-sum-square of its rows' hessians
    rss_left = hess_gap[left] / np.sqrt(Q[left] + 1e-300)
    rss_right = hess_gap[right] / np.sqrt(Q[right] + 1e-300)
    rss_gap = np.minimum(rss_left, rss_right)
    out["direct_hess_err"] = float(np.median(rss_gap))
    out["direct_hess_err_p90"] = float(np.quantile(rss_gap, 0.9))
    out["direct_hess_err_max"] = float(np.max(rss_gap))
    direct = np.where(rss_left <= rss_right, left, right)
    subtracted = np.where(rss_left <= rss_right, right, left)
    direct_leaves = direct[is_leaf[direct]]
    if len(direct_leaves):
        out["leaf_sum_hess_rel"] = float(np.max(hess_rel[direct_leaves]))
        out["leaf_value_err"] = float(np.max(leaf_gap[direct_leaves]))
    subtracted_leaves = subtracted[is_leaf[subtracted]]
    if len(subtracted_leaves):
        out["leaf_sum_hess_rel_subtracted"] = float(np.max(hess_rel[subtracted_leaves]))
    return out


def check_rounds(rounds, check_at, x, label, objective, base_score, eta, lam, logged_loss):
    """Judge rounds ``check_at`` of ``rounds`` (one entry a round, each a list
    of (class id, tree); one tree a round here). Margins before a judged round
    come from the reference's own traversal of every earlier tree. Returns the
    worst gap of each kind over the judged rounds."""
    margin = np.full(len(x), base_margin(objective, base_score), np.float64)
    label = label.astype(np.float64)
    worst = {"loss_abs": 0.0}
    for r in range(max(check_at) + 1):
        if r in check_at:
            parts = _over_row_blocks(
                lambda lo, hi: grad_hess(objective, margin[lo:hi], label[lo:hi]), len(x)
            )
            g, h = (np.concatenate([p[i] for p in parts]) for i in (0, 1))
            for _c, tree in rounds[r]:
                for k, v in check_tree(tree, x, g, h, eta, lam).items():
                    worst[k] = max(worst.get(k, 0.0), v)
        for _c, tree in rounds[r]:
            margin += tree_margin(tree, x)
        if r in check_at:
            parts = _over_row_blocks(
                lambda lo, hi: (hi - lo) * loss(objective, margin[lo:hi], label[lo:hi]), len(x)
            )
            worst["loss_abs"] = max(
                worst["loss_abs"], abs(sum(parts) / len(x) - logged_loss[r])
            )
    return worst


# ----------------------------------------------------------- the plain grower
def _best_split(bins, rows, g, h, num_cuts, num_bins, lam, min_child_weight):
    """(gain, feature, bin, default_left, G, H) of the best split of the node
    holding ``rows``: rows with ``bin <= b`` go left, the missing bin
    (``num_bins - 1``) goes with the better side (right on a tie). The first
    maximum wins, features outermost."""
    gn, hn = g[rows], h[rows]
    G, H = gn.sum(), hn.sum()
    parent = _score(G, H, lam)
    best = (-np.inf, 0, 0, False)
    missing = num_bins - 1
    for f in range(bins.shape[1]):
        col = bins[rows, f]
        gb = np.bincount(col, weights=gn, minlength=num_bins)
        hb = np.bincount(col, weights=hn, minlength=num_bins)
        for b in range(int(num_cuts[f])):
            gl, hl = gb[: b + 1].sum(), hb[: b + 1].sum()
            sides = [(gl, hl, False), (gl + gb[missing], hl + hb[missing], True)]
            gains = []
            for sgl, shl, _default_left in sides:
                sgr, shr = G - sgl, H - shl
                if shl < min_child_weight or shr < min_child_weight:
                    gains.append(-np.inf)
                else:
                    gains.append(0.5 * (_score(sgl, shl, lam) + _score(sgr, shr, lam) - parent))
            default_left = gains[1] > gains[0]
            gain = gains[1] if default_left else gains[0]
            if gain > best[0]:
                best = (gain, f, b, bool(default_left))
    return best + (G, H)


def grow(bins, num_cuts, g, h, max_leaves, lam, eta, min_child_weight=1.0, max_depth=0):
    """Best-first growth to ``max_leaves`` leaves. ``bins`` int [n, d] with
    the missing bin last (``num_bins - 1`` = ``max(num_cuts)``); returns plain
    arrays in expansion order (step t makes nodes 2t+1, 2t+2): feature, bin,
    default_left, left, right, value, gain, sum_hess."""
    bins = np.asarray(bins, np.int64)
    g, h = np.asarray(g, np.float64), np.asarray(h, np.float64)
    num_bins = int(np.max(num_cuts)) + 1
    rows_of = {0: np.arange(len(bins))}
    depth = {0: 0}
    cand = {0: _best_split(bins, rows_of[0], g, h, num_cuts, num_bins, lam, min_child_weight)}
    nodes = {0: {"left": -1, "right": -1}}
    for t in range(max_leaves - 1):
        open_leaves = sorted(n for n in cand if nodes[n]["left"] < 0)
        gains = [cand[n][0] for n in open_leaves]
        pick = open_leaves[int(np.argmax(gains))]
        gain, f, b, default_left, _G, _H = cand[pick]
        if not gain > MIN_SPLIT_LOSS:
            break
        a_id, b_id = 2 * t + 1, 2 * t + 2
        nodes[pick].update(
            left=a_id, right=b_id, feature=f, bin=b, default_left=default_left, gain=gain
        )
        rows = rows_of.pop(pick)
        col = bins[rows, f]
        right = np.where(col == num_bins - 1, not default_left, col > b)
        for child, child_rows in ((a_id, rows[~right]), (b_id, rows[right])):
            rows_of[child] = child_rows
            depth[child] = depth[pick] + 1
            nodes[child] = {"left": -1, "right": -1}
            cand[child] = _best_split(
                bins, child_rows, g, h, num_cuts, num_bins, lam, min_child_weight
            )
            if max_depth > 0 and depth[child] >= max_depth:
                cand[child] = (-np.inf,) + cand[child][1:]
    n = len(nodes)
    out = {
        "feature": np.zeros(n, np.int64), "bin": np.zeros(n, np.int64),
        "default_left": np.zeros(n, bool), "left": np.full(n, -1, np.int64),
        "right": np.full(n, -1, np.int64), "value": np.zeros(n), "gain": np.zeros(n),
        "sum_hess": np.zeros(n),
    }
    for node, fields in nodes.items():
        _gain, _f, _b, _dl, G, H = cand[node]
        out["sum_hess"][node] = H
        out["left"][node], out["right"][node] = fields["left"], fields["right"]
        if fields["left"] < 0:
            out["value"][node] = -eta * G / (H + lam)
        else:
            for name in ("feature", "bin", "default_left", "gain"):
                out[name][node] = fields[name]
    return out
