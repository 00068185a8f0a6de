"""Plain reference for gradient-boosted trees: numpy, float64, no jax.

Imports nothing of the program and takes nothing it computed except the
model it is asked to judge: trees as plain arrays (feature, threshold,
left, right, value, gain, sum_hess per node, xgboost node order) and the raw
float rows. The check is teacher-forced: rows are routed by the judged
tree's own splits on the raw floats, so a near-tie between two candidate
splits cannot fail it, and every stored number is then recomputed from the
reference's own margins, gradients and sums:

* ``sum_hess`` of each node against the hessian sum of the rows routed to it;
* each leaf's value against ``-eta * G / (H + lambda)``;
* each internal node's ``gain`` against
  ``0.5 * (GL^2/(HL+l) + GR^2/(HR+l) - G^2/(H+l))`` of its own split;
* the training loss the program logged for that round against the loss of
  the reference's own margins after the round.

``check_tree`` says which forms of these gaps are judged and why.
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np

ROW_BLOCK = 1 << 19
THREADS = 8
EPS = 1e-16        # the program's hessian floor (xgboost's kRtEps)
LOGLOSS_CLIP = 1e-7  # the program's f32-safe clip inside logloss


def _over_row_blocks(fn, n_rows):
    """``fn(lo, hi)`` over blocks of rows, on a few threads (numpy lets go of
    the interpreter lock inside ``take`` and ``bincount``): the cells hold
    tens of millions of rows and the check has to stay shorter than the window."""
    blocks = [(lo, min(lo + ROW_BLOCK, n_rows)) for lo in range(0, n_rows, ROW_BLOCK)]
    with ThreadPoolExecutor(max_workers=THREADS) as pool:
        return list(pool.map(lambda b: fn(*b), blocks))


def route(tree, x):
    """The node of every row at each depth, the last entry being its leaf.
    Goes right when ``v >= threshold``; NaN follows ``default_left``."""
    n, d = x.shape
    flat = x.reshape(-1)
    base = np.arange(n, dtype=np.int64) * d
    feature = np.maximum(tree["feature"], 0)  # a leaf's feature is never read
    node = np.zeros(n, np.int64)
    path = [node]
    while True:
        internal = tree["left"].take(node) >= 0
        if not internal.any():
            return path
        v = flat.take(base + feature.take(node))
        right = v >= tree["threshold"].take(node)  # float32 both: exact
        missing = np.isnan(v)
        if missing.any():
            right = np.where(missing, ~tree["default_left"].take(node), right)
        nxt = np.where(right, tree["right"].take(node), tree["left"].take(node))
        node = np.where(internal, nxt, node)
        path.append(node)


def tree_margin(tree, x):
    value = tree["value"].astype(np.float64)
    parts = _over_row_blocks(lambda lo, hi: value.take(route(tree, x[lo:hi])[-1]), len(x))
    return np.concatenate(parts)


def grad_hess(objective, margin, label):
    """Per row (binary) or per row and class (softmax), float64."""
    if objective == "binary:logistic":
        p = 1.0 / (1.0 + np.exp(-margin))
        return p - label, np.maximum(p * (1.0 - p), EPS)
    if objective in ("multi:softmax", "multi:softprob"):
        p = softmax(margin)
        onehot = label[:, None] == np.arange(margin.shape[1])[None, :]
        return p - onehot, np.maximum(2.0 * p * (1.0 - p), EPS)
    raise ValueError("the reference has no objective {!r}".format(objective))


def softmax(margin):
    e = np.exp(margin - margin.max(axis=1, keepdims=True))
    return e / e.sum(axis=1, keepdims=True)


def loss(objective, margin, label):
    """The loss the configuration names as its eval_metric, float64."""
    if objective == "binary:logistic":
        p = np.clip(1.0 / (1.0 + np.exp(-margin)), LOGLOSS_CLIP, 1.0 - LOGLOSS_CLIP)
        return float(np.mean(-(label * np.log(p) + (1.0 - label) * np.log(1.0 - p))))
    p = softmax(margin)
    picked = p[np.arange(len(label)), label.astype(np.int64)]
    return float(np.mean(-np.log(np.clip(picked, EPS, 1.0))))


def base_margin(objective, base_score):
    if objective == "binary:logistic":
        return np.log(base_score / (1.0 - base_score))
    return 0.5  # the softmax objective's constant start


def _score(g, h, lam):
    return g * g / (h + lam)


def node_depths(tree):
    depth = np.zeros(len(tree["left"]), np.int64)
    for node in range(len(depth)):  # xgboost order: parents first
        if tree["left"][node] >= 0:
            depth[tree["left"][node]] = depth[node] + 1
            depth[tree["right"][node]] = depth[node] + 1
    return depth


def node_sums(tree, x, g, h):
    """Per node, over the rows the tree's own splits send to it: the sums of
    ``g``, ``h``, ``|g|`` and ``h*h``, float64."""
    n_nodes = len(tree["left"])

    def block(lo, hi):
        gb, hb = g[lo:hi], h[lo:hi]
        weights = (gb, hb, np.abs(gb), hb * hb)
        sums = np.zeros((4, n_nodes))
        seen = np.zeros(n_nodes, bool)
        for node in route(tree, x[lo:hi]):  # a row that stopped early repeats its leaf
            fresh = ~seen[node]
            if fresh.any():
                at = node[fresh]
                for i, w in enumerate(weights):
                    sums[i] += np.bincount(at, weights=w[fresh], minlength=n_nodes)
            seen[node] = True
        return sums

    return sum(_over_row_blocks(block, len(x)))


def check_tree(tree, x, g, h, eta, lam, max_depth):
    """Gaps of one tree's stored numbers against float64 sums of ``g`` and
    ``h`` over the rows its own splits send to each node.

    The program sums a node in one of three ways, and each is judged by what
    it can keep. Leaves at ``max_depth`` come from a float32 total of their
    rows: ``leaf_sum_hess_rel`` and ``leaf_value_err`` hold them to about
    1e-6. Nodes above come from the level histogram, one child of a pair
    summed directly and the other by subtraction from its parent, so the
    subtracted child inherits the absolute error of sums over a million rows
    and a three-row node can be off by a tenth: those are reported
    (``sum_hess_rel``, ``gain_err``, ``leaf_value_err_all``) but only their
    steady forms are judged. ``direct_hess_err`` takes of each sibling pair
    the child that agrees better (the one summed directly), measures its gap
    in units of the root-sum-square of its rows' hessians, which is what
    rounding each term to m bits leaves whatever the node's size, and takes
    the median over the pairs: about 2.5e-5 with the default two-pass bf16
    histogram, about 8e-3 with one bf16 pass (PERF.md section 2).
    ``gain_err_median`` is the median gap of a stored gain, against the size
    of the terms it is a difference of (``A = sum |g|`` standing in for G).
    """
    G, H, A, Q = node_sums(tree, x, g, h)
    depth = node_depths(tree)
    is_leaf = tree["left"] < 0
    hess_gap = np.abs(tree["sum_hess"].astype(np.float64) - H)
    leaf_gap = np.abs(tree["value"].astype(np.float64) + eta * G / (H + lam)) / (
        eta * (A + 1e-30) / (H + lam)
    )
    out = {name: 0.0 for name in NUMBERS}
    out["sum_hess_rel"] = float(np.max(hess_gap / np.maximum(H, 1.0)))
    out["leaf_value_err_all"] = float(np.max(leaf_gap[is_leaf]))
    deepest = is_leaf & (depth == max_depth)
    if deepest.any():
        out["leaf_sum_hess_rel"] = float(np.max((hess_gap / np.maximum(H, 1.0))[deepest]))
        out["leaf_value_err"] = float(np.max(leaf_gap[deepest]))
    internal = np.flatnonzero(~is_leaf)
    if len(internal):
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
        from_histogram = depth[left] < max_depth
        if from_histogram.any():
            left, right = left[from_histogram], right[from_histogram]
            rss_gap = np.minimum(
                hess_gap[left] / np.sqrt(Q[left] + 1e-300),
                hess_gap[right] / np.sqrt(Q[right] + 1e-300),
            )
            out["direct_hess_err"] = float(np.median(rss_gap))
            out["direct_hess_err_p90"] = float(np.quantile(rss_gap, 0.9))
            out["direct_hess_err_max"] = float(np.max(rss_gap))
    return out


NUMBERS = (
    "leaf_sum_hess_rel", "leaf_value_err", "direct_hess_err", "direct_hess_err_p90",
    "direct_hess_err_max", "gain_err_median",
    "sum_hess_rel", "gain_err", "leaf_value_err_all",
)


def tree_depth(tree):
    return int(node_depths(tree).max())


def check_rounds(
    rounds, check_at, x, label, objective, base_score, eta, lam, max_depth, logged_loss
):
    """Judge rounds ``check_at`` of ``rounds`` (a list, one entry per round,
    each a list of (class id, tree)). Margins before a judged round come from
    the reference's own traversal of every earlier tree. Returns the worst
    gap of each kind over the judged rounds and trees."""
    num_group = max(1 + max(c for rnd in rounds for c, _t in rnd), 1)
    start = base_margin(objective, base_score)
    margin = np.full((len(x), num_group), start, np.float64)
    label = label.astype(np.float64)
    worst = {"loss_abs": 0.0}
    for r in range(max(check_at) + 1):
        if r in check_at:
            m = margin[:, 0] if num_group == 1 else margin
            parts = _over_row_blocks(
                lambda lo, hi: grad_hess(objective, m[lo:hi], label[lo:hi]), len(x)
            )
            g, h = (np.concatenate([p[i] for p in parts]) for i in (0, 1))
            for c, tree in rounds[r]:
                gc, hc = (g, h) if num_group == 1 else (g[:, c], h[:, c])
                for k, v in check_tree(tree, x, gc, hc, eta, lam, max_depth).items():
                    worst[k] = max(worst.get(k, 0.0), v)
        for c, tree in rounds[r]:
            margin[:, c] += tree_margin(tree, x)
        if r in check_at:
            m = margin[:, 0] if num_group == 1 else margin
            parts = _over_row_blocks(
                lambda lo, hi: (hi - lo) * loss(objective, m[lo:hi], label[lo:hi]), len(x)
            )
            worst["loss_abs"] = max(
                worst["loss_abs"], abs(sum(parts) / len(x) - logged_loss[r])
            )
    return worst
