"""``gbt_reference`` for rows given as scipy CSR: numpy, float64, no jax.

The same teacher-forced check and the same numbers (``gbt_reference`` says
what each is and why; ``benchmark/README-sparse.md`` what they mean for a
program that bundles columns): rows are routed by the judged tree's own
splits on the raw values, a cell the row does not hold by the split's
``default_left`` (an absent cell is a missing value, as a stored NaN is),
and every stored number is recomputed from the reference's own margins,
gradients and sums. Nothing of the program is imported or read but the
forest it returned and the loss it logged.

No dense matrix of the rows is ever made. A forest names a few hundred of
the columns; a block of rows is *compacted* to those columns alone (float32
``[rows, named columns]``, NaN where the row holds no value), and every tree
is routed through that block by ``gbt_reference.route`` itself, its features
renumbered. So the work runs block by block, each block through all judged
rounds: the margins of a block's rows are its own, and the sums of a judged
tree's nodes and of the loss add up over the blocks. The objective's
gradients, the loss and the formulas of the gaps are ``gbt_reference``'s.
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark.reference import gbt_reference
from benchmark.reference.gbt_reference import _score

BLOCK_FLOATS = 1 << 26  # a compacted block holds at most this many floats (256 MiB)
BLOCK_ROWS = 1 << 17    # and this many rows: enough blocks for every thread


def compact(x, slot_of, width):
    """The CSR block ``x`` as dense float32 ``[rows, width]``: column ``f`` at
    ``slot_of[f]`` (left out where that is negative), NaN where absent."""
    slot = slot_of[x.indices]
    keep = np.flatnonzero(slot >= 0)
    rows = np.repeat(np.arange(x.shape[0]), np.diff(x.indptr))[keep]
    dense = np.full((x.shape[0], width), np.nan, np.float32)
    dense[rows, slot[keep]] = x.data[keep]
    return dense


def path_sums(path, n_nodes, g, h):
    """``gbt_reference.node_sums`` of one block, given its rows' ``path``
    (``route``'s): per node the sums of ``g``, ``h``, ``|g|`` and ``h*h``."""
    weights = (g, h, np.abs(g), h * h)
    sums = np.zeros((4, n_nodes))
    seen = np.zeros(n_nodes, bool)
    for node in path:  # a row that stopped early repeats its leaf
        fresh = ~seen[node]
        if fresh.any():
            at = node[fresh]
            for i, w in enumerate(weights):
                sums[i] += np.bincount(at, weights=w[fresh], minlength=n_nodes)
        seen[node] = True
    return sums


def tree_gaps(tree, sums, eta, lam, max_depth):
    """``gbt_reference.check_tree``'s gaps from a tree's node ``sums``."""
    G, H, A, Q = sums
    depth = gbt_reference.node_depths(tree)
    is_leaf = tree["left"] < 0
    hess_gap = np.abs(tree["sum_hess"].astype(np.float64) - H)
    leaf_gap = np.abs(tree["value"].astype(np.float64) + eta * G / (H + lam)) / (
        eta * (A + 1e-30) / (H + lam)
    )
    out = {name: 0.0 for name in gbt_reference.NUMBERS}
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


def check_rounds(
    rounds, check_at, x, label, objective, base_score, eta, lam, max_depth, logged_loss
):
    """``gbt_reference.check_rounds`` over CSR rows: margins before a judged
    round come from the reference's own traversal of every earlier tree."""
    n, d = x.shape
    last = max(check_at)
    num_group = max(1 + max(c for rnd in rounds for c, _t in rnd), 1)
    start = gbt_reference.base_margin(objective, base_score)
    label = label.astype(np.float64)
    # the columns the judged prefix of the forest names, and its trees over them
    used = [t["feature"][t["left"] >= 0] for rnd in rounds[: last + 1] for _c, t in rnd]
    named = np.unique(np.concatenate(used)) if used else np.empty(0, np.int64)
    named = named[(named >= 0) & (named < d)]
    slot_of = np.full(d, -1, np.int64)
    slot_of[named] = np.arange(len(named))
    compacted = [
        [(c, dict(t, feature=slot_of[np.clip(t["feature"], 0, d - 1)])) for c, t in rnd]
        for rnd in rounds[: last + 1]
    ]

    def block(lo, hi):
        rows = compact(x[lo:hi], slot_of, max(len(named), 1))
        y = label[lo:hi]
        margin = np.full((hi - lo, num_group), start, np.float64)
        sums, losses = {}, {}
        for r, rnd in enumerate(compacted):
            paths = [gbt_reference.route(tree, rows) for _c, tree in rnd]
            if r in check_at:
                m = margin[:, 0] if num_group == 1 else margin
                g, h = gbt_reference.grad_hess(objective, m, y)
                for i, (c, tree) in enumerate(rnd):
                    gc, hc = (g, h) if num_group == 1 else (g[:, c], h[:, c])
                    sums[r, i] = path_sums(paths[i], len(tree["left"]), gc, hc)
            for (c, tree), path in zip(rnd, paths):
                margin[:, c] += tree["value"].astype(np.float64).take(path[-1])
            if r in check_at:
                m = margin[:, 0] if num_group == 1 else margin
                losses[r] = (hi - lo) * gbt_reference.loss(objective, m, y)
        return sums, losses

    # (a split that names a column off the matrix fails splits_off_own_cuts,
    # not this routing: it reads column 0 or the last)
    step = min(max(BLOCK_FLOATS // max(len(named), 1), 1024), BLOCK_ROWS)
    blocks = [(lo, min(lo + step, n)) for lo in range(0, n, step)]
    with ThreadPoolExecutor(max_workers=gbt_reference.THREADS) as pool:
        parts = list(pool.map(lambda b: block(*b), blocks))
    worst = {"loss_abs": 0.0}
    for r in sorted(check_at):
        for i, (_c, tree) in enumerate(rounds[r]):
            sums = sum(p[0][r, i] for p in parts)
            for k, v in tree_gaps(tree, sums, eta, lam, max_depth).items():
                worst[k] = max(worst.get(k, 0.0), v)
        loss = sum(p[1][r] for p in parts) / n
        worst["loss_abs"] = max(worst["loss_abs"], abs(loss - logged_loss[r]))
    return worst


PAIR_CHECK_MAX_VALUES = 1 << 16  # distinct values up to which every pair is tried
VALUES_PROBE = 1 << 18           # entries of a column read first to tell


def splits_off_own_cuts(trees, x):
    """Splits that do not name a column of ``x`` and a threshold that is a cut
    of that column's own present values: the midpoint (float32, as a sketch
    computes it) of two of them, or for a column of one value ``v`` the one
    cut ``v + 1`` above it. A bundled program's split is a position of a bin
    column shared by many columns: one mapped back to the wrong member or
    the wrong bin names another column's cut or none. A column of more than
    ``PAIR_CHECK_MAX_VALUES`` distinct values (a continuous one: trying every
    pair for every threshold would take hours over millions of values) is
    held to what every midpoint keeps: it lies inside the column's range."""
    wanted = {}
    bad = 0
    for tree in trees:
        for node in np.flatnonzero(tree["left"] >= 0):
            f = int(tree["feature"][node])
            if not 0 <= f < x.shape[1]:
                bad += 1
                continue
            wanted.setdefault(f, set()).add(np.float32(tree["threshold"][node]))
    if not wanted:
        return bad
    columns = x[:, sorted(wanted)].tocsc()
    for j, f in enumerate(sorted(wanted)):
        held = columns.data[columns.indptr[j]: columns.indptr[j + 1]]
        held = held[~np.isnan(held)].astype(np.float32)
        if len(np.unique(held[:VALUES_PROBE])) > PAIR_CHECK_MAX_VALUES:
            low, high = held.min(), held.max()
            bad += sum(not low <= t <= high for t in wanted[f])
            continue
        values = np.unique(held)
        bad += sum(not _is_cut(values, t) for t in wanted[f])
    return bad


def _is_cut(values, t):
    """Whether ``t`` is ``(a + b) / 2`` in float32 for two of the ascending
    distinct ``values``, or ``v + 1`` for their only one."""
    if len(values) == 0:
        return False
    if len(values) == 1:
        return bool(t == np.float32(values[0] + np.float32(1.0)))
    below = values[values < t]
    # b near 2t - a; the float32 sum rounds, so the neighbours are tried too
    at = np.searchsorted(values, 2.0 * np.float64(t) - below.astype(np.float64))
    for shift in (-1, 0, 1):
        b = values[np.clip(at + shift, 0, len(values) - 1)]
        if np.any((b > below) & ((below + b) * np.float32(0.5) == t)):
            return True
    return False
