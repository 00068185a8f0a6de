"""process_type=update: refresh / prune an existing model on (new) data.

The reference validates process_type=update with updater in {refresh, prune}
(hyperparameter_validation.py:56-58) and delegates to libxgboost's
TreeRefresher/TreePruner. Semantics mirrored here:

* iteration i processes the loaded model's iteration-i trees (no new trees);
  gradients are computed at the margins of the trees processed so far, like
  normal boosting (num_boost_round caps at the model's round count);
* ``refresh``: re-route the training rows through each tree, rebuild every
  node's (sum_g, sum_h), store sum_hess, recompute internal-node gain
  (0.5*(score_L + score_R - score_parent), xgboost's stored loss_chg
  convention) and — when refresh_leaf (default 1) — replace leaf values with
  eta * optimal weight from the fresh stats;
* ``prune``: bottom-up collapse of internal nodes whose both children are
  leaves and whose gain < gamma; the collapsed node becomes a leaf valued
  eta * its base weight. Stats are ALWAYS recomputed from the update data
  first (leaf values only touched when refresh was requested): stored gains
  follow different conventions per model source (this builder stores
  0.5*delta, the split's own loss change, ops/tree_build.py; a model it
  saved before it did so stores 0.5*delta - gamma_train; imported xgboost
  models store raw loss_chg), so comparing them directly against the update
  job's gamma would double-count gamma or over-prune — one recomputed
  convention makes prune consistent for every model source.

Runs host-side except row routing (the compiled forest kernel): update jobs
are one pass over num_round trees, not a boosting loop — throughput is
bounded by routing, which stays on device.
"""

import numpy as np

from ..ops.predict import forest_leaf_nodes
from ..toolkit import exceptions as exc


def _node_depth_order(tree):
    """Node indices deepest-first (children before parents).

    Root's parent is xgboost's 2147483647 marker (forest.py
    _parents_from_children); any out-of-range parent means "no parent".
    Child indices exceed their parent's in our layouts, so the forward pass
    sees parents before children.
    """
    n = tree.num_nodes
    depth = np.zeros(n, np.int32)
    for node in range(n):
        p = tree.parent[node]
        if 0 <= p < n and p != node:
            depth[node] = depth[p] + 1
    return np.argsort(-depth, kind="stable"), depth


def _score(g, h, reg_lambda, alpha):
    t = np.sign(g) * np.maximum(np.abs(g) - alpha, 0.0)
    return (t * t) / (h + reg_lambda)


def _refresh_tree(tree, leaf_of_row, g, h, config, refresh_leaf, combine=None):
    """Rebuild node stats from rows routed to each leaf; returns the tree's
    per-row contribution after any leaf-value update. ``combine`` (multi-
    host) sums the per-leaf stats across processes — the refresh analog of
    libxgboost TreeRefresher's rabit allreduce of node stats."""
    n_nodes = tree.num_nodes
    G = np.zeros(n_nodes, np.float64)
    H = np.zeros(n_nodes, np.float64)
    np.add.at(G, leaf_of_row, g)
    np.add.at(H, leaf_of_row, h)
    if combine is not None:
        GH = combine(np.stack([G, H]))
        G, H = GH[0], GH[1]
    order, _depth = _node_depth_order(tree)
    for node in order:  # children accumulate into parents (deepest first)
        p = tree.parent[node]
        if 0 <= p < n_nodes and p != node:
            G[p] += G[node]
            H[p] += H[node]

    lam, alpha = config.reg_lambda, config.alpha
    weight = -np.sign(G) * np.maximum(np.abs(G) - alpha, 0.0) / (H + lam)
    if config.max_delta_step > 0:
        weight = np.clip(weight, -config.max_delta_step, config.max_delta_step)

    tree.sum_hess = H.astype(np.float32)
    tree.base_weight = weight.astype(np.float32)
    is_leaf = tree.is_leaf
    internal = ~is_leaf
    l, r = tree.left, tree.right
    gain = np.zeros(n_nodes, np.float32)
    gain[internal] = 0.5 * (
        _score(G[l[internal]], H[l[internal]], lam, alpha)
        + _score(G[r[internal]], H[r[internal]], lam, alpha)
        - _score(G[internal], H[internal], lam, alpha)
    )
    tree.gain = gain
    if refresh_leaf:
        tree.value = np.where(
            is_leaf, (config.eta * weight).astype(np.float32), tree.value
        )


def _prune_tree(tree, gamma, eta):
    """Bottom-up: collapse internal nodes with two leaf children and
    gain < gamma into leaves valued eta * base_weight."""
    order, _depth = _node_depth_order(tree)
    is_leaf = tree.is_leaf.copy()
    pruned = 0
    for node in order:
        if is_leaf[node]:
            continue
        l, r = tree.left[node], tree.right[node]
        if is_leaf[l] and is_leaf[r] and tree.gain[node] < gamma:
            is_leaf[node] = True
            tree.left[node] = -1
            tree.right[node] = -1
            tree.value[node] = eta * tree.base_weight[node]
            pruned += 1
    return pruned


def train_update(config, forest, dtrain, evals, feval, callbacks, num_boost_round, mesh=None):
    """Apply refresh/prune updaters to ``forest`` over ``dtrain``."""
    updaters = [
        u.strip()
        for u in str(config.objective_params.get("updater", "refresh")).split(",")
        if u.strip()
    ]
    bad = [u for u in updaters if u not in ("refresh", "prune")]
    if bad:
        raise exc.UserError(
            "process_type 'update' can only be used with updater 'refresh' and 'prune'"
        )
    refresh_leaf = int(config.objective_params.get("refresh_leaf", 1) or 0)
    if not forest.trees:
        raise exc.UserError(
            "process_type='update' needs an existing model to update "
            "(provide a checkpoint / base_model)."
        )
    import jax

    # multi-host: each host routes its own row shard; per-node (sum_g,
    # sum_h) combine across hosts before the refresh/prune math, so every
    # host applies identical updates (reference parity: libxgboost's
    # TreeRefresher allreduces node stats under Rabit — with replicated
    # channels rows count once per host there too). Requires the cross-host
    # data mesh as the sharding signal; a multi-process run without one
    # would silently refresh divergent per-host models, so refuse loudly.
    # Transport is f32 (x64 is off), summation host-side in f64 — same
    # policy as the metric combine.
    combine = None
    if jax.process_count() > 1:
        if (
            mesh is None
            or "data" not in getattr(mesh, "axis_names", ())
            or int(mesh.shape["data"]) <= 1
        ):
            raise exc.UserError(
                "Multi-process process_type='update' requires a mesh with a "
                "'data' axis spanning the hosts."
            )
        from jax.experimental import multihost_utils

        def combine(stats):
            return np.asarray(
                multihost_utils.process_allgather(stats.astype(np.float32)),
                np.float64,
            ).sum(axis=0)

    objective = forest.objective()
    objective.validate_labels(dtrain.labels)
    G_out = forest.num_output_group
    n = dtrain.num_row
    x = np.asarray(dtrain.features, np.float32)
    labels = np.asarray(dtrain.labels, np.float32)
    weights = np.asarray(dtrain.get_weight(), np.float32)
    base = objective.base_margin(forest.base_score)
    margins = (
        np.full(n, base, np.float32)
        if G_out == 1
        else np.full((n, G_out), base, np.float32)
    )

    rounds = min(num_boost_round, forest.num_boosted_rounds)
    from .booster import _eval_metric_names

    metric_names = _eval_metric_names(config, objective)
    evals_log = {}
    _rows_cache = {}  # round-invariant global labels/weights (cox gather)
    stop = False
    # full callback protocol, like the gbtree loop (booster.py): RoundTimer's
    # round-0 timestamp and phase recorder are armed in before_training
    for cb in callbacks:
        if hasattr(cb, "before_training"):
            forest = cb.before_training(forest) or forest
    for rnd in range(rounds):
        g, h = objective.grad_hess(margins, labels, weights)
        g = np.asarray(g, np.float64)
        h = np.asarray(h, np.float64)
        t0, t1 = forest.iteration_indptr[rnd], forest.iteration_indptr[rnd + 1]
        stacked = forest._stack(slice(t0, t1))
        leaf_nodes = np.asarray(forest_leaf_nodes(stacked, x))  # [n, T_iter]
        for j, t in enumerate(range(t0, t1)):
            tree = forest.trees[t]
            cls = forest.tree_info[t]
            g_c = g if g.ndim == 1 else g[:, cls]
            h_c = h if h.ndim == 1 else h[:, cls]
            # stats always recomputed (one gain convention for prune);
            # leaf values only replaced when refresh was requested
            _refresh_tree(
                tree, leaf_nodes[:, j], g_c, h_c, config,
                refresh_leaf and "refresh" in updaters,
                combine=combine,
            )
            if "prune" in updaters:
                _prune_tree(tree, config.gamma, config.eta)
        forest._stacked_cache = None
        # margins advance with the UPDATED trees (leaf re-lookup: pruning
        # may have collapsed the routing)
        stacked = forest._stack(slice(t0, t1))
        leaf_nodes = np.asarray(forest_leaf_nodes(stacked, x))
        for j, t in enumerate(range(t0, t1)):
            contrib = forest.trees[t].value[leaf_nodes[:, j]]
            if G_out == 1:
                margins += contrib
            else:
                margins[:, forest.tree_info[t]] += contrib

        from .booster import evaluate_host_lines

        results = evaluate_host_lines(
            (
                (
                    name,
                    dm,
                    forest.predict_margin(
                        np.asarray(dm.features, np.float32),
                        iteration_range=(0, rnd + 1),
                    ),
                )
                for dm, name in evals
            ),
            metric_names,
            feval,
            objective,
            G_out,
            config.objective_params,
            combine is not None,
            global_rows_cache=_rows_cache,
        )
        for data_name, metric_name, value in results:
            evals_log.setdefault(data_name, {}).setdefault(metric_name, []).append(value)
        for cb in callbacks:
            if hasattr(cb, "after_iteration") and cb.after_iteration(
                forest, rnd, evals_log
            ):
                stop = True
        if stop:
            break
    for cb in callbacks:
        if hasattr(cb, "after_training"):
            forest = cb.after_training(forest) or forest
    return forest
