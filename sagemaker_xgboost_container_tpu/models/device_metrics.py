"""Device-side (jnp) eval metrics as psum-able partial statistics.

Every metric decomposes into a fixed-size statistics vector that combines
across data shards by plain summation (``lax.psum`` over the "data" mesh
axis) plus a cheap ``finalize`` that turns combined stats into the scalar.
This is what lets boosting rounds batch K-at-a-time (`_rounds_per_dispatch`)
*on a mesh* and makes multi-host metric lines globally exact: the reference
allreduces metrics inside xgb.train under the communicator
(reference distributed.py:219), so every host prints the same value — here
the psum of (numerator, denominator) pairs inside the jitted round does the
same job.

Weighted formulations throughout: padding rows carry weight 0, so they drop
out of every metric automatically.

AUC is the one metric that does not decompose exactly: following xgboost's
own distributed semantics, each shard computes its local weighted
Mann-Whitney AUC and shards combine as a weighted average with weight
(local positive weight x local negative weight). Single-shard runs are
exact.
"""

import jax.numpy as jnp

_EPS = 1e-15


class DeviceMetric:
    """A decomposable metric: ``partial`` -> psum-able f32 [size] -> ``finalize``.

    ``needs_global_rows`` marks the one exception (cox-nloglik): its partial
    is NOT shard-decomposable — the caller must all_gather the row shards
    over the data axis, call ``partial`` on the replicated global arrays,
    and divide by the axis size so the shared downstream psum restores the
    global value (mirroring the booster's Cox gradient path, which gathers
    global risk sets the same way).

    ``needs_groups`` marks a metric that is a mean over query groups (ndcg):
    its ``partial`` takes a fourth argument, the ``ops.ranking.GroupLayout``
    of the rows, and returns (sum of the per-group values, groups)."""

    def __init__(self, name, size, partial, finalize, needs_global_rows=False,
                 needs_groups=False):
        self.name = name
        self.size = size
        self.partial = partial
        self.finalize = finalize
        self.needs_global_rows = needs_global_rows
        self.needs_groups = needs_groups

    def __call__(self, margins, labels, weights):
        return self.finalize(self.partial(margins, labels, weights))


def _sigmoid(m):
    return 1.0 / (1.0 + jnp.exp(-m))


def _softmax(m):
    e = jnp.exp(m - jnp.max(m, axis=1, keepdims=True))
    return e / e.sum(axis=1, keepdims=True)


def _prob_transform(objective_name, margins):
    if objective_name in ("reg:logistic", "binary:logistic"):
        return _sigmoid(margins)
    if objective_name in ("count:poisson", "reg:gamma", "reg:tweedie", "survival:aft", "survival:cox"):
        return jnp.exp(margins)
    return margins


def _weighted_mean_metric(name, objective_name, term_fn, post=None):
    """Metric = post(sum(w * term) / sum(w)); stats vector [num, den]."""

    def partial(m, y, w):
        p = _prob_transform(objective_name, m)
        return jnp.stack([jnp.sum(term_fn(p, y, w) * w), jnp.sum(w)])

    def finalize(stats):
        mean = stats[0] / jnp.maximum(stats[1], _EPS)
        return post(mean) if post is not None else mean

    return DeviceMetric(name, 2, partial, finalize)


def grouped_ndcg(name, k=None):
    """``ndcg`` / ``ndcg@k`` over a ``GroupLayout``: per group DCG over ideal
    DCG with gain ``2^label - 1`` and discount ``1 / log2(1 + rank)``, ranks
    by margin descending with ties broken by position (a stable argsort's
    order), a group without a relevant document counted as 1 — the semantics
    of ``eval_metrics.ndcg``, which ignores weights too. Padding slots carry
    no gain and rank last, so they add nothing.

    A round gathers the margins alone: the gains and each group's ideal DCG
    at ``k`` are the layout's (``ops/ranking.py::SlotColumns``, cutoff
    ``k or 0``; ``ndcg_cutoffs`` names the cutoffs a layout has to carry)."""
    from ..ops import ranking

    def per_group(S, gains, valid, ideal):
        ranks = ranking.rank_descending(S, valid)
        terms = gains * ranking.dcg_discount(ranks)
        if k:
            terms = jnp.where(ranks <= k, terms, 0.0)
        dcg = terms.sum(axis=1)
        ndcg = jnp.where(ideal > 0, dcg / jnp.where(ideal > 0, ideal, 1.0), 1.0)
        held = valid.any(axis=1)  # groups added to fill a chunk hold nothing
        return jnp.where(held, ndcg, 0.0), held.astype(jnp.float32)

    def partial(m, y, w, layout):
        total = count = layout.empty_groups  # the host counts an empty group as 1
        for index, slots in zip(layout.indices, layout.slots):
            _valid, S = ranking.gather_groups(index, (m,), (0.0,))
            ndcg, held = ranking.map_group_chunks(
                per_group,
                (S, slots.gains, slots.valid, slots.ideal_dcg[k or 0]),
                fills=(0.0, 0.0, False, 0.0),
            )
            total = total + ndcg.sum()
            count = count + held.sum()
        return jnp.stack([total, count])

    return DeviceMetric(
        name, 2, partial, lambda s: s[0] / jnp.maximum(s[1], _EPS), needs_groups=True
    )


def ndcg_cutoffs(names):
    """The ``ideal_dcg`` cutoffs the grouped metrics among ``names`` read off
    a layout (0: the whole group), ascending."""
    cutoffs = set()
    for name in names:
        base, _, suffix = name.partition("@")
        if base == "ndcg":
            cutoffs.add(int(float(suffix)) if suffix else 0)
    return tuple(sorted(cutoffs))


def make_device_metric(name, objective_name, num_group=1, params=None):
    """-> DeviceMetric, or None if unsupported on device."""
    params = params or {}
    base, _, suffix = name.partition("@")

    if base == "ndcg":
        return grouped_ndcg(name, ndcg_cutoffs([name])[0])

    if num_group > 1:
        if base == "merror":
            def term(m, y, w):
                pred = jnp.argmax(m, axis=1)
                return (pred != y.astype(jnp.int32)).astype(jnp.float32)

            def partial(m, y, w):
                return jnp.stack([jnp.sum(term(m, y, w) * w), jnp.sum(w)])

            return DeviceMetric(name, 2, partial, lambda s: s[0] / jnp.maximum(s[1], _EPS))
        if base == "mlogloss":
            def partial(m, y, w):
                p = _softmax(m)
                picked = jnp.take_along_axis(
                    p, y.astype(jnp.int32)[:, None], axis=1
                )[:, 0]
                v = -jnp.log(jnp.clip(picked, _EPS, 1.0))
                return jnp.stack([jnp.sum(v * w), jnp.sum(w)])

            return DeviceMetric(name, 2, partial, lambda s: s[0] / jnp.maximum(s[1], _EPS))
        return None

    wm = lambda term_fn, post=None: _weighted_mean_metric(  # noqa: E731
        name, objective_name, term_fn, post
    )

    if base == "rmse":
        return wm(lambda p, y, w: (p - y) ** 2, post=jnp.sqrt)
    if base == "mse":
        return wm(lambda p, y, w: (p - y) ** 2)
    if base == "mae":
        return wm(lambda p, y, w: jnp.abs(p - y))
    if base == "mape":
        return wm(lambda p, y, w: jnp.abs((y - p) / jnp.maximum(jnp.abs(y), _EPS)))
    if base == "rmsle":
        return wm(
            lambda p, y, w: (jnp.log1p(jnp.maximum(p, 0.0)) - jnp.log1p(y)) ** 2,
            post=jnp.sqrt,
        )
    if base == "logloss":
        def term(p, y, w):
            # f32-safe: clip with an epsilon representable in float32
            eps32 = 1e-7
            p = jnp.clip(p, eps32, 1 - eps32)
            return -(y * jnp.log(p) + (1 - y) * jnp.log(1 - p))

        return wm(term)
    if base == "error":
        threshold = float(suffix) if suffix else 0.5
        return wm(
            lambda p, y, w: ((p > threshold).astype(jnp.float32) != y).astype(
                jnp.float32
            )
        )
    if base == "auc":
        def partial(m, y, w):
            # weighted Mann-Whitney with tie midranks in cumulative-weight
            # space (same formulation as eval_metrics.auc, static shapes:
            # tie groups via neighbor-inequality cumsum + segment reductions)
            p = _prob_transform(objective_name, m)
            n = p.shape[0]
            order = jnp.argsort(p)
            sp, sw = p[order], w[order]
            spos = (y[order] > 0).astype(jnp.float32) * sw
            sneg = (1.0 - (y[order] > 0).astype(jnp.float32)) * sw
            new_group = jnp.concatenate(
                [jnp.ones(1, jnp.int32), (sp[1:] != sp[:-1]).astype(jnp.int32)]
            )
            gid = jnp.cumsum(new_group) - 1
            import jax as _jax

            group_w = _jax.ops.segment_sum(sw, gid, num_segments=n)
            cumw = jnp.cumsum(sw)
            group_end = _jax.ops.segment_max(cumw, gid, num_segments=n)
            midrank = group_end - group_w / 2.0
            ranks = midrank[gid]
            w_pos = jnp.sum(spos)
            w_neg = jnp.sum(sneg)
            u = jnp.sum(ranks * spos) - w_pos * w_pos / 2.0
            pairw = w_pos * w_neg
            auc = jnp.clip(u / jnp.maximum(pairw, _EPS), 0.0, 1.0)
            # shards combine as a pair-weighted average (xgboost's
            # distributed-AUC semantics); exact when single-shard
            return jnp.stack([auc * pairw, pairw])

        return DeviceMetric(name, 2, partial, lambda s: s[0] / jnp.maximum(s[1], _EPS))
    if base == "poisson-nloglik":
        def term(p, y, w):
            from jax.scipy.special import gammaln

            p = jnp.maximum(p, _EPS)
            return p - y * jnp.log(p) + gammaln(y + 1.0)

        return wm(term)
    if base == "gamma-nloglik":
        def term(p, y, w):
            p = jnp.maximum(p, _EPS)
            return jnp.log(p) + y / p

        return wm(term)
    if base == "gamma-deviance":
        def term(p, y, w):
            p = jnp.maximum(p, _EPS)
            yy = jnp.maximum(y, _EPS)
            return jnp.log(p / yy) + yy / p - 1.0

        return wm(term, post=lambda x: 2.0 * x)
    if base == "cox-nloglik":
        def partial(m, y, w):
            # negative Breslow partial log-likelihood (device form of
            # eval_metrics.cox_nloglik): labels < 0 = censored at |t|,
            # hazard ratio = exp(margin); risk sets are cumulative sums
            # over the descending-time ordering. Padding rows (weight 0)
            # contribute nothing to either the risk sets or the events.
            p = jnp.exp(m)
            abs_t = jnp.abs(y)
            event = (y > 0).astype(jnp.float32)
            order = jnp.argsort(-abs_t)  # stable, matches the host metric
            hz = jnp.maximum(p, 1e-30)[order] * w[order]
            cum = jnp.cumsum(hz)
            ev = (event * w)[order]
            ll = jnp.sum(
                ev
                * (jnp.log(jnp.maximum(hz, 1e-30)) - jnp.log(jnp.maximum(cum, 1e-30)))
            )
            return jnp.stack([-ll, jnp.sum(ev)])

        return DeviceMetric(
            name,
            2,
            partial,
            lambda s: s[0] / jnp.maximum(s[1], 1e-12),
            needs_global_rows=True,
        )
    if base == "tweedie-nloglik":
        rho = float(suffix) if suffix else float(params.get("tweedie_variance_power", 1.5))

        def term(p, y, w):
            p = jnp.maximum(p, _EPS)
            a = y * jnp.power(p, 1 - rho) / (1 - rho)
            b = jnp.power(p, 2 - rho) / (2 - rho)
            return -a + b

        return wm(term)
    return None


def all_supported(names, objective_name, num_group, params=None, grouped=False):
    """The metrics as device functions, or None where one cannot run there.
    ``grouped``: the caller has a ``GroupLayout`` for every set of rows."""
    fns = [make_device_metric(n, objective_name, num_group, params) for n in names]
    if any(f is None or (f.needs_groups and not grouped) for f in fns):
        return None
    return fns
