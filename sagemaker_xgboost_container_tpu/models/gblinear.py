"""gblinear booster: elastic-net linear model trained by parallel coordinate
descent ("shotgun") — one jitted update per boosting round.

The reference validates booster=gblinear with updaters shotgun/coord_descent
(hyperparameter_validation.py:45-55) and delegates to libxgboost's linear
updater. Here each round updates every coordinate simultaneously from the
current gradients (shotgun-style; exact for orthogonal features, converges
with the eta shrinkage otherwise) — a dense [n, d] matvec pair per round that
maps straight onto the MXU, plus the same objective/metric/callback machinery
as the tree path.

Model format: xgboost gblinear JSON (weights laid out feature-major with the
per-group bias at the end), loadable by real xgboost and by our predictor.
"""

import jax
import jax.numpy as jnp
import numpy as np

from ..toolkit import exceptions as exc
from . import objectives as objectives_mod


class LinearModel:
    """Host-side gblinear model: weights [d, G] + bias [G]."""

    def __init__(self, weights, bias, objective_name, base_score, num_feature, num_class=0,
                 objective_params=None):
        self.weights = np.asarray(weights, np.float32)
        self.bias = np.asarray(bias, np.float32)
        self.objective_name = objective_name
        self.objective_params = dict(objective_params or {})
        self.base_score = float(base_score)
        self.num_feature = int(num_feature)
        self.num_class = int(num_class)
        self.attributes = {}
        self.rounds = 0

    @property
    def num_output_group(self):
        return max(1, self.num_class)

    @property
    def num_boosted_rounds(self):
        return self.rounds

    def objective(self):
        params = dict(self.objective_params)
        if self.num_class:
            params.setdefault("num_class", self.num_class)
        return objectives_mod.create_objective(self.objective_name, params)

    def predict_margin(self, features, iteration_range=None):
        obj = self.objective()
        base = obj.base_margin(self.base_score)
        x = np.nan_to_num(np.asarray(features, np.float32), nan=0.0)
        if x.shape[1] < self.num_feature:
            x = np.pad(x, ((0, 0), (0, self.num_feature - x.shape[1])))
        elif x.shape[1] > self.num_feature:
            x = x[:, : self.num_feature]
        margin = x @ self.weights + self.bias[None, :] + base
        if self.num_output_group == 1:
            return margin[:, 0]
        return margin

    def predict(self, features, output_margin=False, iteration_range=None):
        margin = self.predict_margin(features)
        if output_margin:
            return margin
        return self.objective().margin_to_prediction(margin)

    # ------------------------------------------------------------------ json
    def save_json(self):
        import json

        G = self.num_output_group
        flat = []
        for f in range(self.num_feature):
            flat.extend(float(self.weights[f, g]) for g in range(G))
        flat.extend(float(b) for b in self.bias)
        attributes = dict(self.attributes)
        attributes.setdefault("num_boosted_rounds", str(self.rounds))
        doc = {
            "version": [3, 0, 0],
            "learner": {
                "attributes": attributes,
                "feature_names": [],
                "feature_types": [],
                "gradient_booster": {
                    "model": {"param": {}, "weights": flat},
                    "name": "gblinear",
                },
                "learner_model_param": {
                    "base_score": repr(self.base_score),
                    "num_class": str(self.num_class),
                    "num_feature": str(self.num_feature),
                    "num_target": "1",
                },
                "objective": {"name": self.objective_name},
            },
        }
        return json.dumps(doc)

    def save_model(self, path):
        with open(path, "w") as f:
            f.write(self.save_json())

    @classmethod
    def from_dict(cls, doc):
        learner = doc["learner"]
        lmp = learner["learner_model_param"]
        num_feature = int(lmp.get("num_feature", 0))
        num_class = int(lmp.get("num_class", 0))
        G = max(1, num_class)
        flat = np.asarray(learner["gradient_booster"]["model"]["weights"], np.float32)
        weights = flat[: num_feature * G].reshape(num_feature, G)
        bias = flat[num_feature * G : num_feature * G + G]
        from .forest import _parse_base_score

        model = cls(
            weights,
            bias,
            objective_name=learner["objective"]["name"],
            base_score=_parse_base_score(lmp.get("base_score", 0.5)),
            num_feature=num_feature,
            num_class=num_class,
        )
        model.attributes = dict(learner.get("attributes", {}))
        try:
            model.rounds = int(model.attributes.pop("num_boosted_rounds", 0))
        except (TypeError, ValueError):
            model.rounds = 0
        return model


def train_linear(
    config, dtrain, num_boost_round, evals=(), feval=None, callbacks=None,
    initial_model=None, mesh=None,
):
    """Train a gblinear model; mirrors booster.train's loop contract.

    initial_model: a LinearModel to continue from (checkpoint resume).
    mesh: optional Mesh with a "data" axis — rows shard across devices and
    the per-coordinate sufficient statistics (x_j·g, x_j²·h, bias sums)
    psum across the axis, so every device runs identical weight updates
    (the reference trains gblinear under Rabit the same way: allreduced
    gradient sums in libxgboost's linear updater)."""
    from .booster import _eval_metric_names

    callbacks = list(callbacks or [])
    objective = objectives_mod.create_objective(config.objective, config.objective_params)
    objective.validate_labels(dtrain.labels)
    G = objective.num_output_group

    n, d = dtrain.num_row, dtrain.num_col
    x_host = np.nan_to_num(dtrain.features, nan=0.0)  # linear path: missing = 0

    # multi-process: each host holds its own row shard; arrays assemble into
    # global arrays over the whole mesh (the same contract as the tree
    # booster — reference parity: libxgboost's linear updater allreduces its
    # gradient sums under Rabit exactly like hist does). Anything other
    # than a cross-host data mesh would silently train divergent per-host
    # models — refuse loudly.
    is_multiproc = jax.process_count() > 1
    if is_multiproc and (
        mesh is None
        or "data" not in getattr(mesh, "axis_names", ())
        or int(mesh.shape["data"]) <= 1
    ):
        raise exc.UserError(
            "Multi-process booster=gblinear training requires a mesh with a "
            "'data' axis spanning the hosts."
        )

    n_shards = 1
    axis = None
    if mesh is not None and "data" in getattr(mesh, "axis_names", ()):
        n_shards = int(mesh.shape["data"])
        if n_shards > 1:
            axis = "data"
    grad_hess = objective.grad_hess
    if axis is not None and config.objective == "survival:cox":
        # Cox risk sets span the whole dataset; inside shard_map the plain
        # grad_hess would see only shard-local rows and silently compute
        # wrong risk sets. Same recipe as the tree path's cox-on-mesh
        # (booster.py cox_mesh_grad_hess): all_gather the global rows,
        # compute replicated global gradients (padding rows carry weight 0
        # and drop out of every cumsum), slice this shard's segment. Exact
        # where the reference's per-worker Cox approximation is not.
        base_grad_hess = grad_hess

        def cox_mesh_grad_hess(m, y, wt):
            M = jax.lax.all_gather(m, axis, tiled=True)
            Y = jax.lax.all_gather(y, axis, tiled=True)
            Wt = jax.lax.all_gather(wt, axis, tiled=True)
            Gg, Hh = base_grad_hess(M, Y, Wt)
            k = jax.lax.axis_index(axis)
            c = m.shape[0]
            return (
                jax.lax.dynamic_slice(Gg, (k * c,), (c,)),
                jax.lax.dynamic_slice(Hh, (k * c,), (c,)),
            )

        grad_hess = cox_mesh_grad_hess

    from .booster import _pad_rows

    # pad divisor: LOCAL data shards in a multi-process run (each host lays
    # out only its own rows); whole-mesh data shards otherwise
    pad_unit = (
        max(1, int(mesh.local_mesh.shape["data"])) if is_multiproc else n_shards
    )
    n_pad = -(-n // pad_unit) * pad_unit
    if is_multiproc:
        # hosts may hold UNEVEN row counts: agree on one local padded size
        # so the assembled global array has uniform device shards
        from jax.experimental import multihost_utils

        n_pad = int(
            np.asarray(
                multihost_utils.process_allgather(np.asarray([n_pad], np.int64))
            ).max()
        )
    if n_pad != n:
        # zero-weight padding rows: contribute nothing to any psum'd stat
        x_host = _pad_rows(x_host, n_pad, 0.0)
    xT_host = np.ascontiguousarray(x_host.T)
    labels_np = _pad_rows(np.asarray(dtrain.labels, np.float32), n_pad, 0.0)
    weights_np = _pad_rows(np.asarray(dtrain.get_weight(), np.float32), n_pad, 0.0)
    if axis is not None:
        # place each array in its shard_map layout ONCE; jnp.asarray would
        # commit them to the default device and every round's dispatch would
        # re-scatter ~3x the dataset
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        def put(arr, spec):
            sharding = NamedSharding(mesh, spec)
            if is_multiproc:
                return jax.make_array_from_process_local_data(
                    sharding, np.asarray(arr)
                )
            return jax.device_put(jnp.asarray(arr), sharding)

        x = put(x_host, P("data", None))
        xT = put(xT_host, P(None, "data"))
        xT_sq = put(xT_host**2, P(None, "data"))
        lab_spec = P("data") if labels_np.ndim == 1 else P("data", None)
        labels = put(labels_np, lab_spec)
        weights_row = put(weights_np, P("data"))
    else:
        x = jnp.asarray(x_host)
        xT = jnp.asarray(xT_host)
        xT_sq = xT**2
        labels = jnp.asarray(labels_np)
        weights_row = jnp.asarray(weights_np)
    del x_host, xT_host
    n = n_pad
    base = objective.base_margin(config.base_score)

    lambda_ = config.reg_lambda
    alpha = config.alpha
    eta = config.eta
    lambda_bias = float(config.objective_params.get("lambda_bias", 0.0))

    if initial_model is not None:
        w = jnp.asarray(initial_model.weights.reshape(d, G))
        b = jnp.asarray(initial_model.bias.reshape(G))
        start_round = initial_model.num_boosted_rounds
    else:
        w = jnp.zeros((d, G), jnp.float32)
        b = jnp.zeros(G, jnp.float32)
        start_round = 0

    def _round_body(x_s, xT_s, xT_sq_s, labels_s, weights_s, wc, bc):
        """Sequential coordinate descent (xgboost's coord_descent updater):
        grad/hess computed once per round, then per-coordinate updates with
        the per-row gradient adjusted incrementally (g += h * x_j * delta) —
        stable under correlated features where simultaneous shotgun updates
        diverge. The coordinate sweep is a lax.scan over features, fully
        on-device. Row-dim inputs may be a data-axis shard: every sum over
        rows psums so all shards compute identical updates."""
        n_s = x_s.shape[0]
        m = x_s @ wc + bc[None, :] + base
        margins = m[:, 0] if G == 1 else m
        g, h = grad_hess(margins, labels_s, weights_s)
        g2 = g.reshape(n_s, G) if G > 1 else g[:, None]
        h2 = h.reshape(n_s, G) if G > 1 else h[:, None]

        def allsum(v):
            return jax.lax.psum(v, axis) if axis is not None else v

        def step(g_cur, inputs):
            x_j, x2_j, w_j = inputs          # [n_s], [n_s], [G]
            gw = allsum(x_j @ g_cur) + lambda_ * w_j    # [G]
            hw = allsum(x2_j @ h2) + lambda_            # [G]
            raw = w_j - gw / hw
            new_w = jnp.sign(raw) * jnp.maximum(jnp.abs(raw) - alpha / hw, 0.0)
            delta = eta * (new_w - w_j)
            g_cur = g_cur + h2 * x_j[:, None] * delta[None, :]
            return g_cur, w_j + delta

        g2, new_w = jax.lax.scan(step, g2, (xT_s, xT_sq_s, wc))
        gb = allsum(g2.sum(axis=0)) + lambda_bias * bc
        hb = allsum(h2.sum(axis=0)) + lambda_bias
        bc = bc - eta * gb / jnp.maximum(hb, 1e-6)
        return new_w, bc

    if axis is not None:
        from jax.sharding import PartitionSpec as P

        lab_spec = P("data") if labels.ndim == 1 else P("data", None)
        # graftlint: disable=trace-uncached-jit — session-scope construction: one linear round program per train call
        one_round_sharded = jax.jit(
            jax.shard_map(
                _round_body,
                mesh=mesh,
                in_specs=(
                    P("data", None), P(None, "data"), P(None, "data"),
                    lab_spec, P("data"), P(None, None), P(None),
                ),
                out_specs=(P(None, None), P(None)),
                check_vma=False,
            )
        )

        def one_round(wc, bc):
            return one_round_sharded(x, xT, xT_sq, labels, weights_row, wc, bc)

    else:

        @jax.jit
        def one_round(wc, bc):
            return _round_body(x, xT, xT_sq, labels, weights_row, wc, bc)

    model = LinearModel(
        np.zeros((d, G)), np.zeros(G),
        objective_name=config.objective,
        base_score=config.base_score,
        num_feature=d,
        num_class=config.num_class,
        objective_params={
            k: v for k, v in config.objective_params.items()
            if k in ("scale_pos_weight", "num_class", "lambda_bias")
        },
    )
    metric_names = _eval_metric_names(config, objective)

    _rows_cache = {}

    def _eval_round():
        """One round's metric lines: host evaluation with the shared
        cross-host combine (identical lines on every host — same semantics
        as the tree booster's evaluate())."""
        from .booster import evaluate_host_lines

        results = evaluate_host_lines(
            ((name, dm, model.predict_margin(dm.features)) for dm, name in evals),
            metric_names,
            feval,
            objective,
            G,
            config.objective_params,
            is_multiproc,
            global_rows_cache=_rows_cache,
        )
        for name, metric, value in results:
            evals_log.setdefault(name, {}).setdefault(metric, []).append(value)

    model.rounds = start_round
    evals_log = {}
    stop = False
    # full callback protocol, like the gbtree loop (booster.py): RoundTimer's
    # round-0 timestamp and phase recorder are armed in before_training
    for cb in callbacks:
        if hasattr(cb, "before_training"):
            model = cb.before_training(model) or model
    for rnd in range(start_round, start_round + num_boost_round):
        w, b = one_round(w, b)
        model.weights = np.asarray(w)
        model.bias = np.asarray(b)
        model.rounds = rnd + 1
        _eval_round()
        for cb in callbacks:
            if hasattr(cb, "after_iteration") and cb.after_iteration(model, rnd, evals_log):
                stop = True
        if stop:
            break
    for cb in callbacks:
        if hasattr(cb, "after_training"):
            model = cb.after_training(model) or model
    return model
