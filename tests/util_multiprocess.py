"""Spawn-safe workers for multi-process jax.distributed tests."""


def distributed_train_worker(rank, world, port, q):
    """One process of a 2-process CPU 'pod': trains on its own row shard."""
    import os

    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(
        coordinator_address="127.0.0.1:{}".format(port),
        num_processes=world,
        process_id=rank,
    )
    import numpy as np
    from jax.sharding import Mesh

    from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
    from sagemaker_xgboost_container_tpu.models import train

    rng = np.random.RandomState(0)
    X = rng.rand(800, 4).astype(np.float32)
    y = (3 * X[:, 0] + np.sin(5 * X[:, 1])).astype(np.float32)
    half = 400
    lo, hi = rank * half, (rank + 1) * half
    dtrain = DataMatrix(X[lo:hi], labels=y[lo:hi])

    devices = np.array(jax.devices())  # 4 global devices (2 per process)
    mesh = Mesh(devices, axis_names=("data",))

    forest = train(
        {"max_depth": 3, "eta": 0.3, "max_bin": 64, "seed": 1},
        dtrain,
        num_boost_round=5,
        mesh=mesh,
    )
    preds = forest.predict(X[:50])
    q.put((rank, np.asarray(preds)))


def distributed_metrics_worker(rank, world, port, q):
    """2-process pod: device metrics must be globally exact and identical on
    every host; feval rides the host weighted-mean
    combine and must also agree across hosts."""
    import os

    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(
        coordinator_address="127.0.0.1:{}".format(port),
        num_processes=world,
        process_id=rank,
    )
    import numpy as np
    from jax.sharding import Mesh

    from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
    from sagemaker_xgboost_container_tpu.models import train

    rng = np.random.RandomState(0)
    X = rng.rand(800, 4).astype(np.float32)
    y = ((X[:, 0] + X[:, 1]) > 1.0).astype(np.float32)
    Xv = rng.rand(200, 4).astype(np.float32)
    yv = ((Xv[:, 0] + Xv[:, 1]) > 1.0).astype(np.float32)
    half, vhalf = 400, 100
    dtrain = DataMatrix(
        X[rank * half : (rank + 1) * half], labels=y[rank * half : (rank + 1) * half]
    )
    dval = DataMatrix(
        Xv[rank * vhalf : (rank + 1) * vhalf],
        labels=yv[rank * vhalf : (rank + 1) * vhalf],
    )

    devices = np.array(jax.devices())
    mesh = Mesh(devices, axis_names=("data",))

    def recorder(log):
        class Rec:
            def after_iteration(self, model, epoch, evals_log):
                log.update(
                    {k: {m: list(v) for m, v in d.items()} for k, d in evals_log.items()}
                )
                return False

        return Rec()

    params = {
        "objective": "binary:logistic",
        "max_depth": 3,
        "max_bin": 64,
        "seed": 1,
        "eval_metric": ["logloss", "error"],
        "_rounds_per_dispatch": 5,
    }
    dev_log = {}
    forest = train(
        params, dtrain, num_boost_round=5,
        evals=[(dtrain, "train"), (dval, "validation")],
        callbacks=[recorder(dev_log)], mesh=mesh,
    )
    # exactness oracle: recompute the global metrics of the final model over
    # the FULL datasets host-side; the last device line must match
    check = {}
    for tag, (Xf, yf) in (("train", (X, y)), ("validation", (Xv, yv))):
        p = np.clip(np.asarray(forest.predict(Xf)), 1e-7, 1 - 1e-7)
        check[tag + "_logloss"] = float(
            -np.mean(yf * np.log(p) + (1 - yf) * np.log(1 - p))
        )
        check[tag + "_error"] = float(np.mean((p > 0.5) != yf))

    # host-combined path: a feval forces host-side evaluation
    def feval(margin, dm):
        p = 1.0 / (1.0 + np.exp(-margin))
        return [("myacc", float(np.mean((p > 0.5) == dm.labels)))]

    host_log = {}
    params_host = dict(params)
    params_host.pop("_rounds_per_dispatch")
    forest3 = train(
        params_host, dtrain, num_boost_round=3,
        evals=[(dtrain, "train")], feval=feval,
        callbacks=[recorder(host_log)], mesh=mesh,
    )
    # mixed watchlist (decomposable + feval): the decomposable ones must
    # STILL be globally exact (combined from partial stats, not from a
    # weighted mean of per-host values)
    p3 = np.clip(np.asarray(forest3.predict(X)), 1e-7, 1 - 1e-7)
    check["host3_logloss"] = float(
        -np.mean(y * np.log(p3) + (1 - y) * np.log(1 - p3))
    )
    q.put((rank, dev_log, host_log, check))


def cox_metrics_worker(rank, world, port, q):
    """2-process pod with survival:cox + watchlist (r3 parity debt): the
    cox-nloglik lines must be globally exact and identical on every host —
    both on the device scan path (K>1) and the host evaluate() path (feval
    forces it)."""
    import os

    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(
        coordinator_address="127.0.0.1:{}".format(port),
        num_processes=world,
        process_id=rank,
    )
    import numpy as np
    from jax.sharding import Mesh

    from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
    from sagemaker_xgboost_container_tpu.models import train
    from sagemaker_xgboost_container_tpu.models.eval_metrics import cox_nloglik

    rng = np.random.RandomState(31)
    n = 800
    X = rng.rand(n, 4).astype(np.float32)
    hazard = np.exp(0.8 * X[:, 0] - 0.5 * X[:, 1])
    times = rng.exponential(1.0 / hazard).astype(np.float32) + 0.01
    censored = rng.rand(n) < 0.3
    y = np.where(censored, -times, times).astype(np.float32)
    # UNEVEN shards (401 vs 399): the host evaluate() gather pads to the max
    # local length with weight-0 rows — the NaN hazard the r4 review caught
    lo, hi = (0, 401) if rank == 0 else (401, n)
    dtrain = DataMatrix(X[lo:hi], labels=y[lo:hi])
    # separate validation set, also UNEVEN (121 vs 119): eval-set padding
    # must be cross-process agreed too or its global row gathers mismatch
    Xv = rng.rand(240, 4).astype(np.float32)
    hv = np.exp(0.8 * Xv[:, 0] - 0.5 * Xv[:, 1])
    tv = rng.exponential(1.0 / hv).astype(np.float32) + 0.01
    yv = np.where(rng.rand(240) < 0.3, -tv, tv).astype(np.float32)
    vlo, vhi = (0, 121) if rank == 0 else (121, 240)
    dval = DataMatrix(Xv[vlo:vhi], labels=yv[vlo:vhi])
    mesh = Mesh(np.array(jax.devices()), axis_names=("data",))

    def recorder(log):
        class Rec:
            def after_iteration(self, model, epoch, evals_log):
                log.update(
                    {k: {m: list(v) for m, v in d.items()} for k, d in evals_log.items()}
                )
                return False

        return Rec()

    params = {
        "objective": "survival:cox",
        "max_depth": 3,
        "eta": 0.3,
        "seed": 3,
        "_rounds_per_dispatch": 3,
    }
    dev_log = {}
    forest = train(
        params, dtrain, num_boost_round=6,
        evals=[(dtrain, "train"), (dval, "validation")],
        callbacks=[recorder(dev_log)], mesh=mesh,
    )
    # oracle: global metric of the final model over the COMBINED rows
    check = {
        "train_cox": cox_nloglik(
            np.asarray(forest.predict(X), np.float64), y
        ),
        "val_cox": cox_nloglik(
            np.asarray(forest.predict(Xv), np.float64), yv
        ),
    }

    # host evaluate() path: a feval forces host-side evaluation, where
    # cox-nloglik must ride the process_allgather global-rows branch
    def feval(margin, dm):
        return [("mmean", float(np.mean(margin)))]

    host_log = {}
    params_host = dict(params)
    params_host.pop("_rounds_per_dispatch")
    forest2 = train(
        params_host, dtrain, num_boost_round=3,
        evals=[(dtrain, "train"), (dval, "validation")], feval=feval,
        callbacks=[recorder(host_log)], mesh=mesh,
    )
    check["host3_cox"] = cox_nloglik(
        np.asarray(forest2.predict(X), np.float64), y
    )
    check["host3_val_cox"] = cox_nloglik(
        np.asarray(forest2.predict(Xv), np.float64), yv
    )
    q.put((rank, dev_log, host_log, check))


def gblinear_worker(rank, world, port, q):
    """2-process pod training booster=gblinear (r4 parity lift): coordinate
    descent with psum'd sufficient statistics across hosts — previously a
    UserError. UNEVEN shards (301 vs 299); watchlist lines must be identical
    across hosts and the weights must match a single-device oracle over the
    combined rows."""
    import os

    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(
        coordinator_address="127.0.0.1:{}".format(port),
        num_processes=world,
        process_id=rank,
    )
    import numpy as np
    from jax.sharding import Mesh

    from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
    from sagemaker_xgboost_container_tpu.models import train

    rng = np.random.RandomState(7)
    n = 600
    X = rng.randn(n, 5).astype(np.float32)
    beta = np.asarray([1.0, -2.0, 0.5, 0.0, 3.0], np.float32)
    y = (X @ beta + 0.1 * rng.randn(n)).astype(np.float32)
    lo, hi = (0, 301) if rank == 0 else (301, n)
    dtrain = DataMatrix(X[lo:hi], labels=y[lo:hi])
    mesh = Mesh(np.array(jax.devices()), axis_names=("data",))

    log = {}

    class Rec:
        def after_iteration(self, model, epoch, evals_log):
            log.update(
                {k: {m: list(v) for m, v in d.items()} for k, d in evals_log.items()}
            )
            return False

    params = {"booster": "gblinear", "eta": 0.5, "reg_lambda": 0.1, "eval_metric": "rmse"}
    model = train(
        params, dtrain, num_boost_round=20,
        evals=[(dtrain, "train")], callbacks=[Rec()], mesh=mesh,
    )
    preds = np.asarray(model.predict(X[:32]))
    q.put((rank, preds, log["train"]["rmse"]))


def dart_worker(rank, world, port, q):
    """2-process pod training booster=dart (r4 parity lift): per-round
    dropout draws ride the shared seed so hosts drop identical trees; the
    GSPMD-partitioned builder psums histograms. Both hosts must produce
    identical predictions and watchlist lines."""
    import os

    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(
        coordinator_address="127.0.0.1:{}".format(port),
        num_processes=world,
        process_id=rank,
    )
    import numpy as np
    from jax.sharding import Mesh

    from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
    from sagemaker_xgboost_container_tpu.models import train

    rng = np.random.RandomState(3)
    n = 800
    X = rng.rand(n, 4).astype(np.float32)
    y = (3 * X[:, 0] + np.sin(5 * X[:, 1])).astype(np.float32)
    lo, hi = (0, 401) if rank == 0 else (401, n)  # uneven shards
    dtrain = DataMatrix(X[lo:hi], labels=y[lo:hi])
    mesh = Mesh(np.array(jax.devices()), axis_names=("data",))

    log = {}

    class Rec:
        def after_iteration(self, model, epoch, evals_log):
            log.update(
                {k: {m: list(v) for m, v in d.items()} for k, d in evals_log.items()}
            )
            return False

    params = {
        "booster": "dart",
        "max_depth": 3,
        "eta": 0.3,
        "seed": 5,
        "rate_drop": 0.3,
        "eval_metric": "rmse",
    }
    model = train(
        params, dtrain, num_boost_round=8,
        evals=[(dtrain, "train")], callbacks=[Rec()], mesh=mesh,
    )
    preds = np.asarray(model.predict(X[:32]))
    q.put((rank, preds, log["train"]["rmse"]))


def update_worker(rank, world, port, q):
    """2-process pod running process_type=update (r4 parity lift): each host
    routes its own UNEVEN row shard through the base model; per-node stats
    allgather-sum so both hosts refresh/prune to identical trees — and they
    must equal a single-device update over the combined rows."""
    import os

    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(
        coordinator_address="127.0.0.1:{}".format(port),
        num_processes=world,
        process_id=rank,
    )
    import numpy as np
    from jax.sharding import Mesh

    from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
    from sagemaker_xgboost_container_tpu.models import train

    rng = np.random.RandomState(9)
    n = 600
    X = rng.rand(n, 4).astype(np.float32)
    y = (3 * X[:, 0] + np.sin(5 * X[:, 1])).astype(np.float32)
    # identical base model on every host (same full data + seed, no mesh)
    base = train(
        {"max_depth": 4, "eta": 0.3, "seed": 1, "gamma": 0.0},
        DataMatrix(X, labels=y),
        num_boost_round=4,
    )
    # fresh rows for the update job, sharded UNEVENLY across the hosts; the
    # mesh is the required sharding signal for the cross-host stat combine
    mesh = Mesh(np.array(jax.devices()), axis_names=("data",))
    X2 = rng.rand(500, 4).astype(np.float32)
    y2 = (3 * X2[:, 0] + np.sin(5 * X2[:, 1])).astype(np.float32)
    lo, hi = (0, 251) if rank == 0 else (251, 500)
    refreshed = train(
        {
            "max_depth": 4,
            "eta": 0.3,
            "process_type": "update",
            "updater": "refresh,prune",
            "gamma": 0.1,
            "eval_metric": "rmse",
        },
        DataMatrix(X2[lo:hi], labels=y2[lo:hi]),
        num_boost_round=4,
        evals=[(DataMatrix(X2[lo:hi], labels=y2[lo:hi]), "train")],
        xgb_model=base,
        mesh=mesh,
    )
    preds = np.asarray(refreshed.predict(X2[:32]))
    q.put((rank, preds))


def host_loss_worker(rank, world, port, q):
    """2-process pod where rank 1 dies mid-train (simulated host loss /
    preemption). Contract under test: the SURVIVOR
    must terminate with an error within ~heartbeat_timeout — the job fails
    loudly instead of hanging in the psum or continuing on partial data.
    Recovery is restart + checkpoint resume (test_resume_from_checkpoint)."""
    import os

    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

    import jax

    jax.config.update("jax_platforms", "cpu")
    # older jax (the >=0.4.30 contract floor) has no heartbeat kwarg — gate
    # it exactly as the production path does (algorithm_train.py); without
    # it the runtime default applies and the test just takes longer
    import inspect

    kwargs = {}
    if "heartbeat_timeout_seconds" in inspect.signature(
        jax.distributed.initialize
    ).parameters:
        kwargs["heartbeat_timeout_seconds"] = 10
    jax.distributed.initialize(
        coordinator_address="127.0.0.1:{}".format(port),
        num_processes=world,
        process_id=rank,
        **kwargs,
    )
    import numpy as np
    from jax.sharding import Mesh

    from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
    from sagemaker_xgboost_container_tpu.models import train

    rng = np.random.RandomState(0)
    X = rng.rand(800, 4).astype(np.float32)
    y = (3 * X[:, 0] + np.sin(5 * X[:, 1])).astype(np.float32)
    half = 400
    lo, hi = rank * half, (rank + 1) * half
    dtrain = DataMatrix(X[lo:hi], labels=y[lo:hi])
    mesh = Mesh(np.array(jax.devices()), axis_names=("data",))

    class DieMidTrain:
        def after_iteration(self, model, epoch, evals_log):
            if rank == 1 and epoch == 2:
                q.put(("died", rank, epoch))
                q.close()
                q.join_thread()  # flush the feeder thread before the hard kill
                os._exit(9)  # simulated preemption: no shutdown handshake
            return False

    q.put(("started", rank, None))
    train(
        {"max_depth": 3, "eta": 0.3, "max_bin": 64, "seed": 1},
        dtrain,
        num_boost_round=400,  # far more rounds than the survivor can finish
        callbacks=[DieMidTrain()],
        mesh=mesh,
    )
    # only reachable if the job survived peer loss — the contract violation
    q.put(("completed", rank, None))


def distributed_2d_mesh_worker(rank, world, port, q):
    """2 processes x (2 data x 2 feature) mesh: the data axis spans hosts,
    the feature axis stays within each host. Trains with
    colsample + monotone active."""
    import os

    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(
        coordinator_address="127.0.0.1:{}".format(port),
        num_processes=world,
        process_id=rank,
    )
    import numpy as np
    from jax.sharding import Mesh

    from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
    from sagemaker_xgboost_container_tpu.models import train

    rng = np.random.RandomState(0)
    X = rng.rand(800, 5).astype(np.float32)
    y = (3 * X[:, 0] + np.sin(5 * X[:, 1]) + X[:, 3]).astype(np.float32)
    half = 400
    lo, hi = rank * half, (rank + 1) * half
    dtrain = DataMatrix(X[lo:hi], labels=y[lo:hi])

    devices = np.array(jax.devices()).reshape(2, 2)  # [data, feature]
    mesh = Mesh(devices, axis_names=("data", "feature"))

    forest = train(
        {
            "max_depth": 3,
            "eta": 0.3,
            "max_bin": 64,
            "seed": 1,
            "colsample_bylevel": 0.7,
            "monotone_constraints": [1, 0, 0, 0, 0],
        },
        dtrain,
        num_boost_round=6,
        mesh=mesh,
    )
    preds = forest.predict(X[:64])
    q.put((rank, np.asarray(preds)))
