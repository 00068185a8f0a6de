"""Tests for metadata generation, handler services, round batching, logging."""

import numpy as np
import pytest

from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
from sagemaker_xgboost_container_tpu.models import train
from sagemaker_xgboost_container_tpu.toolkit.metadata import generate_algorithm_spec


def test_generate_algorithm_spec():
    spec = generate_algorithm_spec("123.dkr.ecr.example/xgboost-tpu:latest")
    ts = spec["TrainingSpecification"]
    assert ts["TrainingImage"].endswith(":latest")
    assert any(hp["Name"] == "num_round" for hp in ts["SupportedHyperParameters"])
    assert any(ch["Name"] == "train" for ch in ts["TrainingChannels"])
    assert any(
        m["Name"] == "validation:rmse" for m in ts["MetricDefinitions"]
    )
    infer = spec["InferenceSpecification"]
    assert "text/csv" in infer["SupportedContentTypes"]


def test_instance_type_fetcher_gate():
    """The pricing-API gate: a supplied fetcher's
    result flows into both specs; a failing or empty fetcher falls back to
    the static registry instead of breaking spec generation."""
    from sagemaker_xgboost_container_tpu.toolkit import metadata as M

    spec = generate_algorithm_spec(
        "img:1", instance_type_fetcher=lambda: ["ml.trn9.48xlarge"]
    )
    assert spec["TrainingSpecification"]["SupportedTrainingInstanceTypes"] == [
        "ml.trn9.48xlarge"
    ]
    assert spec["InferenceSpecification"][
        "SupportedRealtimeInferenceInstanceTypes"
    ] == ["ml.trn9.48xlarge"]

    def boom():
        raise ConnectionError("no egress")

    spec = generate_algorithm_spec("img:1", instance_type_fetcher=boom)
    assert (
        spec["TrainingSpecification"]["SupportedTrainingInstanceTypes"]
        == M.DEFAULT_TRAINING_INSTANCES
    )
    assert M.fetch_instance_types(lambda: [], ["d"]) == ["d"]
    assert M.fetch_instance_types(None, ["d"]) == ["d"]


def test_rounds_per_dispatch_equivalence():
    rng = np.random.RandomState(0)
    X = rng.rand(600, 4).astype(np.float32)
    y = (X[:, 0] * 3 + X[:, 1]).astype(np.float32)
    dtrain = DataMatrix(X, labels=y)
    one = train({"max_depth": 3, "seed": 5}, dtrain, num_boost_round=6)
    batched = train(
        {"max_depth": 3, "seed": 5, "_rounds_per_dispatch": 3},
        dtrain,
        num_boost_round=6,
    )
    assert batched.num_boosted_rounds == 6
    np.testing.assert_allclose(one.predict(X), batched.predict(X), rtol=1e-4, atol=1e-5)
    # non-divisible count: extras are discarded
    ragged = train(
        {"max_depth": 3, "seed": 5, "_rounds_per_dispatch": 4},
        dtrain,
        num_boost_round=6,
    )
    assert ragged.num_boosted_rounds == 6


def test_rounds_per_dispatch_falls_back_with_evals():
    rng = np.random.RandomState(1)
    X = rng.rand(300, 3).astype(np.float32)
    y = X[:, 0].astype(np.float32)
    dtrain = DataMatrix(X, labels=y)
    log = {}

    class Recorder:
        def after_iteration(self, model, epoch, evals_log):
            log.update(evals_log)
            return False

    train(
        {"max_depth": 3, "_rounds_per_dispatch": 5},
        dtrain,
        num_boost_round=4,
        evals=[(dtrain, "train")],
        callbacks=[Recorder()],
    )
    # per-round metrics still produced for all 4 rounds
    assert len(log["train"]["rmse"]) == 4


def test_host_fallback_metrics_every_k_rounds():
    """Metrics outside the device set (a feval here) no longer force the
    fused dispatch back to K=1: the scan keeps K, eval margins ride the
    carry, and host metric lines land once per dispatch at the batch-end
    round — with a committed-forest correction when the final batch
    over-builds (num_boost_round % K != 0)."""
    rng = np.random.RandomState(7)
    X = rng.rand(400, 4).astype(np.float32)
    y = (X[:, 0] > 0.5).astype(np.float32)
    dtrain = DataMatrix(X, labels=y)
    dval = DataMatrix(X[:100], labels=y[:100])

    def feval(margin, dm):
        return [("absmargin", float(np.mean(np.abs(margin))))]

    log = {}
    epochs = []

    class Recorder:
        def after_iteration(self, model, epoch, evals_log):
            fresh = sum(len(v) for d in evals_log.values() for v in d.values())
            if fresh != getattr(self, "_seen", 0):
                self._seen = fresh
                epochs.append(epoch)
            log.update(
                {k: {m: list(v) for m, v in d.items()} for k, d in evals_log.items()}
            )
            return False

    forest = train(
        {"objective": "binary:logistic", "max_depth": 3,
         "_rounds_per_dispatch": 4, "eval_metric": "auc"},
        dtrain,
        num_boost_round=6,
        evals=[(dtrain, "train"), (dval, "validation")],
        callbacks=[Recorder()],
        feval=feval,
    )
    assert forest.num_boosted_rounds == 6
    # one metric line per dispatch: the full batch ends at round 3, the
    # truncated final batch reports at round 5 (the last committed round)
    assert epochs == [3, 5]
    assert len(log["train"]["absmargin"]) == 2
    assert len(log["validation"]["auc"]) == 2
    # the truncated batch's final line comes from the COMMITTED forest, not
    # the over-built device margins (2 trees were discarded)
    committed_margin = np.asarray(forest.predict(X, output_margin=True))
    assert abs(
        log["train"]["absmargin"][-1] - float(np.mean(np.abs(committed_margin)))
    ) < 1e-6


def test_host_fallback_early_stopping_counts_rounds_not_entries():
    """EarlyStopping under the once-per-dispatch cadence: stale rounds make
    no stop decision, and patience is measured in boosting ROUNDS since the
    best iteration — counting fresh entries would multiply
    early_stopping_rounds by K, stale repeats would divide it by K."""
    from sagemaker_xgboost_container_tpu.training.callbacks import EarlyStopping

    es = EarlyStopping(rounds=6, data_name="train", metric_name="rmse",
                       maximize=False)
    evals_log = {"train": {"rmse": [1.0]}}
    assert not es.after_iteration(None, 0, evals_log)
    # 3 stale rounds inside the fused batch: no stagnation accrued
    for epoch in (1, 2, 3):
        assert not es.after_iteration(None, epoch, evals_log)
    assert es.stagnation == 0
    evals_log["train"]["rmse"].append(1.5)  # worse at the next batch end
    assert not es.after_iteration(None, 4, evals_log)
    assert es.stagnation == 4  # 4 rounds since best (round 0), patience 6
    evals_log["train"]["rmse"].append(1.6)  # still worse at round 8
    assert es.after_iteration(None, 8, evals_log)  # 8 rounds >= patience 6
    # per-round cadence is unchanged: rounds-since-best == entry count
    es2 = EarlyStopping(rounds=2, data_name="train", metric_name="rmse",
                        maximize=False)
    log2 = {"train": {"rmse": [1.0]}}
    assert not es2.after_iteration(None, 0, log2)
    log2["train"]["rmse"].append(1.1)
    assert not es2.after_iteration(None, 1, log2)
    log2["train"]["rmse"].append(1.2)
    assert es2.after_iteration(None, 2, log2)


def test_evaluation_monitor_skips_stale_rounds(capsys):
    """EvaluationMonitor prints only rounds that produced fresh entries —
    stale values against a new round index would misreport under the
    fused-dispatch cadence."""
    from sagemaker_xgboost_container_tpu.training.callbacks import (
        EvaluationMonitor,
    )

    mon = EvaluationMonitor()
    evals_log = {"train": {"rmse": [0.5]}}
    mon.after_iteration(None, 0, evals_log)
    mon.after_iteration(None, 1, evals_log)  # stale: nothing printed
    evals_log["train"]["rmse"].append(0.4)
    mon.after_iteration(None, 2, evals_log)
    out = capsys.readouterr().out.strip().splitlines()
    assert out == ["[0]\ttrain-rmse:0.50000", "[2]\ttrain-rmse:0.40000"]


def test_algorithm_handler_service(tmp_path):
    rng = np.random.RandomState(2)
    X = rng.rand(200, 3).astype(np.float32)
    y = X[:, 0].astype(np.float32)
    forest = train({"max_depth": 3}, DataMatrix(X, labels=y), num_boost_round=3)
    forest.save_model(str(tmp_path / "xgboost-model"))

    from sagemaker_xgboost_container_tpu.serving.handler_service import (
        AlgorithmHandlerService,
    )

    svc = AlgorithmHandlerService()
    body, ctype = svc.handle(b"0.5,0.2,0.1\n0.9,0.8,0.7", "text/csv", "text/csv", str(tmp_path))
    assert ctype == "text/csv"
    assert len(body.splitlines()) == 2


def test_user_module_handler_requires_model_fn(tmp_path):
    from sagemaker_xgboost_container_tpu.serving.handler_service import (
        UserModuleHandlerService,
    )
    from sagemaker_xgboost_container_tpu.toolkit import exceptions as exc

    svc = UserModuleHandlerService(user_module=None)
    with pytest.raises(exc.UserError, match="model_fn"):
        svc.handle(b"1,2", "text/csv", "text/csv", str(tmp_path))


def test_user_module_handler_transform_fn(tmp_path):
    import types

    module = types.SimpleNamespace(
        model_fn=lambda model_dir: "MODEL",
        transform_fn=lambda model, payload, ctype, accept: ("custom:" + payload.decode(), "text/csv"),
    )
    from sagemaker_xgboost_container_tpu.serving.handler_service import (
        UserModuleHandlerService,
    )

    svc = UserModuleHandlerService(user_module=module)
    body, ctype = svc.handle(b"1,2", "text/csv", "text/csv", str(tmp_path))
    assert body == "custom:1,2"


def test_logging_config():
    from sagemaker_xgboost_container_tpu.utils.logging_config import setup_main_logger

    logger = setup_main_logger("x")
    logger.info("hello")


def test_batched_rounds_emit_device_metrics():
    """K>1 batching now works WITH a train watchlist: per-round metrics come
    back from the device and the stdout contract holds."""
    rng = np.random.RandomState(3)
    X = rng.rand(500, 4).astype(np.float32)
    y = (X[:, 0] * 5).astype(np.float32)
    dtrain = DataMatrix(X, labels=y)
    log = {}

    class Recorder:
        def after_iteration(self, model, epoch, evals_log):
            log.update({k: {m: list(v) for m, v in d.items()} for k, d in evals_log.items()})
            return False

    batched = train(
        {"max_depth": 3, "seed": 2, "_rounds_per_dispatch": 4, "eval_metric": "rmse"},
        dtrain,
        num_boost_round=8,
        evals=[(dtrain, "train")],
        callbacks=[Recorder()],
    )
    assert len(log["train"]["rmse"]) == 8
    # device metrics match host-computed metrics from an unbatched run
    log2 = {}

    class Recorder2:
        def after_iteration(self, model, epoch, evals_log):
            log2.update({k: {m: list(v) for m, v in d.items()} for k, d in evals_log.items()})
            return False

    train(
        {"max_depth": 3, "seed": 2, "eval_metric": "rmse"},
        dtrain,
        num_boost_round=8,
        evals=[(dtrain, "train")],
        callbacks=[Recorder2()],
    )
    np.testing.assert_allclose(
        log["train"]["rmse"], log2["train"]["rmse"], rtol=1e-4, atol=1e-5
    )
    assert batched.num_boosted_rounds == 8


def test_batched_rounds_auc_metrics_still_per_round():
    rng = np.random.RandomState(4)
    X = rng.rand(300, 3).astype(np.float32)
    y = (X[:, 0] > 0.5).astype(np.float32)
    dtrain = DataMatrix(X, labels=y)
    log = {}

    class Recorder:
        def after_iteration(self, model, epoch, evals_log):
            log.update(evals_log)
            return False

    train(
        {
            "objective": "binary:logistic",
            "max_depth": 3,
            "_rounds_per_dispatch": 4,
            "eval_metric": "auc",
        },
        dtrain,
        num_boost_round=4,
        evals=[(dtrain, "train")],
        callbacks=[Recorder()],
    )
    assert len(log["train"]["auc"]) == 4  # host fallback still per-round


def test_batched_rounds_with_validation_set_device_metrics():
    rng = np.random.RandomState(5)
    X = rng.rand(700, 4).astype(np.float32)
    y = (X[:, 0] * 5 + X[:, 1]).astype(np.float32)
    dtrain = DataMatrix(X[:500], labels=y[:500])
    dval = DataMatrix(X[500:], labels=y[500:])

    def run(params):
        log = {}

        class Rec:
            def after_iteration(self, model, epoch, evals_log):
                log.update(
                    {k: {m: list(v) for m, v in d.items()} for k, d in evals_log.items()}
                )
                return False

        train(
            params,
            dtrain,
            num_boost_round=6,
            evals=[(dtrain, "train"), (dval, "validation")],
            callbacks=[Rec()],
        )
        return log

    batched = run({"max_depth": 3, "seed": 6, "_rounds_per_dispatch": 3, "eval_metric": "rmse"})
    plain = run({"max_depth": 3, "seed": 6, "eval_metric": "rmse"})
    assert len(batched["validation"]["rmse"]) == 6
    np.testing.assert_allclose(
        batched["validation"]["rmse"], plain["validation"]["rmse"], rtol=1e-4, atol=1e-5
    )
    np.testing.assert_allclose(
        batched["train"]["rmse"], plain["train"]["rmse"], rtol=1e-4, atol=1e-5
    )


class TestRequirementsInstall:
    def test_no_file_is_noop(self, tmp_path):
        from sagemaker_xgboost_container_tpu.utils.requirements import (
            install_requirements_if_present,
        )

        assert install_requirements_if_present(str(tmp_path)) is False

    def test_bad_requirements_raises_user_error(self, tmp_path):
        from sagemaker_xgboost_container_tpu.toolkit import exceptions as exc
        from sagemaker_xgboost_container_tpu.utils.requirements import (
            install_requirements_if_present,
        )

        (tmp_path / "requirements.txt").write_text(
            "this-package-definitely-does-not-exist-xyz==99.99.99\n"
        )
        with pytest.raises(exc.UserError):
            install_requirements_if_present(str(tmp_path))

    def test_constraints_pin_framework_packages(self, tmp_path, monkeypatch):
        """A customer requirements.txt must run under a constraints file
        pinning jax/numpy/... at their live versions (ADVICE r2: an
        unconstrained install could downgrade the runtime under the
        server)."""
        from sagemaker_xgboost_container_tpu.utils import requirements as R

        (tmp_path / "requirements.txt").write_text("some-extra-package\n")
        captured = {}

        def fake_check_call(cmd):
            captured["cmd"] = list(cmd)

        monkeypatch.setattr(R.subprocess, "check_call", fake_check_call)
        assert R.install_requirements_if_present(str(tmp_path)) is True
        assert "-c" in captured["cmd"], captured
        # the constraints file is cleaned up after the call; capture its
        # contents by re-generating one the same way
        cpath = R._write_constraints_file()
        try:
            pins = open(cpath).read()
        finally:
            import os as _os

            _os.unlink(cpath)
        import numpy

        assert "numpy=={}".format(numpy.__version__) in pins
        import jax

        assert "jax=={}".format(jax.__version__) in pins

    def test_constraints_opt_out(self, tmp_path, monkeypatch):
        from sagemaker_xgboost_container_tpu.utils import requirements as R

        (tmp_path / "requirements.txt").write_text("some-extra-package\n")
        captured = {}
        monkeypatch.setenv("GRAFT_PIP_NO_CONSTRAINTS", "1")
        monkeypatch.setattr(
            R.subprocess, "check_call", lambda cmd: captured.update(cmd=list(cmd))
        )
        assert R.install_requirements_if_present(str(tmp_path)) is True
        assert "-c" not in captured["cmd"]
