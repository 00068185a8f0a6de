"""Serving tests: real HTTP server over a socket, a model trained on the
seeded Abalone-shaped channel (tests/reference_fixtures.py).

Coverage model: reference test/unit/algorithm_mode/test_serve(_utils).py +
the MME lifecycle from test/integration/local/test_multiple_model_endpoint.py
— but against our threaded WSGI server with the XLA predict kernel.
"""

import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
from sagemaker_xgboost_container_tpu.models import Forest, train
from sagemaker_xgboost_container_tpu.serving import serve_utils
from sagemaker_xgboost_container_tpu.serving.app import ScoringService, make_app
from sagemaker_xgboost_container_tpu.serving.mme import make_mme_app
from tests.reference_fixtures import (
    REFERENCE_RESOURCES,
    needs_reference_artifacts,
    resources,
)
from tests.util_ports import free_port

# models pickled and saved by real xgboost: the reference's own artefacts
ABALONE_MODELS = REFERENCE_RESOURCES + "/abalone/models"
REF_MODELS = REFERENCE_RESOURCES + "/models"


@pytest.fixture(scope="module")
def abalone_model_dir(tmp_path_factory):
    """Train a small abalone model into a model dir."""
    from sagemaker_xgboost_container_tpu.data.readers import get_data_matrix

    dm = get_data_matrix(resources() + "/abalone/data/train", "libsvm")
    forest = train(
        {"objective": "reg:squarederror", "max_depth": 4}, dm, num_boost_round=8
    )
    model_dir = tmp_path_factory.mktemp("model")
    forest.save_model(str(model_dir / "xgboost-model"))
    return str(model_dir)


def _swallow(batcher, x):
    """Issue a batcher request, ignoring any error (queue-full test filler)."""
    try:
        batcher.predict(x, timeout=10)
    except Exception:
        pass


def _serve(app):
    """Start the threaded WSGI server on a free port; return base URL."""
    from wsgiref.simple_server import make_server

    from sagemaker_xgboost_container_tpu.serving.server import (
        _QuietHandler,
        _ThreadedWSGIServer,
    )

    port = free_port()
    httpd = make_server(
        "127.0.0.1", port, app, server_class=_ThreadedWSGIServer, handler_class=_QuietHandler
    )
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()
    return "http://127.0.0.1:{}".format(port), httpd


def _request(url, method="GET", data=None, headers=None):
    req = urllib.request.Request(url, data=data, method=method, headers=headers or {})
    try:
        with urllib.request.urlopen(req, timeout=30) as resp:
            return resp.status, resp.read(), dict(resp.headers)
    except urllib.error.HTTPError as e:
        return e.code, e.read(), dict(e.headers)


LIBSVM_PAYLOAD = b"1:2 2:0.74 3:0.6 4:0.195 5:1.974 6:0.598 7:0.4085 8:0.71"
CSV_PAYLOAD = b"2,0.74,0.6,0.195,1.974,0.598,0.4085,0.71,0.5"


class TestSingleModelEndpoint:
    @pytest.fixture(autouse=True, scope="class")
    def _server(self, request, abalone_model_dir):
        app = make_app(ScoringService(abalone_model_dir))
        base, httpd = _serve(app)
        request.cls.base = base
        yield
        httpd.shutdown()

    def test_ping(self):
        status, _, _ = _request(self.base + "/ping")
        assert status == 200

    def test_execution_parameters(self):
        status, body, _ = _request(self.base + "/execution-parameters")
        assert status == 200
        params = json.loads(body)
        assert params["BatchStrategy"] == "MULTI_RECORD"
        assert params["MaxPayloadInMB"] == 6

    def test_invocations_libsvm_csv_out(self):
        status, body, _ = _request(
            self.base + "/invocations",
            method="POST",
            data=LIBSVM_PAYLOAD,
            headers={"Content-Type": "text/libsvm"},
        )
        assert status == 200, body
        value = float(body.decode().strip())
        assert 0 < value < 30  # abalone ring count territory

    def test_invocations_csv_json_out(self):
        status, body, _ = _request(
            self.base + "/invocations",
            method="POST",
            data=CSV_PAYLOAD[: CSV_PAYLOAD.rfind(b",")],  # 8 features
            headers={"Content-Type": "text/csv", "Accept": "application/json"},
        )
        assert status == 200, body
        doc = json.loads(body)
        assert "predictions" in doc and "score" in doc["predictions"][0]

    def test_empty_payload_204(self):
        status, _, _ = _request(
            self.base + "/invocations",
            method="POST",
            data=b"",
            headers={"Content-Type": "text/csv"},
        )
        assert status == 204

    def test_bad_content_type_415(self):
        status, _, _ = _request(
            self.base + "/invocations",
            method="POST",
            data=b"<xml/>",
            headers={"Content-Type": "application/xml"},
        )
        assert status == 415

    def test_bad_accept_406(self):
        status, _, _ = _request(
            self.base + "/invocations",
            method="POST",
            data=LIBSVM_PAYLOAD,
            headers={"Content-Type": "text/libsvm", "Accept": "application/x-npz"},
        )
        assert status == 406

    def test_multirow_csv(self):
        rows = b"\n".join([CSV_PAYLOAD[: CSV_PAYLOAD.rfind(b",")]] * 5)
        status, body, _ = _request(
            self.base + "/invocations",
            method="POST",
            data=rows,
            headers={"Content-Type": "text/csv"},
        )
        assert status == 200
        assert len(body.decode().strip().split("\n")) == 5


@needs_reference_artifacts
class TestReferenceModelServing:
    """Models produced by real xgboost (pickle/UBJ/legacy binary) serve."""

    @pytest.mark.parametrize(
        "model_dir",
        [
            ABALONE_MODELS + "/libsvm_pickled",
            REF_MODELS + "/saved_booster",
            REF_MODELS + "/pickled_model",
        ],
    )
    def test_load_and_predict(self, model_dir):
        model, fmt = serve_utils.get_loaded_booster(model_dir)
        n_feat = model.num_feature
        X = np.random.RandomState(0).rand(4, n_feat).astype(np.float32)
        dtest = DataMatrix(X)
        preds = serve_utils.predict(model, fmt, dtest, "text/csv", model.objective_name)
        assert np.asarray(preds).shape[0] == 4

    def test_abalone_pickled_sane_predictions(self):
        model, fmt = serve_utils.get_loaded_booster(ABALONE_MODELS + "/libsvm_pickled")
        from sagemaker_xgboost_container_tpu.serving.encoder import libsvm_to_matrix

        dtest = libsvm_to_matrix(LIBSVM_PAYLOAD).pad_features(model.num_feature)
        preds = serve_utils.predict(model, fmt, dtest, "text/libsvm", model.objective_name)
        assert 0 < float(np.asarray(preds)[0]) < 30


class TestSelectableInference:
    def test_binary_keys(self, monkeypatch):
        rng = np.random.RandomState(0)
        X = rng.randn(300, 3).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.float32)
        forest = train(
            {"objective": "binary:logistic", "max_depth": 3},
            DataMatrix(X, labels=y),
            num_boost_round=5,
        )
        preds = forest.predict(X[:4])
        selected = serve_utils.get_selected_predictions(
            preds,
            ["predicted_label", "probability", "probabilities", "labels"],
            "binary:logistic",
        )
        assert len(selected) == 4
        for row in selected:
            assert row["predicted_label"] in (0, 1)
            assert 0 <= row["probability"] <= 1
            assert len(row["probabilities"]) == 2
            assert row["labels"] == [0, 1]

    def test_invalid_keys_get_nan(self):
        selected = serve_utils.get_selected_predictions(
            np.asarray([1.5]), ["predicted_score", "probabilities"], "reg:squarederror"
        )
        assert selected[0]["predicted_score"] == 1.5
        assert np.isnan(selected[0]["probabilities"])

    def test_encode_csv_and_jsonlines(self):
        preds = [
            {"predicted_label": 1, "probabilities": [0.4, 0.6]},
            {"predicted_label": 0, "probabilities": [0.9, 0.1]},
        ]
        csv_out = serve_utils.encode_selected_predictions(
            preds, ["predicted_label", "probabilities"], "text/csv"
        )
        assert csv_out.splitlines()[0] == '1,"[0.4, 0.6]"'
        jl = serve_utils.encode_selected_predictions(
            preds, ["predicted_label", "probabilities"], "application/jsonlines"
        )
        assert json.loads(jl.splitlines()[0])["predicted_label"] == 1

    def test_encode_recordio(self):
        from sagemaker_xgboost_container_tpu.data.recordio import iter_records, record_pb2

        preds = [{"predicted_label": 1, "probabilities": [0.4, 0.6]}]
        buf = serve_utils.encode_selected_predictions(
            preds, ["predicted_label", "probabilities"], "application/x-recordio-protobuf"
        )
        records = list(iter_records(buf))
        assert len(records) == 1
        rec = record_pb2.Record()
        rec.ParseFromString(records[0])
        assert list(rec.label["probabilities"].float32_tensor.values) == pytest.approx(
            [0.4, 0.6]
        )

    def test_selectable_end_to_end_http(self, monkeypatch, tmp_path):
        rng = np.random.RandomState(0)
        X = rng.randn(300, 3).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.float32)
        forest = train(
            {"objective": "binary:logistic", "max_depth": 3},
            DataMatrix(X, labels=y),
            num_boost_round=5,
        )
        forest.save_model(str(tmp_path / "xgboost-model"))
        monkeypatch.setenv("SAGEMAKER_INFERENCE_OUTPUT", "predicted_label,probability")
        app = make_app(ScoringService(str(tmp_path)))
        base, httpd = _serve(app)
        try:
            status, body, _ = _request(
                base + "/invocations",
                method="POST",
                data=b"0.5,0.1,0.2\n-2.0,0.0,0.0",
                headers={"Content-Type": "text/csv", "Accept": "application/json"},
            )
            assert status == 200, body
            doc = json.loads(body)
            assert set(doc["predictions"][0]) == {"predicted_label", "probability"}
        finally:
            httpd.shutdown()


class TestMultiModelEndpoint:
    def test_lifecycle(self, abalone_model_dir):
        app = make_mme_app()
        base, httpd = _serve(app)
        try:
            status, body, _ = _request(base + "/models")
            assert status == 200 and json.loads(body)["models"] == []

            payload = json.dumps(
                {"model_name": "abalone", "url": abalone_model_dir}
            ).encode()
            status, body, _ = _request(
                base + "/models",
                method="POST",
                data=payload,
                headers={"Content-Type": "application/json"},
            )
            assert status == 200, body

            # duplicate load -> 409
            status, _, _ = _request(
                base + "/models",
                method="POST",
                data=payload,
                headers={"Content-Type": "application/json"},
            )
            assert status == 409

            status, body, _ = _request(base + "/models")
            assert json.loads(body)["models"][0]["modelName"] == "abalone"

            status, body, _ = _request(
                base + "/models/abalone/invoke",
                method="POST",
                data=LIBSVM_PAYLOAD,
                headers={"Content-Type": "text/libsvm"},
            )
            assert status == 200, body
            assert 0 < float(body.decode().strip()) < 30

            status, _, _ = _request(base + "/models/abalone", method="DELETE")
            assert status == 200
            status, _, _ = _request(
                base + "/models/abalone/invoke",
                method="POST",
                data=LIBSVM_PAYLOAD,
                headers={"Content-Type": "text/libsvm"},
            )
            assert status == 404
        finally:
            httpd.shutdown()

    def test_unknown_model_404(self):
        app = make_mme_app()
        base, httpd = _serve(app)
        try:
            status, _, _ = _request(base + "/models/ghost")
            assert status == 404
        finally:
            httpd.shutdown()

    def test_payload_cap_and_hard_limit(self, abalone_model_dir, monkeypatch):
        """MMS payload sizing contract (reference serving_mms.py:80-83):
        SAGEMAKER_MAX_REQUEST_SIZE is honored but hard-capped at 20MB."""
        from sagemaker_xgboost_container_tpu.serving import mme as mme_mod

        monkeypatch.setenv("SAGEMAKER_MAX_REQUEST_SIZE", "1024")
        assert mme_mod._max_request_size() == 1024
        monkeypatch.setenv("SAGEMAKER_MAX_REQUEST_SIZE", str(64 * 1024**2))
        assert mme_mod._max_request_size() == 20 * 1024**2
        monkeypatch.delenv("SAGEMAKER_MAX_REQUEST_SIZE")
        monkeypatch.setenv("MAX_CONTENT_LENGTH", "2048")
        assert mme_mod._max_request_size() == 2048

        monkeypatch.setenv("SAGEMAKER_MAX_REQUEST_SIZE", "64")
        app = make_mme_app()
        base, httpd = _serve(app)
        try:
            payload = json.dumps(
                {"model_name": "abalone", "url": abalone_model_dir}
            ).encode()
            status, _, _ = _request(
                base + "/models",
                method="POST",
                data=payload,
                headers={"Content-Type": "application/json"},
            )
            assert status == 200
            big = b"1:0.1 " * 50  # > 64 bytes
            status, _, _ = _request(
                base + "/models/abalone/invoke",
                method="POST",
                data=b"0 " + big,
                headers={"Content-Type": "text/libsvm"},
            )
            assert status == 413
            status, _, _ = _request(
                base + "/models/abalone/invoke",
                method="POST",
                data=LIBSVM_PAYLOAD,
                headers={"Content-Type": "text/libsvm"},
            )
            assert status == 200
        finally:
            httpd.shutdown()

    def test_job_queue_full_returns_503(self):
        """SAGEMAKER_MODEL_JOB_QUEUE_SIZE analog: a saturated coalescer
        queue rejects with 503 instead of queueing unboundedly."""
        from sagemaker_xgboost_container_tpu.serving.batcher import (
            JobQueueFull,
            PredictBatcher,
        )

        release = threading.Event()

        def slow_predict(feats):
            release.wait(5)
            return np.zeros(feats.shape[0], np.float32)

        batcher = PredictBatcher(slow_predict, max_queue=1, max_wait_ms=0.1)
        x = np.zeros((1, 3), np.float32)
        t = threading.Thread(target=lambda: batcher.predict(x, timeout=10))
        t.start()
        time.sleep(0.3)  # first request now blocked inside slow_predict
        # r5 inline fast path: the first request runs on ITS caller's thread
        # (holding the exec lock), so total in-flight capacity is
        # max_queue + 1 worker-held + 1 inline. Two fillers saturate it:
        # one dequeued by the worker (parked at the exec lock, pre-drain),
        # one still queued (the max_queue=1 slot).
        fillers = [
            threading.Thread(target=lambda: _swallow(batcher, x))
            for _ in range(2)
        ]
        for f in fillers:
            f.start()
            time.sleep(0.3)
        try:
            with pytest.raises(JobQueueFull):
                batcher.predict(x, timeout=10)
        finally:
            release.set()
            t.join()
            for f in fillers:
                f.join()


class TestScriptModeServing:
    def test_user_hooks_through_real_server(self, tmp_path, monkeypatch):
        # user module provides transform_fn + model_fn (reference
        # test_abalone.py custom transform_fn scenario)
        code_dir = tmp_path / "code"
        code_dir.mkdir()
        (code_dir / "inference.py").write_text(
            "def model_fn(model_dir):\n"
            "    return 'sentinel-model'\n"
            "\n"
            "def transform_fn(model, payload, content_type, accept):\n"
            "    assert model == 'sentinel-model'\n"
            "    return 'echo:' + payload.decode(), 'text/csv'\n"
        )
        monkeypatch.setenv("SAGEMAKER_PROGRAM", "inference.py")
        monkeypatch.setenv("SAGEMAKER_SUBMIT_DIRECTORY", str(code_dir))
        monkeypatch.setenv("SM_MODEL_DIR", str(tmp_path))

        from sagemaker_xgboost_container_tpu.serving.server import build_app

        app = build_app()
        base, httpd = _serve(app)
        try:
            status, body, _ = _request(
                base + "/invocations",
                method="POST",
                data=b"1,2,3",
                headers={"Content-Type": "text/csv"},
            )
            assert status == 200, body
            assert body == b"echo:1,2,3"
        finally:
            httpd.shutdown()


class TestBatcher:
    def test_coalesces_concurrent_requests(self):
        import threading as th
        import time as _time

        from sagemaker_xgboost_container_tpu.serving.batcher import PredictBatcher

        calls = []

        def fake_predict(feats):
            # real dispatches take time; while one batch is in flight the
            # queue accumulates, which is exactly the window the coalescer
            # exploits. An instant predict_fn would make coalescing depend
            # on thread-scheduling luck (a lone idle-endpoint request
            # deliberately dispatches immediately — adaptive linger).
            calls.append(feats.shape[0])
            _time.sleep(0.05)
            return feats[:, 0] * 2

        batcher = PredictBatcher(fake_predict, max_wait_ms=50)
        results = {}
        barrier = th.Barrier(8)

        def issue(i):
            x = np.full((3, 2), float(i), np.float32)
            barrier.wait(10)  # near-simultaneous arrival
            results[i] = batcher.predict(x)

        threads = [th.Thread(target=issue, args=(i,)) for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        for i in range(8):
            np.testing.assert_allclose(results[i], [2.0 * i] * 3)
        # the first request may dispatch solo (idle endpoint); everything
        # arriving during its in-flight window must coalesce
        assert len(calls) < 8, calls
        assert sum(calls) == 24

    def test_error_propagates(self):
        from sagemaker_xgboost_container_tpu.serving.batcher import PredictBatcher

        def boom(feats):
            raise ValueError("bad batch")

        batcher = PredictBatcher(boom)
        with pytest.raises(ValueError, match="bad batch"):
            batcher.predict(np.zeros((2, 2), np.float32))

    def test_idle_request_runs_inline(self):
        """r5 latency fix: an idle endpoint's request executes predict_fn on
        the CALLER's thread (no worker handoff — ~0.7 ms of condvar
        ping-pong saved per request); with the worker busy, requests fall
        back to the coalescing queue and run on the worker thread."""
        import threading as th

        from sagemaker_xgboost_container_tpu.serving.batcher import PredictBatcher

        idents = []
        release = th.Event()

        def record_predict(feats):
            idents.append(th.get_ident())
            if feats[0, 0] == 99.0:  # the blocker request parks the worker
                release.wait(5)
            return feats[:, 0]

        batcher = PredictBatcher(record_predict)
        x = np.zeros((1, 2), np.float32)
        batcher.predict(x)
        assert idents[-1] == th.get_ident(), "idle request should run inline"

        # occupy the exec lock via a slow inline run, then issue a second
        # request from another thread: it must take the queue and run on
        # the WORKER thread once the blocker releases the lock
        blocker = th.Thread(
            target=lambda: batcher.predict(np.full((1, 2), 99.0, np.float32))
        )
        blocker.start()
        time.sleep(0.2)  # blocker now inside record_predict holding the lock
        contended_done = th.Event()

        def contended():
            batcher.predict(x)
            contended_done.set()

        ct = th.Thread(target=contended)
        ct.start()
        time.sleep(0.2)  # contended request is now queued behind the lock
        release.set()    # let the blocker finish; worker then drains
        assert contended_done.wait(10)
        ct.join(10)
        blocker.join(10)
        assert idents[-1] not in (th.get_ident(), blocker.ident), (
            "contended request must run on the worker thread"
        )

    def test_csv_sniff_fast_path(self):
        """The unambiguous-delimiter fast path must agree with the Sniffer
        contract on every payload shape serving accepts."""
        from sagemaker_xgboost_container_tpu.serving.encoder import (
            _sniff_delimiter, csv_to_matrix,
        )

        assert _sniff_delimiter("1.0,2.0,3.0") == ","
        assert _sniff_delimiter("1.0;2.0;3.0") == ";"
        assert _sniff_delimiter("1.0\t2.0") == "\t"
        assert _sniff_delimiter("3.14") == ","      # single cell
        assert _sniff_delimiter("") == ","
        # ambiguous (comma AND space): the full Sniffer decides, and the
        # parsed matrix is still correct
        m = csv_to_matrix(b"1.0, 2.0, 3.0\n4.0, 5.0, 6.0")
        assert m.features.shape == (2, 3)
        np.testing.assert_allclose(m.features[0], [1.0, 2.0, 3.0])
        m2 = csv_to_matrix(b"1,2\n,4")  # empty cell -> nan
        assert np.isnan(m2.features[1, 0])

    def test_csv_single_column_incidental_whitespace(self):
        """ADVICE r5: a single-column payload with incidental leading/
        trailing whitespace must not sniff ' ' as the delimiter and grow a
        phantom NaN column — the probe line is stripped first."""
        from sagemaker_xgboost_container_tpu.serving.encoder import (
            _sniff_delimiter, csv_to_matrix,
        )

        assert _sniff_delimiter("1.0 ") == ","
        assert _sniff_delimiter(" 1.0") == ","
        m = csv_to_matrix(b"1.0 ")
        assert m.features.shape == (1, 1)
        np.testing.assert_allclose(m.features, [[1.0]])
        m = csv_to_matrix(b" 1.0")
        assert m.features.shape == (1, 1)
        np.testing.assert_allclose(m.features, [[1.0]])
        # interior whitespace is still a real delimiter
        m = csv_to_matrix(b"1.0 2.0\n3.0 4.0")
        assert m.features.shape == (2, 2)

    def test_served_predictions_match_direct(self, abalone_model_dir):
        svc = ScoringService(abalone_model_dir)
        svc.load_model()
        from sagemaker_xgboost_container_tpu.serving.encoder import libsvm_to_matrix

        dtest = libsvm_to_matrix(LIBSVM_PAYLOAD)
        batched = svc.predict(dtest, "text/libsvm")
        direct = serve_utils.predict(
            svc.model, svc.model_format, dtest, "text/libsvm", svc.objective
        )
        np.testing.assert_allclose(np.asarray(batched), np.asarray(direct), rtol=1e-6)


class TestEnsembleAndBatchMode:
    def test_ensemble_average(self, tmp_path):
        rng = np.random.RandomState(0)
        X = rng.rand(200, 3).astype(np.float32)
        y = (X[:, 0] * 4).astype(np.float32)
        m1 = train({"max_depth": 3, "seed": 1}, DataMatrix(X, labels=y), num_boost_round=3)
        m2 = train({"max_depth": 3, "seed": 2, "subsample": 0.7}, DataMatrix(X, labels=y), num_boost_round=3)
        m1.save_model(str(tmp_path / "xgboost-model-0"))
        m2.save_model(str(tmp_path / "xgboost-model-1"))

        model, fmt = serve_utils.get_loaded_booster(str(tmp_path), ensemble=True)
        assert isinstance(model, list) and len(model) == 2
        dtest = DataMatrix(X[:5])
        preds = serve_utils.predict(model, fmt, dtest, "text/csv", "reg:squarederror")
        expect = (m1.predict(X[:5]) + m2.predict(X[:5])) / 2.0
        np.testing.assert_allclose(np.asarray(preds), expect, rtol=1e-5)

    def test_ensemble_disabled_env(self, tmp_path, monkeypatch):
        rng = np.random.RandomState(3)
        X = rng.rand(100, 2).astype(np.float32)
        y = X[:, 0].astype(np.float32)
        m = train({"max_depth": 2}, DataMatrix(X, labels=y), num_boost_round=2)
        m.save_model(str(tmp_path / "xgboost-model-0"))
        m.save_model(str(tmp_path / "xgboost-model-1"))
        monkeypatch.setenv("SAGEMAKER_INFERENCE_ENSEMBLE", "false")
        svc = ScoringService(str(tmp_path))
        svc.load_model()
        assert not isinstance(svc.model, list)

    def test_sagemaker_batch_output(self, abalone_model_dir, monkeypatch):
        monkeypatch.setenv("SAGEMAKER_BATCH", "true")
        app = make_app(ScoringService(abalone_model_dir))
        base, httpd = _serve(app)
        try:
            status, body, _ = _request(
                base + "/invocations",
                method="POST",
                data=LIBSVM_PAYLOAD,
                headers={"Content-Type": "text/libsvm"},
            )
            assert status == 200
            # batch transform responses are newline-terminated
            assert body.endswith(b"\n")
        finally:
            httpd.shutdown()


def test_invocations_recordio_accept(abalone_model_dir):
    app = make_app(ScoringService(abalone_model_dir))
    base, httpd = _serve(app)
    try:
        status, body, _ = _request(
            base + "/invocations",
            method="POST",
            data=LIBSVM_PAYLOAD,
            headers={
                "Content-Type": "text/libsvm",
                "Accept": "application/x-recordio-protobuf",
            },
        )
        assert status == 200
        from sagemaker_xgboost_container_tpu.data.recordio import read_recordio_protobuf

        feats, _labels = read_recordio_protobuf(body)
        assert feats.shape[0] == 1
    finally:
        httpd.shutdown()


def test_ensemble_vote_for_softmax(tmp_path):
    rng = np.random.RandomState(5)
    X = rng.randn(300, 3).astype(np.float32)
    y = ((X[:, 0] > 0).astype(int) + (X[:, 1] > 0).astype(int)).astype(np.float32)
    for seed in (1, 2, 3):
        m = train(
            {"objective": "multi:softmax", "num_class": 3, "max_depth": 3, "seed": seed,
             "subsample": 0.8},
            DataMatrix(X, labels=y),
            num_boost_round=4,
        )
        m.save_model(str(tmp_path / ("xgboost-model-%d" % seed)))
    model, fmt = serve_utils.get_loaded_booster(str(tmp_path), ensemble=True)
    assert len(model) == 3
    preds = serve_utils.predict(
        model, fmt, DataMatrix(X[:20]), "text/csv", "multi:softmax"
    )
    preds = np.asarray(preds)
    assert preds.shape == (20,)
    assert set(np.unique(preds)).issubset({0.0, 1.0, 2.0})


class TestConcurrentServing:
    def test_parallel_clients_all_correct(self, tmp_path):
        """32 concurrent clients x 3 rounds: no connection resets (listen
        backlog), every response correct (coalescer scatter-back)."""
        rng = np.random.RandomState(0)
        X = rng.rand(500, 6).astype(np.float32)
        y = (X @ rng.rand(6).astype(np.float32) * 5).astype(np.float32)
        forest = train(
            {"max_depth": 4, "objective": "reg:squarederror"},
            DataMatrix(X, labels=y),
            num_boost_round=10,
        )
        forest.save_model(os.path.join(str(tmp_path), "xgboost-model"))
        expect = np.asarray(forest.predict(X[:32]))

        app = make_app(ScoringService(str(tmp_path)))
        base, httpd = _serve(app)
        errors = []

        def hit(i, out):
            try:
                body = ",".join("%.6f" % v for v in X[i]).encode()
                status, resp, _ = _request(
                    base + "/invocations",
                    method="POST",
                    data=body,
                    headers={"Content-Type": "text/csv"},
                )
                assert status == 200
                out[i] = float(resp.decode().strip())
            except Exception as e:  # surface in the main thread
                errors.append((i, repr(e)))

        try:
            for _ in range(3):
                out = [None] * 32
                ts = [
                    threading.Thread(target=hit, args=(i, out)) for i in range(32)
                ]
                for t in ts:
                    t.start()
                for t in ts:
                    t.join()
                assert not errors, errors[:3]
                np.testing.assert_allclose(out, expect, rtol=1e-4)
        finally:
            httpd.shutdown()


def test_warmup_predict_async(abalone_model_dir):
    """Model-load warmup: compiles the first device buckets off the request
    path (TPU first-hit compile spike), never raises, and is inert for
    degenerate models."""
    model, _fmt = serve_utils.get_loaded_booster(abalone_model_dir)
    serve_utils.warmup_predict_async(model)
    threads = [t for t in threading.enumerate() if t.name == "predict-warmup"]
    for t in threads:
        t.join(timeout=120)
    assert not [
        t for t in threading.enumerate()
        if t.name == "predict-warmup" and t.is_alive()
    ]
    # the warmed bucket serves correctly (beyond the host-path threshold)
    n = 40
    x = np.full((n, model.num_feature), 0.5, np.float32)
    preds = model.predict(x)
    assert preds.shape == (n,) and np.isfinite(np.asarray(preds)).all()

    # degenerate model (no features): warmup skips without raising
    class NoFeatures:
        num_feature = 0

    serve_utils.warmup_predict_async(NoFeatures())
    for t in threading.enumerate():
        if t.name == "predict-warmup":
            t.join(timeout=30)

    # kill-switch respected
    os.environ["GRAFT_PREDICT_WARMUP"] = "0"
    try:
        before = {t.ident for t in threading.enumerate()}
        serve_utils.warmup_predict_async(model)
        started = [
            t for t in threading.enumerate()
            if t.name == "predict-warmup" and t.ident not in before
        ]
        assert not started
    finally:
        os.environ.pop("GRAFT_PREDICT_WARMUP", None)
