"""Built-image integration tier.

Runs scripts/image_smoke.sh: builds docker/Dockerfile.tpu (CPU variant via
the JAX_SPEC build-arg), fabricates the SageMaker /opt/ml filesystem the
platform mounts, then runs the image's `train` and `serve` CMDs for real —
the repo analog of the reference's local_mode docker-compose harness
(reference test/utils/local_mode.py:371-557). Skip-marked where Docker (or
the network its build needs) is unavailable; the env-derivation the image
relies on is covered unconditionally in TestDeriveSmEnv below.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.skipif(
    shutil.which(os.environ.get("DOCKER", "docker")) is None,
    reason="docker not installed on this host",
)
def test_image_builds_and_runs_sagemaker_contract():
    result = subprocess.run(
        ["bash", os.path.join(REPO, "scripts", "image_smoke.sh")],
        capture_output=True,
        text=True,
        timeout=1800,
    )
    if result.returncode == 75:  # script-level SKIP convention
        pytest.skip(result.stdout.strip() or "image smoke unavailable")
    assert result.returncode == 0, result.stdout + "\n" + result.stderr
    assert "IMAGE SMOKE OK" in result.stdout


class TestDeriveSmEnv:
    """entry.derive_sm_env: a bare /opt/ml mount (the real BYO-container
    contract) must yield a full SM_* environment; explicit env wins."""

    def _tree(self, tmp_path):
        cfg = tmp_path / "config"
        cfg.mkdir()
        (cfg / "hyperparameters.json").write_text('{"num_round": "5"}')
        (cfg / "resourceconfig.json").write_text(
            json.dumps({"current_host": "algo-2", "hosts": ["algo-1", "algo-2"]})
        )
        for ch in ("train", "validation"):
            (tmp_path / "data" / ch).mkdir(parents=True)
        return tmp_path

    def _run(self, tmp_path, extra_env=()):
        """Subprocess so os.environ mutation can't leak into the suite."""
        code = (
            "import json, os\n"
            "from sagemaker_xgboost_container_tpu.training import entry\n"
            "entry.derive_sm_env(input_root={root!r})\n"
            "print(json.dumps({{k: v for k, v in os.environ.items()"
            " if k.startswith('SM_')}}))\n"
        ).format(root=str(tmp_path))
        env = dict(os.environ)
        for k in list(env):
            if k.startswith("SM_"):
                del env[k]
        env.update(dict(extra_env))
        out = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True,
            text=True,
            env=env,
            cwd=REPO,
            check=True,
        )
        return json.loads(out.stdout.splitlines()[-1])

    def test_derives_channels_hosts_and_config_paths(self, tmp_path):
        sm = self._run(self._tree(tmp_path))
        assert sm["SM_CHANNEL_TRAIN"] == str(tmp_path / "data" / "train")
        assert sm["SM_CHANNEL_VALIDATION"] == str(tmp_path / "data" / "validation")
        assert json.loads(sm["SM_HOSTS"]) == ["algo-1", "algo-2"]
        assert sm["SM_CURRENT_HOST"] == "algo-2"
        assert sm["SM_INPUT_TRAINING_CONFIG_FILE"].endswith(
            "config/hyperparameters.json"
        )
        assert sm["SM_MODEL_DIR"] == "/opt/ml/model"

    def test_explicit_env_wins(self, tmp_path):
        sm = self._run(
            self._tree(tmp_path),
            extra_env=[("SM_CHANNEL_TRAIN", "/elsewhere"), ("SM_CURRENT_HOST", "me")],
        )
        assert sm["SM_CHANNEL_TRAIN"] == "/elsewhere"
        assert sm["SM_CURRENT_HOST"] == "me"

    def test_no_tree_defaults_single_host(self, tmp_path):
        sm = self._run(tmp_path / "absent")
        assert json.loads(sm["SM_HOSTS"]) == ["algo-1"]
        assert sm["SM_CURRENT_HOST"] == "algo-1"
        assert "SM_CHANNEL_TRAIN" not in sm
