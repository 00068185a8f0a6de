"""Multi-host built-image cluster tier.

Runs scripts/image_cluster.sh: builds the image, then (a) a 2-host
docker-compose cluster trains over ShardedByS3Key data and exactly one host
saves, (b) SIGTERM mid-train persists exactly one intermediate model, (c)
the MME REST lifecycle runs against a real `docker run`. Skip-marked where
Docker is unavailable (this dev host); structured to run anywhere Docker
exists. The pieces that need no Docker — the script's bash syntax, the
SM_JAX_DISTRIBUTED=on force-gate, and the master-only SIGTERM save it
asserts — are tested unconditionally below and in tests/test_parallel.py.
"""

import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "scripts", "image_cluster.sh")


def test_cluster_script_is_valid_bash():
    r = subprocess.run(["bash", "-n", SCRIPT], capture_output=True, text=True)
    assert r.returncode == 0, r.stderr


def test_cluster_script_covers_reference_guarantees():
    """The three reference bars stay wired: exactly-one-save, mid-train
    kill, MME lifecycle (local_mode.py:477-557, test_early_stopping.py:
    57-68, test_multiple_model_endpoint.py:32-101)."""
    with open(SCRIPT) as f:
        src = f.read()
    assert "ShardedByS3Key" in src
    assert "save_model_on_termination" in src
    assert "exactly 1" in src
    for route in ("/models", "/invoke"):
        assert route in src
    # the compose cluster must force a REAL multi-process runtime on CPU
    assert 'SM_JAX_DISTRIBUTED: "on"' in src


def test_sm_jax_distributed_on_forces_cpu_cluster():
    """SM_JAX_DISTRIBUTED=on must initialize jax.distributed even on the
    CPU backend (the compose tier depends on it); 'auto' must keep
    skipping. Runs in subprocesses — jax.distributed is process-global."""
    from tests.util_ports import free_port

    code = (
        "import sys, jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "from sagemaker_xgboost_container_tpu.training.algorithm_train import (\n"
        "    maybe_init_jax_distributed)\n"
        "up = maybe_init_jax_distributed(\n"
        "    ['127.0.0.1', 'localhost'], sys.argv[1], port=int(sys.argv[2]))\n"
        "print('UP' if up else 'SKIPPED', jax.device_count())\n"
    )
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"

    # auto: skipped on CPU (no coordinator needed — returns before connect)
    env["SM_JAX_DISTRIBUTED"] = "auto"
    r = subprocess.run(
        [sys.executable, "-c", code, "127.0.0.1", "0"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=120,
    )
    assert "SKIPPED" in r.stdout, r.stdout + r.stderr

    # on: a real 2-process CPU cluster forms; both see 2 global devices
    env["SM_JAX_DISTRIBUTED"] = "on"
    port = str(free_port())
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", code, host, port],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, env=env, cwd=REPO,
        )
        for host in ("127.0.0.1", "localhost")
    ]
    outs = []
    for p in procs:
        out, err = p.communicate(timeout=180)
        assert p.returncode == 0, out + err
        outs.append(out)
    for out in outs:
        assert "UP 2" in out, outs


def test_image_cluster_dry_tier():
    """The docker-less `dry` tier must PASS on this host —
    not skip: Dockerfile structure + COPY sources, the version-contract and
    native-parser gates the image build runs, compose-file syntax, and
    console-script wiring are all checkable without a docker daemon."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        ["bash", SCRIPT, "dry"],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO,
    )
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    assert "DRY TIER OK" in r.stdout


@pytest.mark.skipif(
    shutil.which(os.environ.get("DOCKER", "docker")) is None,
    reason="docker not installed on this host",
)
@pytest.mark.parametrize("tier", ["cluster", "kill", "mme"])
def test_image_cluster_tier(tier):
    r = subprocess.run(
        ["bash", SCRIPT, tier],
        capture_output=True,
        text=True,
        timeout=2400,
    )
    if r.returncode == 75:
        pytest.skip(r.stdout.strip() or "cluster tier unavailable")
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
