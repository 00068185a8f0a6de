"""Contract tests for the stdlib coverage gate (scripts/covgate.py) — the
reference's --cov-fail-under=60 (tox.ini:29-30) must actually evaluate, not
silently disarm."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.skipif(
    not hasattr(sys, "monitoring"), reason="covgate needs python >= 3.12"
)


def _run_gated(tmp_path, fail_under, test_body):
    """Run a tiny pytest session under the covgate plugin in a subprocess."""
    t = tmp_path / "test_tiny.py"
    t.write_text(test_body)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run(
        [
            sys.executable,
            "-m",
            "pytest",
            str(t),
            "-q",
            "-p",
            "scripts.covgate",
            "--covgate-fail-under={}".format(fail_under),
            "-p",
            "no:cacheprovider",
        ],
        cwd=str(tmp_path),
        env=env,
        capture_output=True,
        text=True,
        timeout=300,
    )


BODY = """
def test_uses_package():
    from sagemaker_xgboost_container_tpu.toolkit import exceptions as exc
    try:
        raise exc.UserError("x")
    except exc.UserError:
        pass
"""


def test_gate_passes_below_threshold(tmp_path):
    r = _run_gated(tmp_path, 0.1, BODY)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "covgate:" in r.stderr
    doc = json.load(open(tmp_path / ".covgate.json"))
    # unimported package files still count their executable lines
    assert doc["total_lines"] > 5000, doc["total_lines"]
    assert doc["total_pct"] > 0


def test_gate_fails_above_threshold(tmp_path):
    r = _run_gated(tmp_path, 99.0, BODY)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "FAILED the 99.0% gate" in r.stderr


def test_ci_full_tier_arms_a_gate():
    """ci.sh full must never run ungated: either pytest-cov, covgate, or a
    hard failure (exit 3)."""
    with open(os.path.join(REPO, "scripts", "ci.sh")) as f:
        src = f.read()
    assert "covgate" in src and "exit 3" in src
