"""Dependency-version contract.

The reference asserts its pinned dependency set from inside the built image
(reference test/integration/local/test_versions.py runs
test/resources/versions/train.py in the container). The TPU repo's single
source of truth is version_contract.SUPPORTED, consumed by setup.py
(install_requires), the Dockerfile gate, and this test — so the dev/test
environment, pip resolution, and the shipped image all enforce one list.
"""

import runpy
import subprocess
import sys

from sagemaker_xgboost_container_tpu import version_contract as vc


def test_live_environment_satisfies_contract():
    assert vc.violations() == []


def test_contract_covers_every_install_require():
    reqs = vc.install_requires()
    assert len(reqs) == len(vc.SUPPORTED)
    for name in ("jax", "numpy", "scipy", "pandas", "pyarrow", "protobuf"):
        assert any(r.startswith(name) for r in reqs), name


def test_module_is_importable_without_dependencies():
    """setup.py loads the module by path before install_requires exist —
    module-level code must be stdlib-only."""
    ns = runpy.run_path(vc.__file__.replace(".pyc", ".py"))
    assert callable(ns["install_requires"])


def test_cli_gate_passes_here():
    """`python -m …version_contract` is the Dockerfile gate; it must exit 0
    in a healthy environment and print a definitive line."""
    out = subprocess.run(
        [sys.executable, "-m", "sagemaker_xgboost_container_tpu.version_contract"],
        capture_output=True,
        text=True,
    )
    assert out.returncode == 0, out.stderr
    assert "dependency contract OK" in out.stdout


def test_violation_detection(monkeypatch):
    monkeypatch.setitem(vc.SUPPORTED, "numpy", ">=999.0")
    bad = vc.violations()
    assert any(n == "numpy" for n, _v, _s in bad)
    monkeypatch.setitem(vc.SUPPORTED, "definitely-not-installed-xyz", ">=1.0")
    assert any(v is None for _n, v, _s in vc.violations())
