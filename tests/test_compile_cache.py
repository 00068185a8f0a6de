"""The persistent XLA compilation cache is placed from outside.

Resolution order (utils/compile_cache.py): ``JAX_COMPILATION_CACHE_DIR`` set
-> jax already uses it and the code sets no directory; unset -> one fixed
directory inside the checkout, never a temp name, a pid or a time. That
``train`` and ``serve`` both arm it before their first compile, through the
entry points, is asserted on the CPU rehearsal of ``chip_smoke.py``
(tests/test_chip_smoke.py: trainer and server entries land under the
directory the environment names); cold versus warm compile seconds are a chip
observation (CHANGES.md PR 21).
"""

import os
import re
import subprocess
import tempfile

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_module(monkeypatch):
    """compile_cache with its process-once latch reset, and jax's cache
    config restored afterwards (the suite runs with the cache disabled)."""
    import jax

    from sagemaker_xgboost_container_tpu.utils import compile_cache

    monkeypatch.setattr(compile_cache, "_resolved", None)
    saved = {
        name: getattr(jax.config, name)
        for name in (
            "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes",
        )
    }
    yield compile_cache
    for name, value in saved.items():
        jax.config.update(name, value)


def test_env_dir_means_the_code_sets_no_directory(cache_module, monkeypatch, tmp_path):
    import jax

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "outside"))
    updates = []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda name, value: (updates.append(name), real_update(name, value))[1],
    )
    assert cache_module.enable_compile_cache() == str(tmp_path / "outside")
    assert "jax_compilation_cache_dir" not in updates
    # the zero write thresholds are still applied: small programs cache too
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1
    # resolved once per process: a later env flip must not re-arm mid-job
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "other"))
    assert cache_module.enable_compile_cache() == str(tmp_path / "outside")


def test_unset_env_uses_one_fixed_directory_inside_the_checkout(
    cache_module, monkeypatch
):
    import jax

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = cache_module.enable_compile_cache()
    assert path == cache_module.DEFAULT_CACHE_DIR == os.path.join(REPO_ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    # a directory that moves never hits: no temp name, pid or time in it
    assert not path.startswith(tempfile.gettempdir())
    assert str(os.getpid()) not in path
    assert not re.search(r"\d{6,}", path)


def test_unwritable_dir_degrades_not_fails(cache_module, monkeypatch, tmp_path):
    blocker = tmp_path / "blocker"
    blocker.write_text("not a directory")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(blocker / "cache"))
    assert cache_module.enable_compile_cache() is None


def test_old_private_knob_is_gone():
    old_knob = "GRAFT_COMPILE" + "_CACHE_DIR"  # replaced, not kept beside
    hits = subprocess.run(
        ["grep", "-rl", old_knob, "--exclude=CHANGES.md", "--exclude=ISSUE.md",
         "--exclude=PERF_LEDGER.jsonl", "--exclude-dir=.git",
         "--exclude-dir=.chipwork", "--exclude-dir=chiprun_out", REPO_ROOT],
        capture_output=True, text=True,
    ).stdout.split()
    assert hits == []
