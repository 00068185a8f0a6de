"""chip_smoke.py: the on-demand proof that `train` and `serve` start on the
chip. Here, on the CPU: the explicit rehearsal mode runs the same phases at
the same widths and passes; the default mode finds no TPU and fails fast —
it never trains on the CPU in the chip run's name."""

import json
import os
import re
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(*args, timeout, **extra_env):
    env = dict(os.environ)
    # the smoke sets its children's platform itself; it must not depend on
    # the harness's virtual devices
    env.pop("XLA_FLAGS", None)
    env.update(extra_env)
    return subprocess.run(
        [sys.executable, SMOKE, *args],
        env=env,
        capture_output=True,
        text=True,
        timeout=timeout,
    )


def test_cpu_rehearsal_runs_every_phase_and_passes(tmp_path):
    cache_dir = tmp_path / "xla-cache"
    result = _run(
        "--cpu-rehearsal", timeout=600, JAX_COMPILATION_CACHE_DIR=str(cache_dir)
    )
    assert result.returncode == 0, result.stderr[-4000:] + result.stdout[-2000:]
    lines = result.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    out = result.stdout
    for phase in ("train_fused: 16 rounds", "train_checkpointed: 4 rounds", "serve:"):
        assert phase in out, out
    # every observation is labeled as one, never under a metric's name
    assert "smoke observation" in out and "rounds/sec" not in out
    # the compile cache is placed from outside: the environment passes
    # through untouched, and BOTH entry points arm it before their first
    # compile — the trainers' and then the server's executables land under
    # the directory JAX_COMPILATION_CACHE_DIR names (tests/test_compile_cache.py
    # covers the resolution order)
    report = re.search(
        r"compile cache: (\S+) \((\d+) entries after the trainers, (\d+) after "
        r"the server; JAX_COMPILATION_CACHE_DIR set\)", out,
    )
    assert report and report.group(1) == str(cache_dir), out
    assert 0 < int(report.group(2)) < int(report.group(3)), out
    assert len([f for f in os.listdir(cache_dir) if f.endswith("-cache")]) == int(
        report.group(3)
    )


def test_default_mode_without_a_tpu_fails_fast_and_prints_no_result():
    t0 = time.monotonic()
    result = _run(timeout=120)
    assert result.returncode != 0
    assert time.monotonic() - t0 < 60
    assert "no TPU device" in result.stderr
    assert '"ok"' not in result.stdout


def test_parent_never_imports_jax():
    """One process per chip: a parent that had touched JAX would hold the
    chip its children need. The parent is stdlib + numpy + pyarrow."""
    with open(SMOKE) as f:
        source = f.read()
    code = (
        "import sys, runpy\n"
        "sys.argv = ['chip_smoke.py', '--help']\n"
        "try:\n"
        "    runpy.run_path({!r}, run_name='__main__')\n"
        "except SystemExit:\n"
        "    pass\n"
        "assert 'jax' not in sys.modules, 'chip_smoke.py imported jax'\n"
    ).format(SMOKE)
    subprocess.run([sys.executable, "-c", code], check=True, capture_output=True)
    assert "\nimport jax" not in source and "\nfrom jax" not in source
