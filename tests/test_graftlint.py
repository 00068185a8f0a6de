"""graftlint: the multi-pass static analyzer (docs/static-analysis.md).

Coverage model: one known-bad + one known-good fixture per rule family —
including regression fixtures reproducing the two shipped bug shapes the
analyzer exists to prevent (the PR-4 per-round uncached-jit recompile and
the PR-3 timeout-less trickle ``recv``) — plus suppression and baseline
semantics, CLI contract (exit codes, JSON, ``--stats``), the legacy-gate
shims, and the self-check that the shipped package + docs are clean under
the non-baselined rule set.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = "sagemaker_xgboost_container_tpu"

from sagemaker_xgboost_container_tpu.toolkit.graftlint import core  # noqa: E402
from sagemaker_xgboost_container_tpu.toolkit.graftlint.__main__ import (  # noqa: E402
    main as graftlint_main,
)


# --------------------------------------------------------------- fixtures


def make_tree(tmp_path, files, docs=None):
    """Build a throwaway repo root: ``files`` land under the package dir,
    ``docs`` under docs/. Returns the root as str."""
    pkg = tmp_path / PKG
    pkg.mkdir(exist_ok=True)
    for rel, src in files.items():
        path = pkg / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(src))
    for rel, text in (docs or {}).items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(text))
    return str(tmp_path)


def run_rules(root, *rules, **kwargs):
    report = core.run(root, select=list(rules) or None,
                      use_baseline=kwargs.pop("use_baseline", False), **kwargs)
    assert not report.errors, report.errors
    return report


def rule_set(report):
    return {f.rule for f in report.findings}


# ----------------------------------------------------------- trace-safety


def test_trace_env_read_flags_reachable_function(tmp_path):
    root = make_tree(tmp_path, {"mod.py": """\
        import os
        import jax

        def kernel(x):
            chunk = int(os.environ.get("GRAFT_CHUNK", "1"))
            return x * chunk

        round_fn = jax.jit(kernel)
        """})
    report = run_rules(root, "trace-env-read")
    assert [f.rule for f in report.findings] == ["trace-env-read"]
    assert "GRAFT_CHUNK" in report.findings[0].message


def test_trace_env_read_follows_call_graph_and_spares_unreachable(tmp_path):
    root = make_tree(tmp_path, {"mod.py": """\
        import os
        import jax

        def helper():
            return os.environ.get("GRAFT_DEEP")

        def kernel(x):
            return x + helper()

        def session_builder():
            # host-side: env reads here are the CORRECT pattern
            knob = os.environ.get("GRAFT_SESSION_KNOB", "a")
            return jax.jit(kernel), knob
        """})
    report = run_rules(root, "trace-env-read")
    # helper is reachable THROUGH kernel; session_builder itself is not a root
    assert len(report.findings) == 1
    assert "GRAFT_DEEP" in report.findings[0].message


def test_trace_pass_reaches_double_buffered_builder_helpers(tmp_path):
    """The tree builders (ops/tree_build, ops/lossguide) call helpers from
    comprehensions and nested closures (a pass's store writes, the per-table
    reads of a split step). The name-based call graph must keep treating
    that shape as jit-reachable so trace-env-read / trace-host-sync still
    cover the hot path."""
    root = make_tree(tmp_path, {"mod.py": """\
        import os
        import jax

        def apply_collective(g):
            # BAD: env read on the traced path, reached via comprehension
            return g * int(os.environ.get("GRAFT_COMM_KNOB", "1"))

        def scan_batch(g):
            # BAD: host sync on the traced path, reached via nested closure
            return g.item()

        def build_tree(gs):
            batches = [apply_collective(g) for g in gs]

            def _batch(g):
                return scan_batch(g)

            return [_batch(g) for g in batches]

        round_fn = jax.jit(build_tree)
        """})
    report = run_rules(root, "trace-env-read", "trace-host-sync")
    assert rule_set(report) == {"trace-env-read", "trace-host-sync"}
    assert any("GRAFT_COMM_KNOB" in f.message for f in report.findings)


def test_trace_env_read_envconfig_helper_definition_exempt(tmp_path):
    # the call SITE is the policy surface: a traced caller of env_int is
    # flagged, but the helper's own os.getenv body is not — otherwise every
    # justified (suppressed) caller would re-surface the read one level down
    root = make_tree(tmp_path, {
        "utils/envconfig.py": """\
            import os

            def env_int(name, default):
                raw = os.getenv(name)
                return int(raw) if raw else default
            """,
        "mod.py": """\
            import jax
            from .utils.envconfig import env_int

            def kernel(x):
                return x * env_int("GRAFT_SCALE", 1)

            f = jax.jit(kernel)
            """,
    })
    report = run_rules(root, "trace-env-read")
    assert [(f.path, f.rule) for f in report.findings] == [
        (PKG + "/mod.py", "trace-env-read")
    ]
    assert "GRAFT_SCALE" in report.findings[0].message


def test_trace_env_read_resolves_absolute_imports_when_root_is_package_dir(tmp_path):
    """Scan root = the package dir itself: module keys lose the package
    prefix while absolute imports keep it; the prefix-tolerant lookup must
    still connect the call graph (a silent miss here exits 0 on a dirty
    tree)."""
    make_tree(tmp_path, {
        "helper.py": """\
            import os

            def leaky():
                return os.environ.get("GRAFT_X")
            """,
        "mod.py": """\
            import jax
            from sagemaker_xgboost_container_tpu.helper import leaky

            def kernel(x):
                return leaky()

            jitted = jax.jit(kernel)
            """,
    })
    report = run_rules(str(tmp_path / PKG), "trace-env-read")
    assert rule_set(report) == {"trace-env-read"}
    assert report.findings[0].path == "helper.py"


def test_uncached_jit_regression_pr4_resketch_shape(tmp_path):
    # the PR-4 bug: a jit wrapper constructed per call inside the per-round
    # re-sketch path — every round recompiled from an empty cache
    root = make_tree(tmp_path, {"binning.py": """\
        import jax
        import jax.numpy as jnp

        def device_cut_points(values, max_cuts):
            fn = jax.jit(lambda v: jnp.sort(v)[:max_cuts])
            return fn(values)
        """})
    report = run_rules(root, "trace-uncached-jit")
    assert [f.rule for f in report.findings] == ["trace-uncached-jit"]


def test_uncached_jit_cached_factory_and_module_level_are_clean(tmp_path):
    root = make_tree(tmp_path, {"binning.py": """\
        import functools
        import jax
        import jax.numpy as jnp

        @functools.lru_cache(maxsize=None)
        def _cut_points_kernel(max_cuts):
            return jax.jit(lambda v: jnp.sort(v)[:max_cuts])

        _APPLY = jax.jit(jnp.digitize)

        def device_cut_points(values, max_cuts):
            return _cut_points_kernel(max_cuts)(values)
        """})
    assert not run_rules(root, "trace-uncached-jit").findings


def test_trace_host_sync_flags_item_and_print(tmp_path):
    root = make_tree(tmp_path, {"mod.py": """\
        import jax

        def body(x):
            print(x)
            return x.sum().item()

        f = jax.jit(body)
        """})
    report = run_rules(root, "trace-host-sync")
    assert len(report.findings) == 2
    assert all(f.rule == "trace-host-sync" for f in report.findings)


def test_trace_host_sync_ignores_unreachable(tmp_path):
    root = make_tree(tmp_path, {"mod.py": """\
        def host_summary(x):
            return x.sum().item()
        """})
    assert not run_rules(root, "trace-host-sync").findings


# ------------------------------------------------- concurrency discipline


def test_socket_unbounded_regression_pr3_recv_shape(tmp_path):
    # the PR-3 master hang: a recv loop with no deadline anywhere — a peer
    # trickling one byte per timeout window wedges the reader forever
    root = make_tree(tmp_path, {"net.py": """\
        def recv_exact(sock, n):
            buf = b""
            while len(buf) < n:
                chunk = sock.recv(n - len(buf))
                if not chunk:
                    raise ConnectionError("peer closed")
                buf += chunk
            return buf
        """})
    report = run_rules(root, "socket-unbounded")
    assert [f.rule for f in report.findings] == ["socket-unbounded"]


def test_socket_with_timeout_in_scope_is_clean(tmp_path):
    root = make_tree(tmp_path, {"net.py": """\
        def recv_bounded(sock, n, timeout):
            sock.settimeout(timeout)
            buf = b""
            while len(buf) < n:
                chunk = sock.recv(n - len(buf))
                if not chunk:
                    raise ConnectionError("peer closed")
                buf += chunk
            return buf
        """})
    assert not run_rules(root, "socket-unbounded").findings


def test_socket_member_timeout_set_elsewhere_in_class_is_clean(tmp_path):
    root = make_tree(tmp_path, {"net.py": """\
        class Listener:
            def start(self):
                self._sock.settimeout(5.0)

            def poll(self):
                return self._sock.accept()
        """})
    assert not run_rules(root, "socket-unbounded").findings


def test_thread_daemon_missing(tmp_path):
    root = make_tree(tmp_path, {"mod.py": """\
        import threading

        def spawn(fn):
            good = threading.Thread(target=fn, daemon=True)
            also_good = threading.Thread(target=fn, daemon=False)
            bad = threading.Thread(target=fn)
            return good, also_good, bad
        """})
    report = run_rules(root, "thread-daemon-missing")
    assert [f.rule for f in report.findings] == ["thread-daemon-missing"]


def test_shared_state_unlocked(tmp_path):
    root = make_tree(tmp_path, {"worker.py": """\
        import threading

        class Batcher:
            def __init__(self):
                self._lock = threading.Lock()
                self._carry = None  # __init__ writes are exempt
                self._t = threading.Thread(target=self._run, daemon=True)

            def _run(self):
                while True:
                    self._carry = 1  # BAD: daemon thread, no lock

            def poll(self):
                with self._lock:
                    self._carry = None  # good: under the lock
        """})
    report = run_rules(root, "shared-state-unlocked")
    assert len(report.findings) == 1
    assert "_carry" in report.findings[0].message


def test_shared_state_all_locked_is_clean(tmp_path):
    root = make_tree(tmp_path, {"worker.py": """\
        import threading

        class Batcher:
            def __init__(self):
                self._lock = threading.Lock()
                self._carry = None
                self._t = threading.Thread(target=self._run, daemon=True)

            def _run(self):
                while True:
                    with self._lock:
                        self._carry = 1

            def poll(self):
                with self._lock:
                    self._carry = None
        """})
    assert not run_rules(root, "shared-state-unlocked").findings


# --------------------------------------------------------- contract drift

CONTRACT_DOCS = {
    "docs/observability.md": """\
        # Observability
        | Env var | Default | Effect |
        | --- | --- | --- |
        | `GRAFT_DOCD_KNOB` | `1` | documented knob, exists in code |
        | `GRAFT_GHOST_KNOB` | `1` | documented knob, gone from code |

        | Metric | Type | Meaning |
        | --- | --- | --- |
        | `widget_spins_total` | counter | documented, exists |
        | `widget_ghost_total` | counter | documented, gone |
        """,
    "docs/robustness.md": """\
        # Robustness
        | Code | Meaning | Source |
        | --- | --- | --- |
        | `85` | documented, exists | constants.py |
        | `86` | documented, no constant behind it | nowhere |

        | Fault point | Fires in |
        | --- | --- |
        | `data.read` | readers |
        | `ghost.point` | nowhere |
        """,
}

CONTRACT_CODE = {
    "constants.py": """\
        SM_HOSTS = "SM_HOSTS"  # platform contract: self-named, exempt
        EXIT_DOCUMENTED = 85
        EXIT_UNDOCUMENTED = 87
        """,
    "app.py": """\
        import os
        from .utils.faults import fault_point
        from .telemetry.registry import get_registry

        REG = get_registry()

        def configure():
            a = os.environ.get("GRAFT_DOCD_KNOB")
            b = os.environ.get("GRAFT_UNDOC_KNOB")
            c = os.environ.get("SM_HOSTS")  # platform name: exempt
            REG.counter("widget_spins_total").inc()
            REG.counter("widget_undoc_total").inc()
            fault_point("data.read")
            fault_point("secret.site")
            return a, b, c
        """,
}


def test_contract_drift_both_directions(tmp_path):
    root = make_tree(tmp_path, CONTRACT_CODE, docs=CONTRACT_DOCS)
    report = core.run(root, use_baseline=False)
    by_rule = {}
    for f in report.findings:
        by_rule.setdefault(f.rule, []).append(f.message)

    assert any("GRAFT_UNDOC_KNOB" in m
               for m in by_rule["contract-env-undocumented"])
    assert any("GRAFT_GHOST_KNOB" in m
               for m in by_rule["contract-env-orphaned"])
    assert any("widget_undoc_total" in m
               for m in by_rule["contract-metric-undocumented"])
    assert any("widget_ghost_total" in m
               for m in by_rule["contract-metric-orphaned"])
    assert any("secret.site" in m
               for m in by_rule["contract-fault-undocumented"])
    assert any("ghost.point" in m
               for m in by_rule["contract-fault-orphaned"])
    assert any("EXIT_UNDOCUMENTED" in m
               for m in by_rule["contract-exit-undocumented"])
    assert any("86" in m for m in by_rule["contract-exit-orphaned"])

    # documented + existing names are clean in both directions
    flat = "\n".join(m for ms in by_rule.values() for m in ms)
    assert "GRAFT_DOCD_KNOB" not in flat
    assert "widget_spins_total" not in flat
    assert "data.read" not in flat
    assert "SM_HOSTS" not in flat


def test_contract_pass_skips_fixture_trees_without_docs(tmp_path):
    root = make_tree(tmp_path, {"app.py": """\
        import os

        def configure():
            return os.environ.get("GRAFT_UNDOC_KNOB")
        """})
    report = core.run(root, select=[r for r in core.known_rules()
                                    if r.startswith("contract-")],
                      use_baseline=False)
    assert not report.findings


# ------------------------------------------------------------ legacy gates


def test_no_print_rule_and_allowlist(tmp_path):
    root = make_tree(tmp_path, {
        "leaky.py": "def f():\n    print('leak')\n",
        "version_contract.py": "def f():\n    print('verdict')\n",  # allowlisted
    })
    report = run_rules(root, "no-print")
    assert [f.path for f in report.findings] == [PKG + "/leaky.py"]


def test_no_bare_except_rule(tmp_path):
    root = make_tree(tmp_path, {"handler.py": """\
        def f():
            try:
                return 1
            except:
                return 2
        """})
    report = run_rules(root, "no-bare-except")
    assert [f.rule for f in report.findings] == ["no-bare-except"]


def test_legacy_shims_still_work():
    """The deprecated script entrypoints keep their exit-code contract and
    module API (tox/ci.sh/test invocations from PRs 1 and 3 must not break)."""
    for script in ("check_no_print.py", "check_no_bare_except.py"):
        result = subprocess.run(
            [sys.executable, os.path.join(REPO_ROOT, "scripts", script)],
            capture_output=True, text=True,
        )
        assert result.returncode == 0, (script, result.stderr)
        assert "deprecated shim" in result.stderr

    sys.path.insert(0, os.path.join(REPO_ROOT, "scripts"))
    try:
        import check_no_bare_except
        import check_no_print

        assert check_no_print.find_print_calls("print(1)\n", "<m>") == [1]
        assert check_no_bare_except.find_bare_excepts(
            "try:\n    pass\nexcept:\n    pass\n", "<m>"
        ) == [3]
    finally:
        sys.path.pop(0)


# ------------------------------------------------ suppressions & baseline


def test_suppression_same_line_and_line_above(tmp_path):
    root = make_tree(tmp_path, {"mod.py": """\
        def f():
            print('a')  # graftlint: disable=no-print stdout contract for the drill
            # graftlint: disable=no-print covers the next code line
            print('b')
            print('c')
        """})
    report = run_rules(root, "no-print")
    assert len(report.findings) == 1  # only the unsuppressed print('c')
    assert report.findings[0].line == 5
    assert len(report.suppressed) == 2


def test_reasonless_suppression_is_itself_reported(tmp_path):
    root = make_tree(tmp_path, {"mod.py": """\
        def f():
            print('a')  # graftlint: disable=no-print
        """})
    report = core.run(root, use_baseline=False)
    assert rule_set(report) == {"suppression-missing-reason"}


def test_baseline_grandfathers_by_content_not_line_number(tmp_path):
    root = make_tree(tmp_path, {"mod.py": "def f():\n    print('x')\n"})
    baseline = os.path.join(root, "baseline.json")

    report = core.run(root, use_baseline=False)
    core.write_baseline(baseline, report.project, report.findings)

    clean = core.run(root, baseline_path=baseline)
    assert not clean.findings and len(clean.baselined) == 1

    # edits ABOVE the finding shift its line number; content keying holds
    (tmp_path / PKG / "mod.py").write_text(
        "import sys\n\n\ndef f():\n    print('x')\n"
    )
    shifted = core.run(root, baseline_path=baseline)
    assert not shifted.findings and len(shifted.baselined) == 1

    # a NEW finding is not grandfathered by an unrelated baseline entry
    (tmp_path / PKG / "mod.py").write_text(
        "def f():\n    print('x')\n\n\ndef g():\n    print('y')\n"
    )
    dirty = core.run(root, baseline_path=baseline)
    assert len(dirty.findings) == 1 and len(dirty.baselined) == 1


# ---------------------------------------------------------------- the CLI


def test_cli_exits_nonzero_on_every_rule_family(tmp_path, capsys):
    root = make_tree(tmp_path, {
        "trace_bad.py": """\
            import os
            import jax

            def kernel(x):
                fn = jax.jit(lambda v: v)
                return fn(x), os.environ.get("GRAFT_BAD"), x.item()

            f = jax.jit(kernel)
            """,
        "net_bad.py": """\
            import threading

            def reader(sock):
                t = threading.Thread(target=reader)
                return sock.recv(4)
            """,
        "legacy_bad.py": """\
            def f():
                try:
                    print('x')
                except:
                    pass
            """,
        "constants.py": "EXIT_NEW = 95\n",
        "knob.py": "import os\nK = os.environ.get('GRAFT_CLI_UNDOC')\n",
    }, docs={
        "docs/observability.md": "# empty tables\n",
        "docs/robustness.md": "# empty tables\n",
    })
    rc = graftlint_main(["--root", root, "--format", "json"])
    assert rc == 1
    payload = json.loads(capsys.readouterr().out)
    rules_hit = {f["rule"] for f in payload["findings"]}
    # every family trips: trace-safety, concurrency/IO, contract, legacy
    assert {"trace-env-read", "trace-uncached-jit", "trace-host-sync",
            "socket-unbounded", "thread-daemon-missing",
            "contract-env-undocumented", "contract-exit-undocumented",
            "no-print", "no-bare-except"} <= rules_hit
    assert payload["stats"]["no-print"]["live"] == 1


def test_cli_clean_tree_exits_zero(tmp_path, capsys):
    root = make_tree(tmp_path, {"ok.py": "X = 1\n"})
    assert graftlint_main(["--root", root]) == 0
    assert "graftlint: OK" in capsys.readouterr().err


def test_cli_unparseable_file_exits_two(tmp_path, capsys):
    root = make_tree(tmp_path, {"broken.py": "def f(:\n"})
    assert graftlint_main(["--root", root]) == 2


def test_cli_stats_and_list_rules(tmp_path, capsys):
    root = make_tree(tmp_path, {"mod.py": "def f():\n    print('x')\n"})
    rc = graftlint_main(["--root", root, "--stats"])
    err = capsys.readouterr().err
    assert rc == 1 and "rule hit counts" in err and "no-print" in err

    assert graftlint_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule in ("trace-env-read", "socket-unbounded",
                 "contract-env-undocumented", "no-print",
                 "suppression-missing-reason"):
        assert rule in out


def test_cli_select_and_disable(tmp_path):
    root = make_tree(tmp_path, {"mod.py": """\
        def f():
            try:
                print('x')
            except:
                pass
        """})
    assert graftlint_main(["--root", root, "--select", "no-bare-except"]) == 1
    assert graftlint_main(
        ["--root", root, "--disable", "no-print,no-bare-except"]
    ) == 0


def test_cli_write_baseline_roundtrip(tmp_path, capsys):
    root = make_tree(tmp_path, {"mod.py": "def f():\n    print('x')\n"})
    baseline = os.path.join(root, "bl.json")
    assert graftlint_main(
        ["--root", root, "--baseline", baseline, "--write-baseline"]
    ) == 0
    assert graftlint_main(["--root", root, "--baseline", baseline]) == 0
    assert graftlint_main(["--root", root, "--no-baseline"]) == 1


def test_cli_write_baseline_preserves_grandfathered_entries(tmp_path, capsys):
    """Regenerating must keep still-live entries the existing baseline
    already grandfathers: the run that feeds --write-baseline is itself
    baseline-filtered, so writing only report.findings would silently
    un-grandfather everything old and fail the next CI run."""
    root = make_tree(tmp_path, {"mod.py": "def f():\n    print('x')\n"})
    baseline = os.path.join(root, "bl.json")
    assert graftlint_main(
        ["--root", root, "--baseline", baseline, "--write-baseline"]
    ) == 0

    # a second finding appears; regenerate to grandfather it too
    (tmp_path / PKG / "mod.py").write_text(
        "def f():\n    print('x')\n\n\ndef g():\n    print('y')\n"
    )
    assert graftlint_main(
        ["--root", root, "--baseline", baseline, "--write-baseline"]
    ) == 0
    with open(baseline) as f:
        contexts = {e["context"] for e in json.load(f)["entries"]}
    assert contexts == {"print('x')", "print('y')"}
    assert graftlint_main(["--root", root, "--baseline", baseline]) == 0


def test_cli_write_baseline_narrowed_scope_carries_other_entries(tmp_path):
    """A --select-narrowed regeneration must not drop baseline entries for
    rules (or unscanned-but-present files) outside the run's scope — they
    had no chance to re-match."""
    root = make_tree(tmp_path, {"mod.py": """\
        def f():
            try:
                print('x')
            except:
                pass
        """})
    baseline = os.path.join(root, "bl.json")
    assert graftlint_main(
        ["--root", root, "--baseline", baseline, "--write-baseline"]
    ) == 0
    with open(baseline) as f:
        assert {e["rule"] for e in json.load(f)["entries"]} == {
            "no-print", "no-bare-except",
        }

    # regenerate considering ONLY no-print: the no-bare-except entry rides
    assert graftlint_main(
        ["--root", root, "--baseline", baseline, "--select", "no-print",
         "--write-baseline"]
    ) == 0
    with open(baseline) as f:
        assert {e["rule"] for e in json.load(f)["entries"]} == {
            "no-print", "no-bare-except",
        }
    assert graftlint_main(["--root", root, "--baseline", baseline]) == 0

    # but an entry whose finding was FIXED (in scope, no longer matching)
    # is dropped on regeneration
    (tmp_path / PKG / "mod.py").write_text(
        "def f():\n    try:\n        pass\n    except:\n        pass\n"
    )
    assert graftlint_main(
        ["--root", root, "--baseline", baseline, "--select", "no-print",
         "--write-baseline"]
    ) == 0
    with open(baseline) as f:
        assert {e["rule"] for e in json.load(f)["entries"]} == {"no-bare-except"}


def test_standalone_launcher_reports_on_broken_package_tree(tmp_path):
    """scripts/graftlint.py must not import the product package: on a tree
    whose package __init__ chain doesn't even parse, the gate still runs
    and reports exit 2 instead of dying with an import traceback."""
    pkg = tmp_path / PKG
    pkg.mkdir()
    (pkg / "__init__.py").write_text("import jax (\n")  # SyntaxError
    (pkg / "busted.py").write_text("def f(:\n")
    result = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "scripts", "graftlint.py"),
         "--root", str(tmp_path)],
        capture_output=True, text=True,
    )
    assert result.returncode == 2, (result.stdout, result.stderr)
    assert "cannot parse" in result.stderr
    assert "Traceback" not in result.stderr


# ------------------------------------------------------------- self-check


def test_shipped_package_is_clean_without_baseline():
    """Acceptance gate: the shipped package + docs pass the FULL rule set
    with no baseline, and the checked-in baseline is empty (grandfathered
    debt is not allowed to accumulate silently — docs/static-analysis.md)."""
    report = core.run(REPO_ROOT, use_baseline=False)
    assert not report.errors, report.errors
    assert not report.findings, [
        "{}:{} [{}]".format(f.path, f.line, f.rule) for f in report.findings
    ]
    # every inline suppression that fired carries a reason
    assert all(s.reason for _, s in report.suppressed)

    with open(os.path.join(REPO_ROOT, core.DEFAULT_BASELINE)) as f:
        assert json.load(f)["entries"] == []
