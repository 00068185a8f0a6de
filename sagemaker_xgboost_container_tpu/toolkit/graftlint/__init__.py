"""graftlint — the repo's multi-pass static analyzer.

One tool owns the machine-checked policies that previously lived in one-off
scripts (``scripts/check_no_print.py``, ``scripts/check_no_bare_except.py``)
or, worse, in reviewers' heads. Four pass families (docs/static-analysis.md
catalogues every rule):

* **trace-safety** — functions reachable from ``jax.jit``/``shard_map``
  closures must not read env knobs (resolve at session build time, the
  ``resolve_hist_knobs`` pattern), must not construct un-cached jit
  wrappers (the per-round re-sketch recompile class), and must not sync to
  host (``.item()``, ``np.asarray`` on device values, ``print``).
* **concurrency & I/O discipline** — sockets read/accept/connect under a
  timeout (or the bounded-read helpers), threads declare ``daemon=``
  explicitly, and state shared with a daemon-thread entrypoint is written
  under its lock.
* **contract drift** — every ``SM_*``/``GRAFT_*`` env knob, telemetry
  metric name, fault-point string, and supervision exit code is
  cross-checked against the documented tables in ``docs/observability.md``
  and ``docs/robustness.md`` — both directions (undocumented code names
  and orphaned doc rows fail).
* **legacy gates** — the no-print and no-bare-except policies, re-homed.

CLI (``scripts/graftlint.py`` is the canonical invocation — it loads this
subpackage via importlib under a private alias, so the gate still reports
exit 2 on a tree whose package ``__init__`` chain doesn't import;
``python -m ...toolkit.graftlint`` also works on a healthy tree)::

    python scripts/graftlint.py \
        [--format text|json] [--select r1,r2] [--stats] [paths...]

Per-line suppression: ``# graftlint: disable=<rule>[,<rule>] <reason>``
(a reason string is required — a bare suppression still suppresses but is
itself reported). Grandfathered findings live in
``scripts/graftlint_baseline.json``; keep it empty.

Dependency-free by design: stdlib ``ast`` + ``re`` only, so the gate runs
in every tier of every image.
"""

from .core import Finding, Project, run  # noqa: F401

__all__ = ["Finding", "Project", "run"]
