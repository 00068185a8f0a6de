"""TPU-native gradient-boosting training & serving container.

A JAX/XLA re-implementation of the SageMaker XGBoost container's contract
(hyperparameter schema, data channels, metrics, serving API, distributed,
checkpoint/resume, selectable inference) over an XLA histogram tree builder
sharded across a TPU mesh instead of libxgboost + Rabit/NCCL.
"""

import time as _time

_IMPORT_BEGAN = _time.time()  # the package's first line, by the wall clock

import sys as _sys  # noqa: E402

__version__ = "0.1.0"

#: ``(start, end, jax_inside)`` of the package's own imports, by the wall
#: clock: this file's and that of each module that notes its own (``models``,
#: which brings ``train`` in, and ``training``, a job's). ``jax_inside``: the
#: import was the first to bring jax in. One inside another counts once:
#: ``telemetry.spans.record_startup`` takes their union for the span
#: ``startup.package_import``; what a caller runs between them is not in it.
IMPORT_INTERVALS = []


def import_began():
    """A module's first line: the clock, and whether jax is loaded yet."""
    return _time.time(), "jax" in _sys.modules


def note_import(began):
    """A module's last line: its import ran from ``began`` to now."""
    start, jax_before = began
    IMPORT_INTERVALS.append((start, _time.time(), not jax_before and "jax" in _sys.modules))


note_import((_IMPORT_BEGAN, "jax" in _sys.modules))
