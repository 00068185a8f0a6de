"""Persistent XLA compilation cache, placed from outside the program.

First-round XLA compile is the dominant fixed cost of every short job: a
repeat training run, a server restart, each process of one chip-tool call.
jax ships a persistent on-disk compilation cache keyed by the serialized
HLO + compile options + backend version; the cache directory is part of
how an entry is found again, so it must not move between processes.

Resolution order, once per process:

* ``JAX_COMPILATION_CACHE_DIR`` set: jax itself already reads it — this
  module sets no directory, so whoever runs the program (an operator, the
  chip tool) decides where entries live and finds them again next time.
* unset: one fixed directory, ``DEFAULT_CACHE_DIR`` (``.jax_cache`` beside
  the package — inside the checkout, listed in ``.gitignore``). Never a
  temp name, a pid or a time: a directory that moves never hits.

Either way the write thresholds are zeroed so small programs cache too.
``train`` and ``serve`` both arm it through
``utils/device_runtime.start_device_runtime`` before their first compile.

Cache correctness is jax's own contract (the key covers the executable's
identity including backend/toolchain versions); an unwritable directory
degrades to a logged warning, never a failed job.
"""

import logging
import os
import threading

logger = logging.getLogger(__name__)

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)

_lock = threading.Lock()
_resolved = None  # None = not yet resolved; "" = resolved to disabled


def enable_compile_cache():
    """Arm jax's persistent compilation cache; returns its directory, or
    None when arming failed. Idempotent and process-once: the first call
    resolves the directory and every later call returns the same answer
    (sessions must see one consistent compile-cache state)."""
    global _resolved
    with _lock:
        if _resolved is not None:
            return _resolved or None
        import jax

        from_env = os.environ.get(CACHE_DIR_ENV, "").strip()
        path = from_env or DEFAULT_CACHE_DIR
        try:
            os.makedirs(path, exist_ok=True)
            if not from_env:
                jax.config.update("jax_compilation_cache_dir", path)
            # cache every executable: short jobs pay many small compiles,
            # which the default write thresholds would skip
            jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
            jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
            # jax latches its cache state at the FIRST compile of the
            # process: if anything jitted before this call, the directory
            # would silently never be read or written — clear the latch
            from jax.experimental.compilation_cache import (
                compilation_cache as _cc,
            )

            _cc.reset_cache()
        except Exception as e:  # arming is an optimization, never an outage
            logger.warning(
                "persistent XLA compilation cache at %r could not be armed: %s",
                path, e,
            )
            _resolved = ""
            return None
        _resolved = path
        logger.info(
            "persistent XLA compilation cache at %s (%s)",
            path, CACHE_DIR_ENV if from_env else "default",
        )
        return path
