"""What this process runs on, said once and out loud.

The histogram kernel, the node-totals lowering and the quantile sketch all
pick their implementation from ``jax.default_backend()``, and Pallas kernels
are interpreted on the CPU. None of that is visible in a job log unless the
program prints it, so a run that quietly landed on the wrong device looks
like a slow run on the right one. ``start_device_runtime`` is the one place
``train`` (training/algorithm_train.train_job) and ``serve``
(serving/server.serving_entrypoint) pass through before their first compile:
it arms the persistent compile cache and logs one ``device runtime:`` line
with the backend, the device kind and count, the mesh, every
backend-selected lowering and whether Pallas is interpreted.
"""

import json
import logging
import os
import sys

from .compile_cache import enable_compile_cache

logger = logging.getLogger(__name__)

RUNTIME_LINE_PREFIX = "device runtime: "


def device_summary():
    """{"platform", "kind", "count"} as jax reports the default backend's
    devices. Initializes the backend — call after jax.distributed is up."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def require_accelerator(who):
    """Exit 2 when jax's default backend is the CPU and the CPU was not
    asked for by name (``JAX_PLATFORMS=cpu``): a rate measured on the CPU
    must never be printed by a run that was looking for a chip. Returns
    :func:`device_summary` for the caller's result line."""
    import jax

    try:
        found = jax.default_backend() != "cpu"
        reason = "default backend is 'cpu'"
    except RuntimeError as e:  # the requested platform failed to initialize
        found, reason = False, str(e)
    if not found and os.environ.get("JAX_PLATFORMS") != "cpu":
        sys.stderr.write(
            "{}: jax found no accelerator ({}); set JAX_PLATFORMS=cpu to ask "
            "for a CPU run by name\n".format(who, reason)
        )
        sys.exit(2)
    return device_summary()


def _libtpu_version():
    from importlib.metadata import PackageNotFoundError, version

    try:
        return version("libtpu")
    except PackageNotFoundError:  # CPU-only image: nothing to report
        return None


def split_steps_per_round(grow_policy, max_leaves, trees_per_round):
    """Split steps one boosting round runs: a loss-guided build's
    ``max_leaves - 1`` a tree; 0 for a depth-wise job, None where no trees
    are built (a server)."""
    if grow_policy is None:
        return None
    if grow_policy != "lossguide":
        return 0
    return max(int(max_leaves or 0) - 1, 0) * int(trees_per_round or 1)


def start_device_runtime(
    role, mesh=None, knobs=None, route_width=None, grow_policy=None, max_depth=None,
    trees_per_round=None, max_leaves=None, **facts
):
    """Arm the compile cache and log the ``device runtime:`` line.

    ``role``: "train" | "serve". ``mesh``: the training mesh (None on one
    device). ``knobs``: the session's HistKnobs snapshot; resolved here when
    the caller has none (the server — it reports what a session in this
    process would pick). ``route_width``: the feature width of the train bins,
    for which the line names the bin fetch's lowering (the trainer; a server
    routes no binned rows). ``grow_policy``: the trainer's, for which the line
    names how evaluation rows walk a new tree (``eval_traversal``: ``level``
    for a depth-wise job, ``replay`` for a loss-guided one, whose rows are
    taken through the tree's splits in the order they were made,
    ``ops/tree_build.py::predict_binned_steps``; a server builds no trees). ``max_depth``: the trainer's, for which the line names how
    the build's rows read their level's node tables (``build_table_impl``: the
    lowering at the widest level, ``2**max_depth`` entries; a loss-guided
    build reads no node table: a split step routes one node's rows by a
    dynamic column slice, and its ``max_leaves - 1`` steps are one rolled
    loop, ``ops/lossguide.py``). ``trees_per_round``: the trainer's classes x
    ``num_parallel_tree``, the trees one boosting round grows (the gauge
    ``round_class_trees``; a server grows none). ``max_leaves``: the
    trainer's, from which the line says the split steps a round
    (``split_steps_per_round``, the gauge ``round_split_steps``: 0 for a
    depth-wise job, null for a server) beside ``grow_policy``. ``facts``: what else only the caller knows about
    the path taken (the trainer's ingest mode). Returns the logged fields.
    """
    import jax
    import jaxlib

    from ..data.binning import _sketch_impl
    from ..ops.histogram import (
        choose_hist_impl,
        choose_totals_impl,
        pallas_interpret,
        resolve_hist_knobs,
    )
    from ..ops.tree_build import (
        choose_eval_traversal,
        choose_route_impl,
        choose_table_impl,
    )

    cache_dir = enable_compile_cache()
    if knobs is None:
        knobs = resolve_hist_knobs()
    eval_traversal = (
        choose_eval_traversal(grow_policy) if grow_policy is not None else None
    )
    fields = dict(device_summary())
    fields.update(
        role=role,
        backend=jax.default_backend(),
        mesh=(
            {name: int(size) for name, size in mesh.shape.items()}
            if mesh is not None
            else None
        ),
        hist_impl=choose_hist_impl(knobs.backend),
        totals_impl=choose_totals_impl(knobs.backend),
        route_impl=(
            choose_route_impl(knobs.backend, route_width)
            if route_width is not None
            else None
        ),
        route_width=route_width,
        eval_traversal=eval_traversal,
        build_table_impl=(
            choose_table_impl(knobs.backend, 2 ** max_depth)
            if eval_traversal == "level" and max_depth is not None
            else None
        ),
        trees_per_round=trees_per_round,
        grow_policy=grow_policy,
        split_steps_per_round=split_steps_per_round(
            grow_policy, max_leaves, trees_per_round
        ),
        sketch_impl=_sketch_impl(),
        pallas_interpret=pallas_interpret(),
        compile_cache_dir=cache_dir,
        jax=jax.__version__,
        jaxlib=jaxlib.__version__,
        libtpu=_libtpu_version(),
        **facts,
    )
    logger.info("%s%s", RUNTIME_LINE_PREFIX, json.dumps(fields, sort_keys=True))
    return fields
