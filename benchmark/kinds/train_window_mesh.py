"""Traffic kind ``train_window_mesh``: ``train_window`` on the mesh a job gets.

One call of the public ``models.train()`` with ``mesh=`` the `data` mesh
``training/algorithm_train.py::training_mesh`` builds over the cell's
``chips`` devices, as ``train_job`` does on a multi-chip instance: rows
divided over the chips, every shard set up on its own chip, the round program
under ``shard_map`` with the histogram and metric ``psum`` over `data`.
Everything else is ``train_window.run`` itself, driven through its
``train_fn``: the data (all of the configuration's rows, made from the seed),
the window, the timing, and the reference's checks over every row.

**The readers get the first chip's shape.** Kernels, stages and the breakdown
are read from the first chip's events (``trace_reduce.TraceSummary.first_chip``),
and that chip holds ``rows_a_chip`` of the configuration's rows, not
``train_rows``: the ``config`` this kind hands the readers carries
``train_rows`` = ``rows_a_chip["train"]`` (and ``validation_rows`` likewise),
so that a kernel's needed work is counted for the rows its events covered.
"""

from benchmark.kinds import train_window


def job_mesh(chips):
    """The mesh a training job builds on this machine, held to ``chips``
    devices; the `data` axis has to have exactly that many."""
    from sagemaker_xgboost_container_tpu.training.algorithm_train import training_mesh

    mesh = training_mesh(chips)
    shards = 1 if mesh is None else int(mesh.shape["data"])
    if shards != int(chips):
        raise SystemExit(
            "benchmark: the cell needs a `data` mesh of {} and the job's mesh "
            "has {}".format(chips, shards)
        )
    return mesh


def run(ctx, train_fn=None):
    """Drive one run. ``train_fn`` stands in for ``models.train`` in the tests
    that break the timed path underneath; it is handed ``mesh=`` too."""
    from sagemaker_xgboost_container_tpu import models

    mesh = job_mesh(ctx["cell"]["chips"])

    def train_on_mesh(*args, **kwargs):
        return (train_fn or models.train)(*args, mesh=mesh, **kwargs)

    run = train_window.run(ctx, train_fn=train_on_mesh)
    a_chip = ctx["config"]["rows_a_chip"]
    run["config"] = dict(
        run["config"], train_rows=a_chip["train"], validation_rows=a_chip["validation"]
    )
    return run
