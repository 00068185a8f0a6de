"""Traffic kind ``train_window_ckpt``: ``train_window`` for a spot-safe job.

One call of the public ``models.train()`` with a checkpoint directory, as
``training/algorithm_train.py`` runs every job that is given one: one round a
dispatch (the traffic's ``rounds_per_dispatch``, not the configuration's) and
``training/checkpointing.py::SaveCheckpointCallBack`` in front of the window's
callback, so that a round's save ends before the round's end is timed.
Everything else is ``train_window.run`` itself, driven through its
``train_fn``: the data, the window, the timing, the reference's checks (with
one round a dispatch it judges round 0 and the window's last).

``correct`` adds the guarantee the traffic states. After the window, and
after ``flush_checkpoints()``, the newest checkpoint that verifies
(``load_checkpoint``) loads to a forest equal tree for tree, bit for bit, to
the first rounds of the forest ``train()`` returned, it holds every round its
name says, and it is no older than the last acknowledged round less one
(each check has the limit 0).
"""

import shutil
import tempfile

from benchmark import limits
from benchmark.kinds import train_window


def trees_differing(saved, returned):
    """How many of ``saved``'s trees are not, array for array and bit for
    bit, the tree at the same place in ``returned`` (rounds as ``plain_rounds``
    gives them); a round of another length counts whole."""
    differing = 0
    for mine, theirs in zip(saved, returned):
        if len(mine) != len(theirs):
            differing += max(len(mine), len(theirs))
            continue
        for (group_a, a), (group_b, b) in zip(mine, theirs):
            same = group_a == group_b and all(
                a[key].dtype == b[key].dtype
                and a[key].shape == b[key].shape
                and a[key].tobytes() == b[key].tobytes()
                for key in a
            )
            differing += int(not same)
    return differing


def judge_checkpoint(checkpoint_dir, forest, acknowledged):
    """The guarantee's checks, from what is on disk now. ``acknowledged``
    rounds have been returned to the caller; a checkpoint named for round
    ``e`` has to hold rounds 0 to ``e``."""
    from sagemaker_xgboost_container_tpu.models.forest import Forest
    from sagemaker_xgboost_container_tpu.training import checkpointing

    checkpointing.flush_checkpoints()
    path, named_rounds = checkpointing.load_checkpoint(checkpoint_dir)
    if path is None:  # nothing on disk verifies: every acknowledged round is lost
        return [limits.check("ckpt_rounds_behind_over_one", acknowledged, 0)]
    saved = Forest.load_model(path)
    on_disk = saved.num_boosted_rounds
    differing = trees_differing(
        train_window.plain_rounds(saved, on_disk),
        train_window.plain_rounds(forest, min(on_disk, acknowledged)),
    ) + max(on_disk - acknowledged, 0)
    return [
        limits.check("ckpt_rounds_behind_over_one", max(acknowledged - named_rounds - 1, 0), 0),
        limits.check("ckpt_rounds_short_of_its_name", max(named_rounds - on_disk, 0), 0),
        limits.check("ckpt_trees_differing", int(differing), 0),
    ]


def run(ctx, train_fn=None):
    """Drive one run. ``train_fn`` stands in for ``models.train`` in the tests
    that break the timed path underneath; it is handed the saver and the
    window's callback, in that order."""
    traffic = ctx["traffic"]
    if int(traffic["save_every_rounds"]) != 1:
        raise SystemExit("benchmark: SaveCheckpointCallBack saves every round and no rarer")
    from sagemaker_xgboost_container_tpu import models
    from sagemaker_xgboost_container_tpu.training.checkpointing import SaveCheckpointCallBack

    config = dict(ctx["config"], rounds_per_dispatch=int(traffic["rounds_per_dispatch"]))
    checkpoint_dir = tempfile.mkdtemp(prefix="bench_ckpt_")
    saver = SaveCheckpointCallBack(checkpoint_dir, max_to_keep=int(traffic["max_to_keep"]))
    returned = []

    def train_with_saver(params, dtrain, callbacks, **kwargs):
        # the save first: inside the round it is charged to
        forest = (train_fn or models.train)(
            params, dtrain, callbacks=[saver] + callbacks, **kwargs
        )
        returned.append(forest)
        return forest

    try:
        run = train_window.run(dict(ctx, config=config), train_fn=train_with_saver)
        forest = returned[0]
        run["checks"] += judge_checkpoint(checkpoint_dir, forest, forest.num_boosted_rounds)
    finally:
        saver.stop(timeout=10.0)
        shutil.rmtree(checkpoint_dir, ignore_errors=True)
    return run
