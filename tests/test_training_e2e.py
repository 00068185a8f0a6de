"""End-to-end training-entrypoint tests.

Fabricates the SageMaker filesystem contract in a tempdir (the reference's
local_mode.py:371-396 trick, without Docker) and runs the real `train`
entrypoint in a subprocess, asserting on produced model files and the HPO
stdout-regex contract.
"""

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from tests.reference_fixtures import resources

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ABALONE = resources() + "/abalone/data"


def _sm_env(tmp_path, hyperparameters, channels, train_dir, val_dir=None, hosts=None):
    conf = tmp_path / "input" / "config"
    conf.mkdir(parents=True)
    model_dir = tmp_path / "model"
    output_dir = tmp_path / "output" / "data"
    model_dir.mkdir()
    output_dir.mkdir(parents=True)
    (conf / "hyperparameters.json").write_text(json.dumps(hyperparameters))
    (conf / "inputdataconfig.json").write_text(json.dumps(channels))

    env = dict(os.environ)
    env.update(
        {
            "SM_INPUT_TRAINING_CONFIG_FILE": str(conf / "hyperparameters.json"),
            "SM_INPUT_DATA_CONFIG_FILE": str(conf / "inputdataconfig.json"),
            "SM_CHECKPOINT_CONFIG_FILE": str(conf / "checkpointconfig.json"),
            "SM_CHANNEL_TRAIN": train_dir,
            "SM_MODEL_DIR": str(model_dir),
            "SM_OUTPUT_DATA_DIR": str(output_dir),
            "SM_HOSTS": json.dumps(hosts or ["algo-1"]),
            "SM_CURRENT_HOST": (hosts or ["algo-1"])[0],
            "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": REPO,
        }
    )
    if val_dir:
        env["SM_CHANNEL_VALIDATION"] = val_dir
    return env, model_dir, output_dir


def _run_train(env):
    return subprocess.run(
        [sys.executable, "-m", "sagemaker_xgboost_container_tpu.training.entry"],
        env=env,
        capture_output=True,
        text=True,
        timeout=600,
    )


LIBSVM_CHANNELS = {
    "train": {
        "ContentType": "libsvm",
        "TrainingInputMode": "File",
        "S3DistributionType": "FullyReplicated",
    },
    "validation": {
        "ContentType": "libsvm",
        "TrainingInputMode": "File",
        "S3DistributionType": "FullyReplicated",
    },
}


@pytest.mark.e2e
def test_abalone_end_to_end(tmp_path):
    env, model_dir, _ = _sm_env(
        tmp_path,
        {
            "num_round": "10",
            "objective": "reg:squarederror",
            "max_depth": "4",
            "eval_metric": "rmse",
        },
        LIBSVM_CHANNELS,
        ABALONE + "/train",
        ABALONE + "/validation",
    )
    result = _run_train(env)
    assert result.returncode == 0, result.stderr[-3000:]
    assert (model_dir / "xgboost-model").exists()
    # HPO scrape contract: tab-separated eval lines. `rmse` is one of the
    # container's sklearn metrics (metrics/custom_metrics.py), so it cannot
    # ride back from the device: a job without a checkpoint directory fuses
    # K = 8 rounds a dispatch and the host evaluates once a dispatch, at the
    # batch-end rounds (docs/DESIGN.md, Round pipeline): 7, then the last
    regex = re.compile(r".*\[([0-9]+)\].*\tvalidation-rmse:(\S+)")
    matches = [m for m in map(regex.match, result.stdout.splitlines()) if m]
    assert [int(m.group(1)) for m in matches] == [7, 9], result.stdout[-2000:]
    # model learns: rmse decreases
    assert float(matches[-1].group(2)) < float(matches[0].group(2))
    # model file is valid xgboost JSON loadable by our Forest
    from sagemaker_xgboost_container_tpu.models import Forest

    forest = Forest.load_model(str(model_dir / "xgboost-model"))
    assert forest.num_boosted_rounds == 10


@pytest.mark.e2e
def test_kfold_cv_end_to_end(tmp_path):
    env, model_dir, output_dir = _sm_env(
        tmp_path,
        {
            "num_round": "5",
            "objective": "reg:squarederror",
            "max_depth": "3",
            "_kfold": "3",
            "_num_cv_round": "2",
        },
        LIBSVM_CHANNELS,
        ABALONE + "/train",
        ABALONE + "/validation",
    )
    result = _run_train(env)
    assert result.returncode == 0, result.stderr[-3000:]
    # k*r = 6 models, each with its integrity manifest sidecar
    names = sorted(p.name for p in model_dir.iterdir())
    models = [n for n in names if not n.endswith(".manifest")]
    assert models == ["xgboost-model-{}".format(i) for i in range(6)], names
    assert sorted(n for n in names if n.endswith(".manifest")) == [
        "xgboost-model-{}.manifest".format(i) for i in range(6)
    ], names
    preds = np.loadtxt(str(output_dir / "predictions.csv"), delimiter=",")
    assert preds.shape[1] == 2  # y_true, mean prediction


@pytest.mark.e2e
def test_checkpoint_resume(tmp_path):
    ckpt_dir = tmp_path / "ckpt"
    ckpt_dir.mkdir()
    conf_extra = {"LocalPath": str(ckpt_dir)}
    env, model_dir, _ = _sm_env(
        tmp_path,
        {"num_round": "8", "max_depth": "3", "eval_metric": "rmse"},
        LIBSVM_CHANNELS,
        ABALONE + "/train",
        ABALONE + "/validation",
    )
    ckpt_conf = tmp_path / "input" / "config" / "checkpointconfig.json"
    ckpt_conf.write_text(json.dumps(conf_extra))
    result = _run_train(env)
    assert result.returncode == 0, result.stderr[-3000:]
    names = sorted(os.listdir(ckpt_dir))
    ckpts = [n for n in names if not n.endswith(".manifest")]
    # max_to_keep = 5 retention, each checkpoint with its manifest sidecar
    assert len(ckpts) == 5, names
    assert "xgboost-checkpoint.7" in ckpts
    assert sorted(n + ".manifest" for n in ckpts) == [
        n for n in names if n.endswith(".manifest")
    ], names

    # resume: delete the last checkpoints, rerun — should continue, not restart
    for name in ("xgboost-checkpoint.6", "xgboost-checkpoint.7"):
        os.remove(str(ckpt_dir / name))
    result2 = _run_train(env)
    assert result2.returncode == 0, result2.stderr[-3000:]
    lines = [l for l in result2.stdout.splitlines() if re.match(r"\[[0-9]+\]\t", l)]
    # resumed from iteration 6: rounds 6 and 7 only
    assert lines and lines[0].startswith("[6]"), lines[:3]


@pytest.mark.e2e
def test_user_error_writes_failure_file(tmp_path):
    env, _, _ = _sm_env(
        tmp_path,
        {"num_round": "5", "tree_method": "gpu_hist"},
        LIBSVM_CHANNELS,
        ABALONE + "/train",
    )
    result = _run_train(env)
    assert result.returncode == 1
    # the job's log, failure reason included, goes to stdout
    # (utils/logging_config.py)
    assert "gpu_hist" in result.stdout + result.stderr


@pytest.mark.e2e
def test_csv_binary_logistic_with_accuracy_feval(tmp_path):
    rng = np.random.RandomState(0)
    X = rng.randn(400, 3)
    y = (X[:, 0] > 0).astype(int)
    data_dir = tmp_path / "data"
    data_dir.mkdir()
    rows = np.column_stack([y, X])
    np.savetxt(str(data_dir / "train.csv"), rows, delimiter=",", fmt="%.6f")
    channels = {
        "train": {
            "ContentType": "text/csv",
            "TrainingInputMode": "File",
            "S3DistributionType": "FullyReplicated",
        }
    }
    env, model_dir, _ = _sm_env(
        tmp_path,
        {
            "num_round": "8",
            "objective": "binary:logistic",
            "eval_metric": "logloss,accuracy",
        },
        channels,
        str(data_dir),
    )
    result = _run_train(env)
    assert result.returncode == 0, result.stderr[-3000:]
    # native metric and sklearn custom metric both on the eval line
    assert re.search(r"\ttrain-logloss:\S+", result.stdout)
    assert re.search(r"\ttrain-accuracy:\S+", result.stdout)
    assert (model_dir / "xgboost-model").exists()


@pytest.mark.e2e
def test_sigterm_saves_intermediate_model(tmp_path):
    """Fault injection: kill training mid-run; save_model_on_termination
    leaves a loadable model and the process exits 0 (reference
    test_early_stopping.py:35-68 semantics)."""
    import signal
    import time

    env, model_dir, _ = _sm_env(
        tmp_path,
        {
            "num_round": "100000",
            "max_depth": "3",
            "save_model_on_termination": "true",
        },
        LIBSVM_CHANNELS,
        ABALONE + "/train",
        ABALONE + "/validation",
    )
    proc = subprocess.Popen(
        [sys.executable, "-m", "sagemaker_xgboost_container_tpu.training.entry"],
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
    )
    # SIGTERM once a round has been logged (the handler is installed before
    # the first round runs) and its model is on disk. The job's INFO lines
    # start with "[" too: a round line is "[<round>]<tab>...". A thread
    # drains the pipe so the child never blocks on a full one.
    import threading

    saw_round = threading.Event()

    def drain():
        for line in proc.stdout:
            if re.match(r"\[\d+\]\s", line):
                saw_round.set()

    threading.Thread(target=drain, daemon=True).start()
    assert saw_round.wait(timeout=600), "training never produced a round line"
    deadline = time.time() + 120
    while not (model_dir / "xgboost-model").exists() and time.time() < deadline:
        time.sleep(0.05)
    assert (model_dir / "xgboost-model").exists(), "no intermediate model after a round"
    proc.send_signal(signal.SIGTERM)
    rc = proc.wait(timeout=60)
    assert rc == 0
    assert (model_dir / "xgboost-model").exists()
    from sagemaker_xgboost_container_tpu.models import Forest

    forest = Forest.load_model(str(model_dir / "xgboost-model"))
    assert forest.num_boosted_rounds >= 1


@pytest.mark.e2e
def test_two_host_membership_dataless_host_exits(tmp_path):
    """Reference distributed.py:78-109 semantics: in a 2-host cluster where
    one host has no data, that host broadcasts membership, exits 0, and the
    other host trains and saves the model."""
    import time

    hosts = ["127.0.0.1", "localhost"]
    procs = {}
    dirs = {}
    for host in hosts:
        hdir = tmp_path / host.replace(".", "_")
        hdir.mkdir()
        train_dir = hdir / "train"
        train_dir.mkdir()
        if host == "127.0.0.1":  # only the master host gets data
            src = ABALONE + "/train/abalone.train_0"
            (train_dir / "abalone.train_0").write_bytes(open(src, "rb").read())
        env, model_dir, _ = _sm_env(
            hdir,
            {"num_round": "3", "max_depth": "3"},
            {"train": LIBSVM_CHANNELS["train"]},
            str(train_dir),
            hosts=hosts,
        )
        env["SM_CURRENT_HOST"] = host
        dirs[host] = model_dir
        procs[host] = subprocess.Popen(
            [sys.executable, "-m", "sagemaker_xgboost_container_tpu.training.entry"],
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
        )
    outs = {h: p.communicate(timeout=300)[0] for h, p in procs.items()}
    assert procs["localhost"].returncode == 0, outs["localhost"][-2000:]
    assert procs["127.0.0.1"].returncode == 0, outs["127.0.0.1"][-2000:]
    # exactly the data-holding host saved a model
    assert (dirs["127.0.0.1"] / "xgboost-model").exists()
    assert not (dirs["localhost"] / "xgboost-model").exists()


@pytest.mark.e2e
def test_script_mode_training(tmp_path):
    """Reference script-mode path (test_boston.py analog): the user's training
    script runs as a subprocess with SM_HPS and saves its own model."""
    code_dir = tmp_path / "code"
    code_dir.mkdir()
    (code_dir / "my_train.py").write_text(
        "import argparse, json, os, sys\n"
        "sys.path.insert(0, os.environ['FRAMEWORK_REPO'])\n"
        "import numpy as np\n"
        "from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix\n"
        "from sagemaker_xgboost_container_tpu.models import train\n"
        "\n"
        "parser = argparse.ArgumentParser()\n"
        "parser.add_argument('--num_round', type=int, default=3)\n"
        "parser.add_argument('--max_depth', type=int, default=3)\n"
        "args, _ = parser.parse_known_args()\n"
        "hps = json.loads(os.environ['SM_HPS'])\n"
        "assert hps['num_round'] == '4', hps\n"
        "rng = np.random.RandomState(0)\n"
        "X = rng.rand(200, 3).astype(np.float32)\n"
        "y = (X[:, 0] * 5).astype(np.float32)\n"
        "forest = train({'max_depth': args.max_depth}, DataMatrix(X, labels=y),\n"
        "               num_boost_round=args.num_round)\n"
        "forest.save_model(os.path.join(os.environ['SM_MODEL_DIR'], 'xgboost-model'))\n"
        "print('USER_SCRIPT_DONE rounds=', forest.num_boosted_rounds)\n"
    )
    env, model_dir, _ = _sm_env(
        tmp_path,
        {
            "num_round": "4",
            "max_depth": "3",
            "sagemaker_program": "my_train.py",
            "sagemaker_submit_directory": str(code_dir),
        },
        {"train": LIBSVM_CHANNELS["train"]},
        ABALONE + "/train",
    )
    env["FRAMEWORK_REPO"] = REPO
    result = _run_train(env)
    assert result.returncode == 0, result.stdout[-2000:] + result.stderr[-2000:]
    assert "USER_SCRIPT_DONE" in result.stdout
    assert (model_dir / "xgboost-model").exists()


@pytest.mark.e2e
def test_exact_tree_method_end_to_end(tmp_path):
    """tree_method=exact through the real entrypoint: schema validation
    accepts it, the data-sized all-midpoint binning engages (true
    exact-greedy parity), HPO metric lines print, model saves and learns."""
    env, model_dir, _ = _sm_env(
        tmp_path,
        {
            "objective": "reg:squarederror",
            "tree_method": "exact",
            "max_depth": "4",
            "eta": "0.3",
            "num_round": "8",
        },
        {"train": LIBSVM_CHANNELS["train"]},
        train_dir=os.path.join(ABALONE, "train"),
    )
    result = _run_train(env)
    assert result.returncode == 0, result.stderr[-2000:]
    lines = re.findall(r"\[(\d+)\]\ttrain-rmse:([0-9.]+)", result.stdout)
    assert len(lines) == 8, result.stdout[-2000:]
    assert float(lines[-1][1]) < float(lines[0][1]) * 0.5
    assert (model_dir / "xgboost-model").exists()
