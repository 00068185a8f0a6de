#!/usr/bin/env python3
"""chip_smoke.py: prove that `train` and `serve` still start on the chip.

    python chip_smoke.py                  full width, on the TPU; fails fast
                                          (non-zero, seconds) without one
    python chip_smoke.py --cpu-rehearsal  the same phases at the same widths
                                          on the CPU, a few thousand rows —
                                          a stated mode for the tier-1 test,
                                          never a fallback

One model at its full width — the Higgs configuration (BASELINE.json #2): 28
dense float features with a few percent NaN, binary:logistic, hist,
max_bin=256 (257 bins with the missing bin), max_depth=8 — on seeded
synthetic rows split 9:1 into `train` and `validation` channels, driven
through the entry points a user calls, each as a child process that holds
the chip alone:

  1. `training.entry`, num_round=16, no checkpoint directory: the default
     K=8 fused dispatch runs twice on one compiled program;
  2. `training.entry`, num_round=4, with a checkpoint directory: the K=1
     program every spot-safe job runs — and with SM_INGEST_MODE=whole, so
     the whole-file readers and the on-device quantile sketch run too (at
     this size run 1's default is chunked ingest, which sketches and bins
     on the host);
  3. `serving.server` on the model run 1 wrote: 1-row requests (host C++
     traversal), 64 and 4,096 rows (device kernel, two row buckets),
     GET /ping, SIGTERM, clean drain.

This parent never initialises a JAX backend (stdlib, numpy, pyarrow,
urllib): a parent that had touched JAX would hold the chip its children
need. It checks results, not exit codes alone; any failed check exits
non-zero. Everything it prints above the last line is a smoke observation —
wall and compile seconds of one cold or warm run, not a benchmark metric.
The last stdout line is one JSON object:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""

import argparse
import glob
import io
import json
import math
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
PACKAGE = "sagemaker_xgboost_container_tpu"

NUM_FEATURES = 28
MAX_BIN = 256
MAX_DEPTH = 8
NAN_FRACTION = 0.03
FULL_ROWS = 1_000_000       # 9:1 train:validation; never cut below 262,144
REHEARSAL_ROWS = 4_000
ROUNDS_FUSED = 16           # run 1: K=8 dispatch, twice
ROUNDS_CHECKPOINTED = 4     # run 2: K=1
SEED = 20260926
# final validation logloss after 16 rounds on the seeded data (eta 0.3):
# observed 0.3216 on the chip at 1M rows (from 0.5625 after round 0) and
# 0.4648 at the rehearsal's 4,000 (400 validation rows, depth 8 overfits);
# ln 2 = 0.693 is no learning
LOGLOSS_BOUND = {"full": 0.36, "rehearsal": 0.55}
CHILD_TIMEOUT_S = 900
RUNTIME_LINE = "device runtime: "

HPO_LINE = re.compile(r"^\[(\d+)\]\t.*\bvalidation-logloss:(\S+)")


class SmokeFailure(Exception):
    pass


def check(cond, message):
    if not cond:
        raise SmokeFailure(message)


def say(message):
    print("chip_smoke: " + message, flush=True)


# ------------------------------------------------------------------ the tree


def check_tree():
    """The program must be built from what git would commit: refuse a tree
    that lacks the package, or carries a prebuilt native library that
    `data/native.py` would load in place of a build from native/fastdata.cpp
    (`*.so` is gitignored, so any found here is untracked)."""
    check(
        os.path.isfile(os.path.join(HERE, PACKAGE, "training", "entry.py"))
        and os.path.isfile(os.path.join(HERE, "native", "fastdata.cpp")),
        "{} is not next to chip_smoke.py: run it from the root of a "
        "checkout".format(PACKAGE),
    )
    stray = [
        p
        for root in (PACKAGE, "native")
        for p in glob.glob(os.path.join(HERE, root, "**", "*.so"), recursive=True)
    ]
    check(not stray, "untracked native binaries in the tree: {}".format(stray))


# ---------------------------------------------------------------- the device

_PROBE = (
    "import json, jax, jaxlib\n"
    "from importlib.metadata import version, PackageNotFoundError\n"
    "try:\n"
    "    libtpu = version('libtpu')\n"
    "except PackageNotFoundError:\n"
    "    libtpu = None\n"
    "d = jax.devices()\n"
    "print('PROBE ' + json.dumps({'platform': d[0].platform,"
    " 'kind': d[0].device_kind, 'count': len(d), 'jax': jax.__version__,"
    " 'jaxlib': jaxlib.__version__, 'libtpu': libtpu}))\n"
)


def child_env(platform):
    """The caller's environment, untouched (JAX_COMPILATION_CACHE_DIR
    included), plus the platform the children MUST run on: with
    JAX_PLATFORMS=tpu a missing chip is an error in JAX, not a CPU run."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = platform
    env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    return env


def probe_device(platform):
    """One short-lived child asks JAX what it finds; it exits (and frees the
    chip) before anything else starts."""
    result = subprocess.run(
        [sys.executable, "-c", _PROBE],
        env=child_env(platform),
        capture_output=True,
        text=True,
        timeout=300,
    )
    if result.returncode != 0:
        reason = (result.stderr.strip().splitlines() or ["no output"])[-1]
        raise SmokeFailure(
            "no {} device: JAX_PLATFORMS={} found none ({})".format(
                platform.upper(), platform, reason[:400]
            )
        )
    for line in result.stdout.splitlines():
        if line.startswith("PROBE "):
            device = json.loads(line[len("PROBE "):])
            check(
                device["platform"] == platform,
                "asked for {} and got {}".format(platform, device),
            )
            return device
    raise SmokeFailure("device probe printed nothing: " + result.stdout[-400:])


# ------------------------------------------------------------------ the data


def make_data(rows):
    """Seeded Higgs-shaped rows: features ~ N(0,1) with NAN_FRACTION missing,
    label from a noisy nonlinear logit of the clean features. Row 0 stays
    dense (the channel's delimiter is sniffed from its first line)."""
    import numpy as np

    rng = np.random.RandomState(SEED)
    X = rng.randn(rows, NUM_FEATURES).astype(np.float32)
    logit = X[:, 0] * 0.8 + X[:, 1] * X[:, 2] * 0.5 + np.sin(X[:, 3]) - 0.2
    y = (logit + rng.randn(rows) * 0.5 > 0).astype(np.float32)
    missing = rng.rand(rows, NUM_FEATURES) < NAN_FRACTION
    missing[0] = False
    X[missing] = np.nan
    return X, y


def csv_bytes(columns):
    """Header-less CSV of float columns; NaN is written as an empty field."""
    import pyarrow as pa
    import pyarrow.csv as pacsv

    table = pa.table(
        {
            "c{}".format(i): pa.array(col, from_pandas=True)
            for i, col in enumerate(columns)
        }
    )
    sink = io.BytesIO()
    pacsv.write_csv(table, sink, pacsv.WriteOptions(include_header=False))
    return sink.getvalue()


def write_channel(directory, X, y):
    os.makedirs(directory)
    with open(os.path.join(directory, "part-0.csv"), "wb") as f:
        f.write(csv_bytes([y] + [X[:, j] for j in range(X.shape[1])]))


# -------------------------------------------------------------- the trainer

CHANNEL = {
    "ContentType": "csv",
    "TrainingInputMode": "File",
    "S3DistributionType": "FullyReplicated",
}


def run_trainer(name, work, platform, num_round, checkpoint_dir=None, **extra_env):
    """`python -m …training.entry` against a fabricated SageMaker filesystem
    contract -> (stdout lines, all output text, model dir, wall seconds)."""
    root = os.path.join(work, name)
    conf = os.path.join(root, "input", "config")
    model_dir = os.path.join(root, "model")
    output_dir = os.path.join(root, "output", "data")
    for d in (conf, model_dir, output_dir):
        os.makedirs(d)
    hyperparameters = {
        "num_round": str(num_round),
        "objective": "binary:logistic",
        "tree_method": "hist",
        "max_bin": str(MAX_BIN),
        "max_depth": str(MAX_DEPTH),
        "eval_metric": "logloss",
        "seed": "0",
    }
    with open(os.path.join(conf, "hyperparameters.json"), "w") as f:
        json.dump(hyperparameters, f)
    with open(os.path.join(conf, "inputdataconfig.json"), "w") as f:
        json.dump({"train": CHANNEL, "validation": CHANNEL}, f)
    if checkpoint_dir is not None:
        os.makedirs(checkpoint_dir)
        with open(os.path.join(conf, "checkpointconfig.json"), "w") as f:
            json.dump({"LocalPath": checkpoint_dir}, f)
    env = child_env(platform)
    env.update(
        SM_INPUT_TRAINING_CONFIG_FILE=os.path.join(conf, "hyperparameters.json"),
        SM_INPUT_DATA_CONFIG_FILE=os.path.join(conf, "inputdataconfig.json"),
        SM_CHECKPOINT_CONFIG_FILE=os.path.join(conf, "checkpointconfig.json"),
        SM_CHANNEL_TRAIN=os.path.join(work, "data", "train"),
        SM_CHANNEL_VALIDATION=os.path.join(work, "data", "validation"),
        SM_MODEL_DIR=model_dir,
        SM_OUTPUT_DATA_DIR=output_dir,
        SM_HOSTS='["algo-1"]',
        SM_CURRENT_HOST="algo-1",
        **extra_env,
    )
    t0 = time.monotonic()
    result = subprocess.run(
        [sys.executable, "-m", PACKAGE + ".training.entry"],
        env=env,
        cwd=root,
        capture_output=True,
        text=True,
        timeout=CHILD_TIMEOUT_S,
    )
    wall = time.monotonic() - t0
    with open(os.path.join(work, name + ".log"), "w") as f:
        f.write(result.stdout + "\n--- stderr ---\n" + result.stderr)
    text = result.stdout + result.stderr
    check(
        result.returncode == 0,
        "{} exited {}:\n{}".format(name, result.returncode, text[-4000:]),
    )
    return result.stdout.splitlines(), text, model_dir, wall


def runtime_line(text, who):
    """The child's own statement of what it ran on (utils/device_runtime)."""
    for line in text.splitlines():
        at = line.find(RUNTIME_LINE)
        if at >= 0:
            return json.loads(line[at + len(RUNTIME_LINE):])
    raise SmokeFailure(who + " logged no '{}' line".format(RUNTIME_LINE.strip()))


def check_runtime(fields, who, device, platform, role, **also):
    """tpu, the probed device kind and count, and — on the chip — the
    compiled (not interpreted) Pallas histogram kernel."""
    want = {
        "role": role,
        "backend": platform,
        "platform": device["platform"],
        "kind": device["kind"],
        "count": device["count"],
    }
    if platform == "tpu":
        want.update(
            hist_impl="pallas", totals_impl="onehot", sketch_impl="device",
            pallas_interpret=False,
        )
    want.update(also)
    got = {k: fields.get(k) for k in want}
    check(got == want, "{} ran on {}, expected {}".format(who, got, want))


def records(stdout_lines, metric):
    out = []
    for line in stdout_lines:
        if line.startswith('{"metric": "' + metric + '"'):
            out.append(json.loads(line))
    return out


def check_training(name, stdout_lines, model_dir, num_round, bound=None):
    """Model + manifest written, one HPO line per round, summary and
    attribution records present -> (loglosses, compile seconds, seconds of
    rounds), the last two as the child's own records report them."""
    for artifact in ("xgboost-model", "xgboost-model.manifest"):
        path = os.path.join(model_dir, artifact)
        check(
            os.path.isfile(path) and os.path.getsize(path) > 0,
            "{}: {} missing or empty".format(name, artifact),
        )
    evals = [m for m in map(HPO_LINE.match, stdout_lines) if m]
    check(
        [int(m.group(1)) for m in evals] == list(range(num_round)),
        "{}: expected one validation-logloss line per round 0..{}, got rounds "
        "{}".format(name, num_round - 1, [m.group(1) for m in evals]),
    )
    losses = [float(m.group(2)) for m in evals]
    check(all(math.isfinite(v) for v in losses), name + ": non-finite logloss")
    check(
        losses[-1] < losses[0],
        "{}: validation logloss did not fall: {}".format(name, losses),
    )
    if bound is not None:
        check(
            losses[-1] < bound,
            "{}: final validation logloss {} is not under {}".format(
                name, losses[-1], bound
            ),
        )
    summary = records(stdout_lines, "training.summary")
    attribution = [
        r for r in records(stdout_lines, "training.attribution")
        if not r.get("rolling")
    ]
    check(
        len(summary) == 1 and summary[0]["rounds"] == num_round,
        "{}: training.summary record missing or wrong: {}".format(name, summary),
    )
    check(
        len(attribution) == 1 and "compile_ms" in attribution[0],
        "{}: training.attribution record missing: {}".format(name, attribution),
    )
    return losses, attribution[0]["compile_ms"] / 1000.0, summary[0]["total_s"]


# --------------------------------------------------------------- the server


def free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http(port, path, body=None, timeout=30.0):
    request = urllib.request.Request(
        "http://127.0.0.1:{}{}".format(port, path),
        data=body,
        headers={"Content-Type": "text/csv"} if body is not None else {},
    )
    with urllib.request.urlopen(request, timeout=timeout) as response:
        return response.status, response.read()


def invoke(port, X, timeout):
    """POST rows as text/csv -> list of predictions (one per row)."""
    status, body = http(
        port, "/invocations", csv_bytes([X[:, j] for j in range(X.shape[1])]),
        timeout=timeout,
    )
    check(status == 200, "POST /invocations answered {}".format(status))
    values = [float(v) for v in body.decode().replace(",", "\n").split()]
    check(
        len(values) == len(X),
        "{} predictions for {} rows".format(len(values), len(X)),
    )
    check(
        all(math.isfinite(v) and 0.0 < v < 1.0 for v in values),
        "predictions outside (0, 1) or not finite",
    )
    return values


def run_server(work, platform, model_dir, X, device):
    """Start `serving.server`, answer the requests, SIGTERM, clean drain."""
    port = free_port()
    env = child_env(platform)
    env.update(SM_MODEL_DIR=model_dir, SAGEMAKER_BIND_TO_PORT=str(port))
    log_path = os.path.join(work, "server.log")
    observed = {}
    with open(log_path, "w") as log:
        t0 = time.monotonic()
        server = subprocess.Popen(
            [sys.executable, "-m", PACKAGE + ".serving.server"],
            env=env,
            cwd=work,
            stdout=log,
            stderr=subprocess.STDOUT,
        )
        try:
            deadline = time.monotonic() + 300
            while True:
                check(
                    server.poll() is None,
                    "server exited {} before it was ready:\n{}".format(
                        server.returncode, tail(log_path)
                    ),
                )
                try:
                    if http(port, "/ping", timeout=5.0)[0] == 200:
                        break
                except (urllib.error.URLError, OSError):
                    pass
                check(time.monotonic() < deadline, "server not ready in 300s")
                time.sleep(0.5)
            observed["ready_s"] = time.monotonic() - t0

            # host path (<= GRAFT_HOST_PREDICT_ROWS): the C++ traversal
            t1 = time.monotonic()
            single = [invoke(port, X[i:i + 1], 60.0)[0] for i in range(8)]
            observed["one_row_x8_s"] = time.monotonic() - t1
            # device kernel, two row buckets. Readiness does not wait for
            # the predict warm-up, so the first device request may sit
            # behind the kernel's first compile: allow it generously
            t2 = time.monotonic()
            mid = invoke(port, X[:64], 600.0)
            observed["rows_64_s"] = time.monotonic() - t2
            t3 = time.monotonic()
            big = invoke(port, X[:4096], 600.0)
            observed["rows_4096_s"] = time.monotonic() - t3
            # device kernel == host traversal on the same rows, through the
            # public surface, with no JAX in this process
            for label, batch in (("64", mid), ("4096", big)):
                worst = max(abs(a - b) for a, b in zip(batch, single))
                check(
                    worst <= 1e-5,
                    "{}-row response (device kernel) differs from the 1-row "
                    "responses (host traversal) by {}".format(label, worst),
                )
                observed["device_vs_host_max_abs"] = worst
            check(http(port, "/ping")[0] == 200, "GET /ping after the requests")
        finally:
            if server.poll() is None:
                server.send_signal(signal.SIGTERM)
                try:
                    server.wait(timeout=120)
                except subprocess.TimeoutExpired:
                    server.kill()
                    server.wait()
                    raise SmokeFailure("server ignored SIGTERM for 120s")
    text = open(log_path).read()
    check(
        server.returncode == 0,
        "server exited {} after SIGTERM:\n{}".format(server.returncode, tail(log_path)),
    )
    check("drain complete" in text, "server log shows no clean drain")
    check(
        "predict warmup failed" not in text,
        "the predict warm-up failed:\n" + tail(log_path),
    )
    check(
        "native data plane loaded" in text and "native data plane unavailable" not in text,
        "the native library (native/fastdata.cpp) did not build and load:\n"
        + tail(log_path),
    )
    check_runtime(runtime_line(text, "server"), "server", device, platform, "serve")
    return observed


def tail(path, nbytes=4000):
    with open(path, "rb") as f:
        f.seek(0, os.SEEK_END)
        f.seek(max(0, f.tell() - nbytes))
        return f.read().decode(errors="replace")


# ------------------------------------------------------------------ the run


def cache_entries(directory):
    if not directory or not os.path.isdir(directory):
        return 0
    return sum(1 for name in os.listdir(directory) if name.endswith("-cache"))


def smoke(rehearsal, work):
    platform = "cpu" if rehearsal else "tpu"
    mode = "rehearsal" if rehearsal else "full"
    check_tree()
    t_start = time.monotonic()
    device = probe_device(platform)
    say(
        "platform={platform} device_kind={kind!r} devices={count} jax={jax} "
        "jaxlib={jaxlib} libtpu={libtpu}".format(**device)
    )

    rows = REHEARSAL_ROWS if rehearsal else FULL_ROWS
    t0 = time.monotonic()
    X, y = make_data(rows)
    n_val = rows // 10
    write_channel(os.path.join(work, "data", "validation"), X[:n_val], y[:n_val])
    write_channel(os.path.join(work, "data", "train"), X[n_val:], y[n_val:])
    say(
        "data: {} train + {} validation rows x {} features, max_bin {} (+1 "
        "missing bin), depth {}; written in {:.1f}s".format(
            rows - n_val, n_val, NUM_FEATURES, MAX_BIN, MAX_DEPTH,
            time.monotonic() - t0,
        )
    )

    # how a depth-wise build reads its node tables (ops/tree_build.choose_table_impl)
    build_table_impl = "select" if platform == "tpu" else "gather"
    # 1. fused dispatch (no checkpoint directory)
    out, text, model_dir, wall = run_trainer("train_fused", work, platform, ROUNDS_FUSED)
    fields = runtime_line(text, "train_fused")
    check_runtime(
        fields, "train_fused", device, platform, "train",
        route_impl="dense" if platform == "tpu" else "gather",
        route_width=NUM_FEATURES, eval_traversal="level",
        build_table_impl=build_table_impl,
    )
    cache_dir = fields.get("compile_cache_dir")
    losses, compile_s, rounds_s = check_training(
        "train_fused", out, model_dir, ROUNDS_FUSED, LOGLOSS_BOUND[mode]
    )
    say(
        "train_fused: {} rounds, wall {:.1f}s (smoke observation), rounds "
        "{:.1f}s of which compile {:.1f}s as the child reports; "
        "validation-logloss {:.4f} -> {:.4f}; hist={} (interpreted: {}) "
        "totals={} route={} build_table={} eval_traversal={} ingest={} mesh={}".format(
            ROUNDS_FUSED, wall, rounds_s, compile_s, losses[0], losses[-1],
            fields["hist_impl"], fields["pallas_interpret"],
            fields["totals_impl"], fields["route_impl"], fields["build_table_impl"],
            fields["eval_traversal"], fields["ingest"], fields["mesh"],
        )
    )

    # 2. checkpointed run: K=1, whole-file ingest (the device sketch on TPU)
    ckpt_dir = os.path.join(work, "checkpoints")
    out2, text2, model_dir2, wall2 = run_trainer(
        "train_checkpointed", work, platform, ROUNDS_CHECKPOINTED, ckpt_dir,
        SM_INGEST_MODE="whole",
    )
    fields2 = runtime_line(text2, "train_checkpointed")
    check_runtime(
        fields2, "train_checkpointed", device, platform, "train", ingest="whole",
        eval_traversal="level",
        build_table_impl=build_table_impl,
    )
    losses2, compile2_s, rounds2_s = check_training(
        "train_checkpointed", out2, model_dir2, ROUNDS_CHECKPOINTED
    )
    last = "xgboost-checkpoint.{}".format(ROUNDS_CHECKPOINTED - 1)
    names = sorted(os.listdir(ckpt_dir))
    check(
        last in names and last + ".manifest" in names,
        "checkpointed run left {} in its checkpoint directory".format(names),
    )
    # same seed and rows; K=1 for K=8 and another sketch of the same
    # quantiles (cut points differ in the last places): the rounds agree
    check(
        all(abs(a - b) < 5e-3 for a, b in zip(losses2, losses)),
        "K=1 and K=8 runs disagree: {} vs {}".format(losses2, losses[:len(losses2)]),
    )
    say(
        "train_checkpointed: {} rounds, wall {:.1f}s (smoke observation), "
        "rounds {:.1f}s of which compile {:.1f}s; ingest={} sketch={}; "
        "validation-logloss {:.4f} -> {:.4f}; {} checkpoint files".format(
            ROUNDS_CHECKPOINTED, wall2, rounds2_s, compile2_s,
            fields2["ingest"], fields2["sketch_impl"], losses2[0], losses2[-1],
            len(names),
        )
    )

    # 3. serve the first model — only now, with both trainers gone
    trained_entries = cache_entries(cache_dir)
    t0 = time.monotonic()
    served = run_server(work, platform, model_dir, X[:4096], device)
    say(
        "serve: wall {:.1f}s (smoke observation); ready after {ready_s:.1f}s, "
        "8 x 1 row {one_row_x8_s:.2f}s, 64 rows {rows_64_s:.2f}s, 4096 rows "
        "{rows_4096_s:.2f}s (first device requests include compile or "
        "warm-up wait); device kernel vs host traversal max |diff| "
        "{device_vs_host_max_abs:.2e}".format(time.monotonic() - t0, **served)
    )
    say(
        "compile cache: {} ({} entries after the trainers, {} after the "
        "server; JAX_COMPILATION_CACHE_DIR {})".format(
            cache_dir, trained_entries, cache_entries(cache_dir),
            "set" if os.environ.get("JAX_COMPILATION_CACHE_DIR") else "unset",
        )
    )
    say(
        "total wall {:.1f}s; compile seconds reported by the trainers: "
        "{:.1f} + {:.1f}".format(time.monotonic() - t_start, compile_s, compile2_s)
    )
    return device


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--cpu-rehearsal",
        action="store_true",
        help="run the same phases on the CPU at {} rows (tests); the default "
        "is the full-width TPU run".format(REHEARSAL_ROWS),
    )
    parser.add_argument(
        "--log-dir",
        help="keep the children's logs here (a chip tool shows nothing while "
        "the command runs and throws the machine away after)",
    )
    args = parser.parse_args(argv)
    work = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        device = smoke(args.cpu_rehearsal, work)
    except SmokeFailure as e:
        sys.stderr.write("chip_smoke: FAILED: {}\n".format(e))
        return 1
    finally:
        if args.log_dir:
            os.makedirs(args.log_dir, exist_ok=True)
            for log in glob.glob(os.path.join(work, "*.log")):
                shutil.copy(log, args.log_dir)
        shutil.rmtree(work, ignore_errors=True)
    print(
        json.dumps(
            {
                "ok": True,
                "device": {
                    "platform": device["platform"],
                    "kind": device["kind"],
                    "count": device["count"],
                },
            }
        ),
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
