#!/bin/bash
# Multi-host built-image cluster tier: run the
# shipping image as a 2-host docker-compose cluster against the fabricated
# SageMaker filesystem — the repo analog of the reference's local_mode
# compose harness (reference test/utils/local_mode.py:477-557) and its
# strongest guarantees:
#
#   cluster  — distributed train over ShardedByS3Key data completes on both
#              hosts and EXACTLY ONE host writes the model (reference bar:
#              test_early_stopping.py:57-68 "exactly one host saved")
#   kill     — SIGTERM mid-train with save_model_on_termination: exactly one
#              host persists the intermediate model (spot semantics)
#   mme      — multi-model endpoint REST lifecycle against a real
#              `docker run` (reference test_multiple_model_endpoint.py:32-101)
#
# Usage: scripts/image_cluster.sh [cluster|kill|mme|all|dry]
# cluster/kill/mme/all need Docker + compose (v2 `docker compose` or v1
# `docker-compose`) and network for the image build; exit 75 = environment
# cannot run them (SKIP). `dry` needs NEITHER: it validates
# everything checkable without a docker daemon — Dockerfile structure and
# COPY sources, the version contract + native-parser gates the build RUNs,
# compose-file syntax, and console-script entrypoint wiring — so hosts
# without Docker degrade to partial verification instead of a full skip.
set -uo pipefail
REPO="$(cd "$(dirname "$0")/.." && pwd)"
DOCKER="${DOCKER:-docker}"
TAG="${IMAGE_TAG:-sagemaker-xgboost-tpu:cluster}"
DATA_SRC="${ABALONE_DATA:-/root/reference/test/resources/abalone/data}"
WHAT="${1:-all}"

require_docker() {
  command -v "$DOCKER" >/dev/null || { echo "SKIP: $DOCKER not installed"; exit 75; }
  if "$DOCKER" compose version >/dev/null 2>&1; then
    COMPOSE=("$DOCKER" compose)
  elif command -v docker-compose >/dev/null 2>&1; then
    COMPOSE=(docker-compose)
  else
    echo "SKIP: no docker compose available"; exit 75
  fi

  echo "== build =="
  "$DOCKER" build -f "$REPO/docker/Dockerfile.tpu" \
    --build-arg JAX_SPEC="${JAX_SPEC:-jax}" -t "$TAG" "$REPO" || exit 1
}
if [ "$WHAT" != dry ]; then require_docker; fi

WORK="$(mktemp -d)"
CID=""
cleanup() {
  [ -n "$CID" ] && "$DOCKER" rm -f "$CID" >/dev/null 2>&1 || true
  [ -n "${COMPOSE+x}" ] && [ -f "$WORK/docker-compose.yml" ] \
    && (cd "$WORK" && "${COMPOSE[@]}" down -t 5 >/dev/null 2>&1) || true
  rm -rf "$WORK"
}
trap cleanup EXIT

fabricate_host_tree() {  # fabricate_host_tree <host> <num_round> <extra_hp_json>
  local host=$1 rounds=$2 extra=${3:-}
  local root="$WORK/$host/opt/ml"
  mkdir -p "$root"/{input/config,input/data/train,model,output/data}
  cat > "$root/input/config/hyperparameters.json" <<JSON
{"num_round": "$rounds", "objective": "reg:squarederror", "max_depth": "4",
 "eval_metric": "rmse"${extra:+, $extra}}
JSON
  cat > "$root/input/config/inputdataconfig.json" <<'JSON'
{"train": {"ContentType": "libsvm", "TrainingInputMode": "File",
           "S3DistributionType": "ShardedByS3Key"}}
JSON
  cat > "$root/input/config/resourceconfig.json" <<JSON
{"current_host": "$host", "hosts": ["algo-1", "algo-2"]}
JSON
}

write_compose() {
  cat > "$WORK/docker-compose.yml" <<YAML
services:
  algo-1:
    image: $TAG
    hostname: algo-1
    command: train
    volumes: ["$WORK/algo-1/opt/ml:/opt/ml"]
    environment: &env
      JAX_PLATFORMS: cpu
      SM_JAX_DISTRIBUTED: "on"
      GRAFT_HEARTBEAT_TIMEOUT_S: "30"
  algo-2:
    image: $TAG
    hostname: algo-2
    command: train
    volumes: ["$WORK/algo-2/opt/ml:/opt/ml"]
    environment: *env
YAML
}

count_models() {
  local n=0
  for h in algo-1 algo-2; do
    [ -f "$WORK/$h/opt/ml/model/xgboost-model" ] && n=$((n + 1))
  done
  echo "$n"
}

run_cluster() {
  echo "== cluster: 2-host distributed train (sharded data) =="
  rm -rf "$WORK/algo-1" "$WORK/algo-2"
  fabricate_host_tree algo-1 12
  fabricate_host_tree algo-2 12
  # the reference's 2 abalone shards: one per host (ShardedByS3Key)
  cp "$DATA_SRC/train/abalone.train_0" "$WORK/algo-1/opt/ml/input/data/train/"
  cp "$DATA_SRC/train/abalone.train_1" "$WORK/algo-2/opt/ml/input/data/train/"
  write_compose
  (cd "$WORK" && "${COMPOSE[@]}" up --exit-code-from algo-1) \
    || { echo "FAIL: cluster train"; return 1; }
  local n; n="$(count_models)"
  [ "$n" = 1 ] || { echo "FAIL: expected exactly 1 host to save, got $n"; return 1; }
  echo "CLUSTER TIER OK"
}

run_kill() {
  echo "== kill: SIGTERM mid-train, save_model_on_termination =="
  rm -rf "$WORK/algo-1" "$WORK/algo-2"
  fabricate_host_tree algo-1 100000 '"save_model_on_termination": "true"'
  fabricate_host_tree algo-2 100000 '"save_model_on_termination": "true"'
  cp "$DATA_SRC/train/abalone.train_0" "$WORK/algo-1/opt/ml/input/data/train/"
  cp "$DATA_SRC/train/abalone.train_1" "$WORK/algo-2/opt/ml/input/data/train/"
  write_compose
  (cd "$WORK" && "${COMPOSE[@]}" up -d) || { echo "FAIL: compose up"; return 1; }
  # wait until boosting has demonstrably started (a metric line appeared)
  local started=0
  for _ in $(seq 1 120); do
    if (cd "$WORK" && "${COMPOSE[@]}" logs 2>/dev/null) | grep -q '^\S*algo.*\[0\]'; then
      started=1; break
    fi
    sleep 2
  done
  [ "$started" = 1 ] || { echo "FAIL: training never started"; return 1; }
  sleep 4
  # SIGTERM both containers (spot interruption); 30s grace for the save
  (cd "$WORK" && "${COMPOSE[@]}" stop -t 30) || true
  local n; n="$(count_models)"
  [ "$n" = 1 ] || { echo "FAIL: expected exactly 1 intermediate model, got $n"; return 1; }
  echo "KILL TIER OK"
}

run_mme() {
  echo "== mme: multi-model endpoint REST lifecycle (docker run) =="
  local port="${MME_PORT:-18082}"
  local mdir="$WORK/mme-models"
  # train one single-host model to load twice under different names
  rm -rf "$WORK/algo-1"
  mkdir -p "$WORK/algo-1/opt/ml"/{input/config,input/data/train,model,output/data}
  cat > "$WORK/algo-1/opt/ml/input/config/hyperparameters.json" <<'JSON'
{"num_round": "8", "objective": "reg:squarederror", "max_depth": "3"}
JSON
  cat > "$WORK/algo-1/opt/ml/input/config/inputdataconfig.json" <<'JSON'
{"train": {"ContentType": "libsvm", "TrainingInputMode": "File",
           "S3DistributionType": "FullyReplicated"}}
JSON
  cat > "$WORK/algo-1/opt/ml/input/config/resourceconfig.json" <<'JSON'
{"current_host": "algo-1", "hosts": ["algo-1"]}
JSON
  cp "$DATA_SRC"/train/* "$WORK/algo-1/opt/ml/input/data/train/"
  "$DOCKER" run --rm -v "$WORK/algo-1/opt/ml:/opt/ml" -e JAX_PLATFORMS=cpu \
    "$TAG" train || { echo "FAIL: mme seed train"; return 1; }
  mkdir -p "$mdir/m1" "$mdir/m2"
  cp "$WORK/algo-1/opt/ml/model/xgboost-model" "$mdir/m1/"
  cp "$WORK/algo-1/opt/ml/model/xgboost-model" "$mdir/m2/"

  CID="$("$DOCKER" run -d -p "$port:8080" -v "$mdir:/models" \
    -e JAX_PLATFORMS=cpu -e SAGEMAKER_MULTI_MODEL=true "$TAG" serve)"
  for i in $(seq 1 60); do
    curl -sf "localhost:$port/ping" >/dev/null 2>&1 && break
    sleep 1
    [ "$i" = 60 ] && { echo "FAIL: MME never healthy"; "$DOCKER" logs "$CID"; return 1; }
  done
  # load / list / invoke / unload / reload — the MMS REST surface
  curl -sf -X POST "localhost:$port/models" \
    -H "Content-Type: application/json" \
    -d '{"model_name": "m1", "url": "/models/m1"}' >/dev/null \
    || { echo "FAIL: load m1"; return 1; }
  curl -sf -X POST "localhost:$port/models" \
    -H "Content-Type: application/json" \
    -d '{"model_name": "m2", "url": "/models/m2"}' >/dev/null \
    || { echo "FAIL: load m2"; return 1; }
  curl -s "localhost:$port/models" | grep -q '"m1"' \
    || { echo "FAIL: list"; return 1; }
  PRED="$(curl -s -X POST "localhost:$port/models/m1/invoke" \
    -H "Content-Type: text/libsvm" \
    -d "1:2 2:0.74 3:0.6 4:0.195 5:1.974 6:0.598 7:0.4085 8:0.71")"
  python3 -c "v = float('''$PRED'''.strip()); assert 0.0 < v < 30.0, v" \
    || { echo "FAIL: invoke ($PRED)"; return 1; }
  curl -sf -X DELETE "localhost:$port/models/m1" >/dev/null \
    || { echo "FAIL: unload"; return 1; }
  curl -s -o /dev/null -w "%{http_code}" \
    -X POST "localhost:$port/models/m1/invoke" -H "Content-Type: text/libsvm" \
    -d "1:2" | grep -q 404 || { echo "FAIL: invoke after unload not 404"; return 1; }
  curl -sf -X POST "localhost:$port/models" \
    -H "Content-Type: application/json" \
    -d '{"model_name": "m1", "url": "/models/m1"}' >/dev/null \
    || { echo "FAIL: reload"; return 1; }
  "$DOCKER" rm -f "$CID" >/dev/null 2>&1; CID=""
  echo "MME TIER OK"
}

run_dry() {
  echo "== dry: image-tier checks that need no docker daemon =="

  echo "-- dockerfile structure + COPY sources"
  python3 - "$REPO/docker/Dockerfile.tpu" "$REPO" <<'EOF' || return 1
import re, sys

path, ctx = sys.argv[1], sys.argv[2]
KNOWN = {"FROM", "RUN", "COPY", "ADD", "ARG", "ENV", "ENTRYPOINT", "CMD",
         "EXPOSE", "WORKDIR", "USER", "LABEL", "VOLUME", "SHELL",
         "HEALTHCHECK", "STOPSIGNAL", "ONBUILD"}
# join line continuations, drop comments/blanks
raw = open(path).read()
lines, buf = [], ""
for line in raw.splitlines():
    if not buf and (not line.strip() or line.lstrip().startswith("#")):
        continue
    buf += line
    if buf.endswith("\\"):
        buf = buf[:-1] + " "
        continue
    lines.append(buf)
    buf = ""
assert not buf, "dangling line continuation"
instrs = []
for ln in lines:
    m = re.match(r"([A-Za-z]+)\s+(.*)", ln)
    assert m, f"unparseable line: {ln!r}"
    op = m.group(1).upper()
    assert op in KNOWN, f"unknown instruction {op}"
    instrs.append((op, m.group(2).strip()))
first_non_arg = next(op for op, _ in instrs if op != "ARG")
assert first_non_arg == "FROM", "first instruction must be FROM"
# no ENTRYPOINT/CMD by design: SageMaker invokes the image with the literal
# command "train"/"serve", resolved via PATH to the installed console
# scripts (wiring asserted in the entrypoint step below)
import os
for op, rest in instrs:
    if op in ("COPY", "ADD"):
        parts = [p for p in rest.split() if not p.startswith("--")]
        for src in parts[:-1]:
            assert os.path.exists(os.path.join(ctx, src.lstrip("/"))) or src == ".", \
                f"{op} source {src!r} missing from build context"
print(f"   {len(instrs)} instructions ok")
EOF

  echo "-- version contract + native parser (the gates the image build runs)"
  python3 -m sagemaker_xgboost_container_tpu.version_contract || return 1
  python3 -c "from sagemaker_xgboost_container_tpu.data import native; \
assert native.native_available(), 'native fastdata parser unavailable'" || return 1

  echo "-- compose file syntax"
  write_compose
  # dry skips require_docker, so detect compose here (without requiring it):
  # on docker hosts the real `compose config` validation runs even in dry
  if [ -z "${COMPOSE+x}" ]; then
    if command -v "$DOCKER" >/dev/null && "$DOCKER" compose version >/dev/null 2>&1; then
      COMPOSE=("$DOCKER" compose)
    elif command -v docker-compose >/dev/null 2>&1; then
      COMPOSE=(docker-compose)
    fi
  fi
  if [ -n "${COMPOSE+x}" ]; then
    (cd "$WORK" && "${COMPOSE[@]}" config -q) || return 1
  else
    python3 - "$WORK/docker-compose.yml" <<'EOF' || return 1
import sys

try:
    import yaml
except ImportError:  # minimal structural check without pyyaml
    text = open(sys.argv[1]).read()
    assert "services:" in text and "algo-1:" in text and "algo-2:" in text
    assert "&env" in text and "*env" in text, "anchor/alias pair missing"
    print("   structural check ok (no pyyaml)")
    sys.exit(0)
doc = yaml.safe_load(open(sys.argv[1]))
svcs = doc["services"]
assert set(svcs) == {"algo-1", "algo-2"}, svcs.keys()
for name, svc in svcs.items():
    assert svc["image"], name
    assert svc["command"] == "train", name
    assert svc["volumes"] and svc["volumes"][0].endswith(":/opt/ml"), name
    # the &env anchor must resolve to the same distributed-training env on both
    assert svc["environment"]["SM_JAX_DISTRIBUTED"] == "on", name
print("   yaml parse + anchor resolution ok")
EOF
  fi

  echo "-- entrypoint wiring (setup.py console scripts resolve + on PATH)"
  python3 - "$REPO" <<'EOF' || return 1
import configparser, importlib, os, re, shutil, sys

repo = sys.argv[1]
setup = open(os.path.join(repo, "setup.py")).read()
scripts = dict(re.findall(r"['\"](\w+)\s*=\s*([\w.:]+)['\"]", setup))
assert "train" in scripts and "serve" in scripts, scripts
for name, target in scripts.items():
    mod, func = target.split(":")
    m = importlib.import_module(mod)
    assert callable(getattr(m, func)), target
    # PATH presence is an env property (needs pip install); the image build
    # always installs, so locally it only warns
    exe = shutil.which(name)
    note = exe or "not on PATH here; image build installs it"
    print(f"   {name} -> {target} ({note})")
EOF

  echo "DRY TIER OK"
}

rc=0
case "$WHAT" in
  cluster) run_cluster || rc=1 ;;
  kill)    run_kill || rc=1 ;;
  mme)     run_mme || rc=1 ;;
  all)     run_cluster || rc=1; run_kill || rc=1; run_mme || rc=1 ;;
  dry)     run_dry || rc=1 ;;
  *) echo "usage: $0 [cluster|kill|mme|all|dry]"; exit 2 ;;
esac
[ $rc -eq 0 ] && echo "IMAGE CLUSTER OK"
exit $rc
