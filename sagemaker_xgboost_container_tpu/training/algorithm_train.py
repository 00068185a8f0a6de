"""Algorithm-mode training orchestration: ``sagemaker_train`` + ``train_job``.

The control flow mirrors the reference (algorithm_mode/train.py:116-500):
validate HPs and channels, load + validate data matrices, pick single-host vs
multi-host, run boosting with the callback stack, optionally repeated k-fold
CV with out-of-fold prediction recording, and save model(s) master-only under
the exact ``xgboost-model[-fold]`` names. Errors matching the known customer
substrings re-raise as UserError (reference :461-467).

The compute underneath is the XLA booster (models/booster.py); "use_dask_gpu_
training" is rejected up-front — the data-parallel TPU mesh subsumes that
path.
"""

import logging
import os

import numpy as np
from sklearn.model_selection import RepeatedKFold, RepeatedStratifiedKFold

from ..algorithm import channels as cv
from ..algorithm import hyperparameters as hpv
from ..algorithm import metrics as metrics_mod
from ..constants import CUSTOMER_ERRORS, MODEL_NAME
from ..data.content_types import get_content_type
from ..data.readers import (
    check_data_redundancy,
    get_data_matrix,
    get_size,
    validate_data_file_path,
)
from ..parallel import distributed
from ..telemetry import register_runtime_gauges, span, start_cluster_telemetry
from ..telemetry.spans import record_startup
from ..toolkit import exceptions as exc
from ..toolkit.channels import PIPE_MODE
from ..models import booster
from . import train_utils
from .callbacks import get_callbacks
from .prediction_utils import ValidationPredictionRecorder

logger = logging.getLogger(__name__)

SM_OUTPUT_DATA_DIR = "SM_OUTPUT_DATA_DIR"


def _streaming_plan(train_cfg, train_size, combine_train_val, is_pipe, num_hosts):
    """Decide whole-file vs chunked ingest. -> (use_streaming, max_bin, cfg).

    ``SM_INGEST_MODE=chunked`` forces the chunked path (and raises on an
    unsupported config rather than silently falling back); ``whole`` pins
    the legacy readers; ``auto`` streams a supported single-host job whose
    local channel exceeds one chunk. Multi-host ``auto`` stays on the
    whole-file path: the decision must be identical on every rank before
    any rendezvous exists, and local channel sizes (ShardedByS3Key) are
    not — forcing ``chunked`` via env is uniform by construction.
    """
    from ..data import streaming

    cfg = streaming.resolve_ingest_config()
    if train_cfg is None or is_pipe:
        # forced chunked must refuse, not silently fall back (the documented
        # contract — every other unsupported combination raises)
        if cfg.mode == "chunked":
            raise exc.UserError(
                "SM_INGEST_MODE=chunked is not supported for {}; use "
                "SM_INGEST_MODE=whole.".format(
                    "Pipe-mode input" if is_pipe
                    else "jobs without a validated training config"
                )
            )
        return False, None, cfg
    if cfg.mode == "whole":
        return False, None, cfg
    ok, why, max_bin = streaming.supports_streaming(train_cfg)
    if combine_train_val and ok:
        ok, why = False, "k-fold CV slices float features per fold"
    if cfg.mode == "chunked":
        if not ok:
            raise exc.UserError(
                "SM_INGEST_MODE=chunked is not supported for this job ({}); "
                "use SM_INGEST_MODE=whole or adjust the config.".format(why)
            )
        return True, max_bin, cfg
    # auto
    if not ok or num_hosts > 1:
        return False, None, cfg
    return train_size > cfg.chunk_bytes, max_bin, cfg


def get_validated_data_matrices(
    train_path, validate_path, content_type, csv_weights=0, is_pipe=False,
    combine_train_val=False, train_cfg=None, sm_hosts=None, sm_current_host=None,
):
    """Size/format-check both channels and parse them into DataMatrix objects.

    With the streaming plane armed (``_streaming_plan``) the channels ingest
    chunk-by-chunk into pre-binned matrices instead (``data/streaming.py``):
    the training channel builds the (rank-agreed) cuts, the validation
    channel bins with them. Failures of the chunked plane raise
    ``streaming.IngestError`` — the caller converts them to exit 85.
    """
    train_size = get_size(train_path, is_pipe) if train_path else 0
    val_size = get_size(validate_path, is_pipe) if validate_path else 0

    if not is_pipe:
        if train_size > 0:
            validate_data_file_path(train_path, content_type)
        if val_size > 0:
            validate_data_file_path(validate_path, content_type)

    num_hosts = len(sm_hosts) if sm_hosts else 1
    use_streaming, max_bin, _cfg = _streaming_plan(
        train_cfg, train_size, combine_train_val, is_pipe, num_hosts
    )
    if use_streaming:
        from ..data import streaming

        if streaming.channel_has_sidecars(content_type, train_path, validate_path):
            if _cfg.mode == "chunked":
                raise exc.UserError(
                    "SM_INGEST_MODE=chunked cannot honor libsvm .weight/"
                    ".group sidecar files; remove them or use "
                    "SM_INGEST_MODE=whole."
                )
            logger.info(
                "channel carries libsvm .weight/.group sidecar files; "
                "using the whole-file readers (chunked ingest cannot "
                "honor them)"
            )
            use_streaming = False
    if use_streaming:
        hosts = sm_hosts if num_hosts > 1 else None
        # job-scoped quarantine/budget state: a second ingest in this
        # process (local mode, an elastic-reform replay) must not inherit
        # the previous run's consumed skip budget or carry its quarantine
        # entries into this model's manifest
        streaming.reset_ingest_state()
        logger.info(
            "Streaming (chunked) channel ingest armed: max_bin=%d, %d host(s)",
            max_bin, num_hosts,
        )
        # every host joins the ingest exchange regardless of local channel
        # size (a data-less host contributes an empty sketch and returns
        # None) — peers must never hang waiting for its summary
        train_dmatrix = streaming.ingest_channel(
            train_path, content_type, max_bin, channel="train",
            csv_weights=csv_weights, hosts=hosts, current_host=sm_current_host,
        )
        val_dmatrix = None
        if validate_path is not None and (val_size > 0 or num_hosts > 1):
            val_dmatrix = streaming.ingest_channel(
                validate_path, content_type, max_bin, channel="validation",
                csv_weights=csv_weights,
                cut_points=train_dmatrix.cut_points if train_dmatrix else None,
                hosts=hosts, current_host=sm_current_host,
            )
            if train_dmatrix is None:
                # a train-data-less rank still joined the validation
                # exchange (peers must never hang waiting for it), but
                # without the agreed train cuts its local validation matrix
                # was re-sketched against itself — it must not leak into
                # eval; the rank exits via the existing no-data contract
                val_dmatrix = None
        return train_dmatrix, val_dmatrix, train_dmatrix

    train_dmatrix = (
        get_data_matrix(train_path, content_type, csv_weights=csv_weights, is_pipe=is_pipe)
        if train_size > 0
        else None
    )
    val_dmatrix = (
        get_data_matrix(validate_path, content_type, csv_weights=csv_weights, is_pipe=is_pipe)
        if val_size > 0
        else None
    )

    train_val_dmatrix = train_dmatrix
    if combine_train_val and train_dmatrix is not None and val_dmatrix is not None:
        logger.info("Read both train and validation data into one DataMatrix")
        train_val_dmatrix = train_dmatrix.concat(val_dmatrix)
    return train_dmatrix, val_dmatrix, train_val_dmatrix


def sagemaker_train(
    train_config,
    data_config,
    train_path,
    val_path,
    model_dir,
    sm_hosts,
    sm_current_host,
    checkpoint_config,
):
    """Validate config, load data, select execution mode, run train_job."""
    # process start and the imports, as spans of what is past already
    # (telemetry/spans.py; `train()` adds startup.before_train at its entry)
    record_startup()
    # XLA compile / RSS / device-buffer gauges: registered before any jax
    # work so the first compile is counted (adds no threads; jax-absent and
    # CPU-only paths no-op)
    register_runtime_gauges()
    metrics = metrics_mod.initialize()
    hyperparameters = hpv.initialize(metrics)
    validated_train_config = hyperparameters.validate(train_config)
    if validated_train_config.get("updater"):
        validated_train_config["updater"] = ",".join(validated_train_config["updater"])

    channels = cv.initialize()
    validated_data_config = channels.validate(data_config)

    file_type = get_content_type(validated_data_config["train"].get("ContentType"))
    input_mode = validated_data_config["train"].get("TrainingInputMode")
    csv_weights = validated_train_config.get("csv_weights", 0)
    is_pipe = input_mode == PIPE_MODE

    validation_channel = validated_data_config.get("validation", None)
    combine_train_val = "_kfold" in validated_train_config
    if val_path is not None:
        if train_path == val_path or os.path.basename(train_path) == os.path.basename(val_path):
            logger.warning(
                "Found same path for training and validation. This is not recommended "
                "and results may not be correct."
            )
        elif not is_pipe:
            check_data_redundancy(train_path, val_path)

    num_hosts = len(sm_hosts)
    checkpoint_dir = checkpoint_config.get("LocalPath", None)

    if validated_train_config.pop("use_dask_gpu_training", "false") == "true":
        raise exc.UserError(
            "use_dask_gpu_training is not available in the TPU container: there are no "
            "CUDA devices. Distributed training runs data-parallel over the TPU mesh "
            "automatically — remove this hyperparameter."
        )

    with span("data_ingest", emit=True):
        from ..data import streaming

        try:
            train_dmatrix, val_dmatrix, train_val_dmatrix = get_validated_data_matrices(
                train_path, val_path, file_type, csv_weights, is_pipe,
                combine_train_val, train_cfg=validated_train_config,
                sm_hosts=sm_hosts, sm_current_host=sm_current_host,
            )
        except streaming.IngestError as e:
            # the chunked plane's failure contract: every rank reached this
            # same verdict from the same allgathered state — flight-recorder
            # dump + EXIT_INGEST_FAILED (85) on all of them
            streaming.abort_on_ingest_failure(e)
            # only reachable when the exit is patched (tests): classify as
            # a platform failure so the failure file names the ingest error
            raise exc.PlatformError(str(e))
    missing_validation_data = validation_channel and not val_dmatrix

    train_args = dict(
        train_cfg=validated_train_config,
        train_dmatrix=train_dmatrix,
        val_dmatrix=val_dmatrix,
        train_val_dmatrix=train_val_dmatrix,
        model_dir=model_dir,
        checkpoint_dir=checkpoint_dir,
    )

    if num_hosts > 1:
        logger.info("Distributed node training with %d hosts: %s", num_hosts, sm_hosts)
        distributed.wait_hostname_resolution(sm_hosts)
        include_in_training = True
        if not train_dmatrix:
            logger.warning(
                "Host %s does not have training data and will not be used in "
                "distributed training. Please divide the training data across "
                "instances properly.",
                sm_current_host,
            )
            include_in_training = False
        if missing_validation_data:
            logger.warning(
                "Host %s does not have validation data in the validation channel and "
                "will not be used in distributed training.",
                sm_current_host,
            )
            include_in_training = False
        def _pre_exec(participating_hosts, current_host):
            # order matters: jax.distributed first (it must precede any JAX
            # computation), then the elastic membership registration (its
            # resolved SM_ELASTIC snapshot gates the abort listener), then
            # the abort listener (it must be up before rank 0's aggregator
            # can ever decide to broadcast), then the heartbeat plane over
            # the RE-FORMED cluster — ranks must match the participating
            # host list, not the original SM_HOSTS (hosts without data
            # already exited)
            maybe_init_jax_distributed(participating_hosts, current_host)
            from . import elastic

            if combine_train_val:
                # k-fold CV trains many per-fold callback stacks with no
                # single resume point to reform around — shrink-to-continue
                # is out of scope there, so leave the plane unregistered
                # (inert callback, legacy stale-host abort applies)
                if elastic.resolve_elastic_config().enabled:
                    logger.warning(
                        "SM_ELASTIC is not supported for k-fold CV jobs; a "
                        "dead host takes the legacy coordinated abort"
                    )
            else:
                elastic.register_cluster(participating_hosts, current_host)
            from .watchdog import start_abort_plane

            start_abort_plane(participating_hosts, current_host)
            start_cluster_telemetry(participating_hosts, current_host)
            # membership for the consensus guard (SM_CONSENSUS_EVERY): the
            # digest allgather runs over the RE-FORMED cluster, same as the
            # heartbeat plane — hosts without data already exited
            from .consensus import register_cluster

            register_cluster(participating_hosts, current_host)
            # trace export files are per-rank (trace-rank<r>.json); the rank
            # follows the re-formed cluster like everything above
            from ..telemetry import tracing

            tracing.set_rank(sorted(participating_hosts).index(current_host))
            # fleet observability plane last: span shipping needs the rank
            # set above, and the rank-0 collector/status endpoint bind over
            # the re-formed cluster like the heartbeat plane (inert unless
            # SM_FLEET_TRACE / SM_STATUS_PORT are set)
            from ..telemetry import fleet

            fleet.start_fleet_plane(participating_hosts, current_host)

        distributed.distributed_run(
            exec_fun=train_job,
            args=train_args,
            include_in_training=include_in_training,
            hosts=sm_hosts,
            current_host=sm_current_host,
            pre_exec=_pre_exec,
        )
    elif num_hosts == 1:
        if train_dmatrix:
            if missing_validation_data:
                raise exc.UserError("No data in validation channel path {}".format(val_path))
            logger.info("Single node training.")
            train_args.update({"is_master": True})
            # single-host jobs still get the /status endpoint (and, with
            # SM_FLEET_TRACE, a one-lane merged trace over loopback)
            from ..telemetry import fleet

            fleet.start_fleet_plane([sm_current_host], sm_current_host)
            train_job(**train_args)
        else:
            raise exc.UserError("No data in training channel path {}".format(train_path))
    else:
        raise exc.PlatformError("Number of hosts should be an int greater than or equal to 1")


def training_mesh(num_devices_cap=None):
    """Data-parallel mesh over every visible device (None on one device).

    Under multi-host ``jax.distributed``, jax.devices() spans the whole job,
    so the same Mesh construction covers pod-scale data parallelism — the TPU
    replacement for the reference's Rabit worker group (SURVEY.md §2.3).
    """
    import jax
    from jax.sharding import Mesh

    devices = jax.devices()
    n = len(devices)
    if num_devices_cap:
        n = min(n, int(num_devices_cap))
    if n <= 1:
        return None
    return Mesh(np.array(devices[:n]), axis_names=("data",))


def _accelerator_runtime_present():
    """True when an accelerator backend could come up: the libtpu wheel
    (TPU images) or any registered PJRT plugin. Never initializes a
    backend. CPU-only hosts (no plugin) return False, so auto-mode skips
    distributed init there — the pre-r4 behavior."""
    import importlib.util

    if importlib.util.find_spec("libtpu") is not None:
        return True
    try:
        from importlib.metadata import entry_points

        if len(list(entry_points(group="jax_plugins"))):
            return True
    except Exception:  # metadata backends vary; absence of evidence -> no accel
        pass
    try:
        import jax_plugins  # namespace package populated by installed plugins

        return len(list(getattr(jax_plugins, "__path__", []))) > 0
    except ImportError:
        return False


def maybe_init_jax_distributed(sm_hosts, sm_current_host, port=12355):
    """Bring up the multi-host XLA runtime (coordinator = sorted hosts[0]).

    Mirrors the reference's deterministic rank convention
    (distributed.py:155,:207). Gated to accelerator platforms: the CPU
    simulation tests drive the mesh path in-process instead.

    Mid-train host loss: there is no worker-rejoin analog of the reference
    tracker's ``recover`` path (dmlc_patch/tracker.py:341-353) — when a host
    stops heartbeating, the coordination service poisons the collectives and
    every surviving host terminates within ~GRAFT_HEARTBEAT_TIMEOUT_S
    (default 100s; the job FAILS, it never continues on partial data).
    Recovery is restart + checkpoint resume (training/checkpointing.py picks
    up at the last saved round — the same story as the reference's spot
    training). Failure semantics regression-tested in
    tests/test_parallel.py::test_host_loss_aborts_survivors.
    """
    import jax

    if len(sm_hosts) <= 1:
        return False
    mode = os.environ.get("SM_JAX_DISTRIBUTED", "auto")
    if mode == "off":
        return False
    # Platform detection WITHOUT jax.default_backend(): touching the backend
    # would initialize it, and jax.distributed.initialize() must run first
    # ("must be called before any JAX computations") — the previous
    # default_backend() probe would have PlatformError'd every real
    # multi-host TPU job at startup. Read the requested-platform config;
    # when unset, sniff for an accelerator runtime (libtpu wheel / PJRT
    # plugin) instead of initializing one.
    platforms = (
        os.environ.get("JAX_PLATFORMS")
        or jax.config.jax_platforms
        or ""
    )
    if platforms:
        cpu_only = set(platforms.split(",")) <= {"cpu"}
    else:
        cpu_only = not _accelerator_runtime_present()
    if cpu_only and mode != "on":
        # "auto" skips CPU (the in-process mesh tests cover that path);
        # "on" forces a real multi-process CPU cluster — used by the
        # docker-compose image tier to exercise true cross-host training
        logger.info("Skipping jax.distributed on the CPU backend")
        return False
    hosts = sorted(sm_hosts)
    try:
        jax.distributed.initialize(
            coordinator_address="{}:{}".format(hosts[0], port),
            num_processes=len(hosts),
            process_id=hosts.index(sm_current_host),
            heartbeat_timeout_seconds=int(
                os.environ.get("GRAFT_HEARTBEAT_TIMEOUT_S", "100")
            ),
        )
        logger.info(
            "jax.distributed up: %d processes, %d global devices",
            len(hosts),
            jax.device_count(),
        )
        return True
    except Exception as e:
        # record the failure for the /status endpoint before raising: a
        # wedged multi-host bring-up is exactly when an operator curls
        # /status instead of grepping eight hosts' logs
        from ..telemetry import fleet

        fleet.note_status(backend_init_error=str(e))
        raise exc.PlatformError(
            "Failed to initialize the multi-host XLA runtime", caused_by=e
        )


def _reinit_jax_distributed(sm_hosts, sm_current_host):
    """Re-init the multi-host XLA runtime at the shrunken world size.

    The elastic reform hook: tear down the old coordination client (whose
    membership still includes the dead host) and bring the runtime back up
    over the survivor list. On CPU-auto paths (drills, single-accelerator
    hosts) both halves are no-ops, exactly like startup.
    """
    import jax

    try:
        if jax.distributed.is_initialized():
            jax.distributed.shutdown()
    except Exception as e:
        # a coordination client wedged on the dead host may refuse a clean
        # shutdown; re-init decides whether that is fatal
        logger.warning("jax.distributed shutdown before re-init failed: %s", e)
    return maybe_init_jax_distributed(sm_hosts, sm_current_host)


def train_job(
    train_cfg, train_dmatrix, val_dmatrix, train_val_dmatrix, model_dir, checkpoint_dir, is_master
):
    """Run boosting (or repeated k-fold CV) on this node; save master-only.

    With the elastic plane armed (``SM_ELASTIC``), the single-model branch
    runs under ``elastic.supervised_train``: a membership reform unwinds the
    boosting loop at a round boundary, survivors re-rendezvous, and this
    function's ``train_once`` closure rebuilds everything per generation —
    fresh callbacks (which re-read the last digest-verified checkpoint and
    validate the recorded world-size transition), a fresh mesh over the
    re-initialized runtime, and a rebuilt booster session under the SAME
    hist-knobs snapshot. ``is_master`` survives a shrink unchanged: the
    master is the sorted-first participant, and only the master's own
    aggregator can propose a shrink — a dead master is not survivable (the
    legacy jax heartbeat timeout applies) and is documented as such.
    """
    from ..data.binning import BinnedMatrix
    from ..data.matrix import normalize_feature_types
    from ..ops.histogram import resolve_hist_knobs
    from ..utils.device_runtime import start_device_runtime

    train_cfg = dict(train_cfg)
    # columns given as categories: the channels carry no types, the
    # hyperparameters name them (`feature_types`, under `enable_categorical`)
    feature_types = train_cfg.pop("feature_types", None)
    if train_cfg.pop("enable_categorical", "false") == "true" and feature_types is not None:
        for matrix in (train_dmatrix, val_dmatrix, train_val_dmatrix):
            if matrix is None:
                continue
            if isinstance(matrix, BinnedMatrix) and "c" in feature_types:
                # `_streaming_plan` keeps such a job on the whole-file readers;
                # a pre-binned matrix has sketched the codes as numbers already
                raise exc.UserError(
                    "Categorical columns (feature_types 'c') need the whole-file "
                    "readers: chunked ingest bins a category's code as a number. "
                    "Use SM_INGEST_MODE=whole."
                )
            if not isinstance(matrix, BinnedMatrix):
                matrix.feature_types = normalize_feature_types(feature_types, matrix.num_col)
    num_devices_cap = train_cfg.pop("_num_devices", None)
    mesh = training_mesh(num_devices_cap)
    # one knob snapshot for the whole job: every generation the reform loop
    # rebuilds the session with, so a shrink can never pick up mid-job env
    # drift — and what the device-runtime line reports is what trains
    hist_knobs = resolve_hist_knobs()
    # before the first compile: arm the compile cache, say what we run on.
    # Chunked ingest hands over matrices it sketched and binned on the host;
    # the sketch lowering named in the line is the whole-file path's
    start_device_runtime(
        "train", mesh=mesh, knobs=hist_knobs, route_width=train_dmatrix.num_col,
        grow_policy=train_cfg.get("grow_policy", "depthwise"),
        max_depth=int(train_cfg.get("max_depth") or 6),  # TrainConfig's default
        max_leaves=int(train_cfg.get("max_leaves") or 0),
        trees_per_round=(
            int(train_cfg.get("num_class") or 1)
            if str(train_cfg.get("objective", "")).startswith("multi:")
            else 1
        ) * int(train_cfg.get("num_parallel_tree") or 1),
        ingest="chunked" if isinstance(train_dmatrix, BinnedMatrix) else "whole",
    )
    # r2: ranking objectives shard rows by group and survival:cox gathers
    # global risk sets inside the jitted round, so every objective trains on
    # a data-parallel mesh
    num_round = train_cfg.pop("num_round")
    save_model_on_termination = train_cfg.pop("save_model_on_termination", "false")

    # fleet observability: planned rounds feed the /status ETA, and kill -3
    # becomes a live inspection dump (flight recorder + skew snapshot)
    # instead of the default core-dump abort — both no-ops when unobserved
    from ..telemetry import fleet

    fleet.note_status(rounds_planned=num_round)
    fleet.install_sigquit_handler(default_dir=model_dir)

    tuning_objective_metric_param = train_cfg.pop("_tuning_objective_metric", None)
    eval_metric = train_cfg.get("eval_metric")
    cleaned_eval_metric, configured_feval, tuning_objective_metric = (
        train_utils.get_eval_metrics_and_feval(tuning_objective_metric_param, eval_metric)
    )
    if cleaned_eval_metric:
        train_cfg["eval_metric"] = cleaned_eval_metric
    else:
        train_cfg.pop("eval_metric", None)

    early_stopping_rounds = train_cfg.pop("early_stopping_rounds", None)
    early_stopping_data_name = "validation" if val_dmatrix else None
    early_stopping_metric = None
    if early_stopping_rounds:
        if tuning_objective_metric:
            early_stopping_metric = tuning_objective_metric[-1]
        elif eval_metric:
            early_stopping_metric = eval_metric[-1]

    logger.info(
        "Train matrix has %d rows and %d columns",
        train_dmatrix.num_row,
        train_dmatrix.num_col,
    )
    if val_dmatrix:
        logger.info("Validation matrix has %d rows", val_dmatrix.num_row)

    # Default to batching several boosting rounds per device dispatch when no
    # per-round host artifact is required (checkpoint files / intermediate
    # model saves must land every round for spot safety). Metrics that can't
    # ride back from the device (feval, ranking metrics) no longer force
    # K=1: the booster keeps the fused dispatch and host-evaluates once per
    # K rounds (docs/DESIGN.md §Round pipeline). Explicit
    # _rounds_per_dispatch always wins.
    if (
        not checkpoint_dir
        and save_model_on_termination != "true"
        and "_rounds_per_dispatch" not in train_cfg
    ):
        train_cfg["_rounds_per_dispatch"] = int(
            os.environ.get("SM_ROUNDS_PER_DISPATCH_DEFAULT", "8")
        )

    try:
        kfold = train_cfg.pop("_kfold", None)
        watchlist = [(train_dmatrix, "train")]
        if val_dmatrix is not None:
            watchlist.append((val_dmatrix, "validation"))

        from .profiling import xla_trace

        if kfold is None:
            from . import elastic

            mesh_box = {"mesh": mesh}

            def _train_once():
                xgb_model, iteration, callbacks = get_callbacks(
                    model_dir=model_dir,
                    checkpoint_dir=checkpoint_dir,
                    early_stopping_data_name=early_stopping_data_name,
                    early_stopping_metric=early_stopping_metric,
                    early_stopping_rounds=early_stopping_rounds,
                    save_model_on_termination=save_model_on_termination,
                    is_master=is_master,
                    num_round=num_round,
                    num_rows=train_dmatrix.num_row,
                    train_cfg=train_cfg,
                )
                try:
                    with xla_trace(), span("train", emit=True):
                        return booster.train(
                            train_cfg,
                            train_dmatrix,
                            num_boost_round=num_round - iteration,
                            evals=watchlist,
                            feval=configured_feval,
                            callbacks=callbacks,
                            xgb_model=xgb_model,
                            mesh=mesh_box["mesh"],
                            hist_knobs=hist_knobs,
                        )
                except elastic.ReformRequested:
                    # the abandoned generation's threads (watchdog monitor,
                    # checkpoint deleter) must not outlive it — a stale
                    # watchdog firing mid-reform would exit 79 a healthy
                    # survivor
                    elastic.drain_callbacks(callbacks)
                    raise

            def _on_reform(new_hosts, current_host):
                # per-generation re-wiring: runtime first (as at startup),
                # then the mesh over the new device set, then the control
                # planes over the survivor list
                _reinit_jax_distributed(new_hosts, current_host)
                mesh_box["mesh"] = training_mesh(num_devices_cap)
                from .watchdog import start_abort_plane

                start_abort_plane(new_hosts, current_host)
                start_cluster_telemetry(new_hosts, current_host)
                from ..telemetry import tracing

                tracing.set_rank(sorted(new_hosts).index(current_host))
                from ..telemetry import fleet

                fleet.start_fleet_plane(new_hosts, current_host)

            bst = elastic.supervised_train(_train_once, on_reform=_on_reform)
        else:
            num_cv_round = train_cfg.pop("_num_cv_round", 1)
            logger.info(
                "Run %s-round of %s-fold cross validation with %s rows",
                num_cv_round,
                kfold,
                train_val_dmatrix.num_row,
            )
            bst = []
            evals_results = []
            num_class = train_cfg.get("num_class", None)
            objective = train_cfg.get("objective") or ""
            classification_problem = bool(num_class) or objective.startswith("binary:")
            y = train_val_dmatrix.get_label() if classification_problem else None
            rkf = (
                RepeatedStratifiedKFold(n_splits=kfold, n_repeats=num_cv_round)
                if y is not None
                else RepeatedKFold(n_splits=kfold, n_repeats=num_cv_round)
            )
            val_pred = ValidationPredictionRecorder(
                y_true=train_val_dmatrix.get_label(),
                num_cv_round=num_cv_round,
                classification=classification_problem,
                output_data_dir=os.environ[SM_OUTPUT_DATA_DIR],
            )
            splits = list(rkf.split(X=range(train_val_dmatrix.num_row), y=y))

            parallel_folds = _try_parallel_cv(
                train_cfg=train_cfg,
                train_val_dmatrix=train_val_dmatrix,
                splits=splits,
                num_round=num_round,
                kfold=kfold,
                checkpoint_dir=checkpoint_dir,
                early_stopping_rounds=early_stopping_rounds,
                configured_feval=configured_feval,
                save_model_on_termination=save_model_on_termination,
            )
            if parallel_folds is not None:
                bst, evals_results = parallel_folds
                for k, (train_idx, val_idx) in enumerate(splits):
                    cv_val = train_val_dmatrix.slice(val_idx)
                    val_pred.record(val_idx, bst[k].predict(cv_val.features))
                    if (k + 1) % kfold == 0:
                        logger.info(
                            "The metrics of round %d cross validation",
                            (k + 1) // kfold,
                        )
                        print_cv_metric(num_round, evals_results[k + 1 - kfold : k + 1])
            else:
                for train_idx, val_idx in splits:
                    cv_train = train_val_dmatrix.slice(train_idx)
                    cv_val = train_val_dmatrix.slice(val_idx)
                    xgb_model, iteration, callbacks = get_callbacks(
                        model_dir=model_dir,
                        checkpoint_dir=checkpoint_dir,
                        early_stopping_data_name=early_stopping_data_name,
                        early_stopping_metric=early_stopping_metric,
                        early_stopping_rounds=early_stopping_rounds,
                        save_model_on_termination=save_model_on_termination,
                        is_master=is_master,
                        fold=len(bst),
                        num_round=num_round,
                        num_rows=cv_train.num_row,
                        train_cfg=train_cfg,
                    )

                    class _EvalsRecorder:
                        def __init__(self):
                            self.log = {}

                        def after_iteration(self, model, epoch, evals_log):
                            self.log = {k: dict(v) for k, v in evals_log.items()}
                            return False

                    recorder = _EvalsRecorder()
                    logger.info("Train cross validation fold %d", (len(bst) % kfold) + 1)
                    fold_booster = booster.train(
                        train_cfg,
                        cv_train,
                        num_boost_round=num_round - iteration,
                        evals=[(cv_train, "train"), (cv_val, "validation")],
                        feval=configured_feval,
                        callbacks=callbacks + [recorder],
                        xgb_model=xgb_model,
                        mesh=mesh,
                    )
                    bst.append(fold_booster)
                    evals_results.append(recorder.log)
                    val_pred.record(val_idx, fold_booster.predict(cv_val.features))
                    if len(bst) % kfold == 0:
                        logger.info(
                            "The metrics of round %d cross validation", len(bst) // kfold
                        )
                        print_cv_metric(num_round, evals_results[-kfold:])
            val_pred.save()
            if num_cv_round > 1:
                logger.info(
                    "The overall metrics of %s-round cross validation", num_cv_round
                )
                print_cv_metric(num_round, evals_results)
    except Exception as e:
        for customer_error_message in CUSTOMER_ERRORS:
            if customer_error_message in str(e):
                raise exc.UserError(str(e))
        if isinstance(e, (exc.UserError, exc.PlatformError)):
            raise
        raise exc.AlgorithmError("XGB train call failed with exception:\n {}".format(e))

    os.makedirs(model_dir, exist_ok=True)
    if is_master:
        from ..data import streaming
        from ..utils import integrity
        from . import elastic

        def _save_with_manifest(model, model_location):
            model.save_model(model_location)
            try:
                # the manifest travels inside model.tar.gz: serving
                # digest-verifies the artifact at load. Best-effort — a
                # failed sidecar write must not fail a finished job (the
                # model loads manifest-less, exactly like older runs).
                # A model that trained through elastic shrinks carries the
                # full membership log, and one that trained past quarantined
                # input chunks carries the agreed quarantine record — the
                # provenance for "this artifact lost those rows".
                # model telemetry (SM_MODEL_TELEMETRY): the final learning
                # curve and the drift-PSI baseline ride in the manifest too,
                # so serving gets the training-time distribution for free
                from ..telemetry import model as model_telemetry

                integrity.write_manifest(
                    model_location,
                    fingerprint=integrity.config_fingerprint(train_cfg),
                    membership_log=elastic.membership_log() or None,
                    quarantine=streaming.quarantine_record(),
                    learning=model_telemetry.learning_summary(),
                    drift_baseline=model_telemetry.drift_baseline(),
                )
            except OSError as e:
                logger.warning(
                    "could not write model manifest for %s: %s", model_location, e
                )

        try:
            # the standalone quarantine manifest (ingest-quarantine.json)
            # rides next to the model so operators can audit skipped input
            # without parsing the model sidecar; absent when nothing skipped
            qpath = streaming.write_quarantine_manifest(model_dir)
            if qpath:
                logger.warning("ingest quarantine manifest written to %s", qpath)
        except OSError as e:
            logger.warning("could not write ingest quarantine manifest: %s", e)

        with span("model_save", emit=True):
            if not isinstance(bst, list):
                model_location = os.path.join(model_dir, MODEL_NAME)
                _save_with_manifest(bst, model_location)
                logger.debug("Stored trained model at %s", model_location)
            else:
                for fold, fold_booster in enumerate(bst):
                    model_location = os.path.join(
                        model_dir, "{}-{}".format(MODEL_NAME, fold)
                    )
                    _save_with_manifest(fold_booster, model_location)
                    logger.debug(
                        "Stored trained model %d at %s", fold, model_location
                    )

    # end-of-run trace export (SM_TRACE): one Chrome-trace file per rank
    # into SM_TRACE_EXPORT_DIR, defaulting alongside the model artifacts so
    # it travels in the output tarball. Best-effort — a failed export must
    # never fail a finished job.
    from ..telemetry import tracing

    try:
        tracing.export_traces(default_dir=model_dir)
    except Exception:
        logger.exception("trace export failed; training result unaffected")
    # fleet merge rides next to the per-rank exports: every rank flushes its
    # shipper, rank 0 writes trace-fleet.json (inert when the plane is off)
    from ..telemetry import fleet

    try:
        fleet.export_fleet_trace(default_dir=model_dir)
    except Exception:
        logger.exception("fleet trace export failed; training result unaffected")


def _try_parallel_cv(
    train_cfg,
    train_val_dmatrix,
    splits,
    num_round,
    kfold,
    checkpoint_dir,
    early_stopping_rounds,
    configured_feval,
    save_model_on_termination,
):
    """Fold-parallel CV fast path; returns (forests, evals_results) or None.

    The reference runs k x r sequential boosting jobs (algorithm_mode/
    train.py:378-459); here each local device trains whole folds in one
    vmapped XLA program (models/cv_parallel.py) — for single-process CV
    jobs, fold parallelism beats data parallelism (folds are independent, so
    there are zero collectives), so it takes precedence over the data mesh.
    Only taken when no per-fold host artifact is needed mid-training
    (checkpoints, early stopping, SIGTERM intermediate saves, feval) and the
    watchlist is device-decomposable; anything else — including multi-host
    runs — falls back to the sequential loop. ``GRAFT_PARALLEL_CV=0``
    disables (e.g. when a fold's data exceeds one device's memory and the
    data mesh is required).
    """
    import jax

    if os.environ.get("GRAFT_PARALLEL_CV", "1") != "1":
        return None
    if jax.process_count() > 1 or jax.local_device_count() <= 1:
        return None
    if checkpoint_dir or early_stopping_rounds or configured_feval is not None:
        return None
    if save_model_on_termination == "true":
        return None
    from ..models.booster import (
        OBJECTIVE_PARAM_KEYS,
        TrainConfig,
        _eval_metric_names,
    )
    from ..models.cv_parallel import parallel_cv_supported, train_cv_parallel
    from ..models.forest import Forest

    try:
        cfg = TrainConfig(dict(train_cfg))
    except Exception:
        return None  # the sequential path surfaces config errors verbatim

    def forest_factory():
        return Forest(
            objective_name=cfg.objective,
            objective_params={
                k: v
                for k, v in cfg.objective_params.items()
                if k in OBJECTIVE_PARAM_KEYS
            },
            base_score=cfg.base_score,
            num_feature=train_val_dmatrix.num_col,
            num_class=cfg.num_class,
        )

    metric_names = _eval_metric_names(cfg, forest_factory().objective())
    if not parallel_cv_supported(cfg, metric_names, False):
        return None
    logger.info(
        "Training %d CV folds in parallel across %d devices",
        len(splits),
        jax.device_count(),
    )
    forests, evals_results = train_cv_parallel(
        cfg, train_val_dmatrix, splits, num_round, metric_names, forest_factory
    )
    # per-fold per-round stdout lines in the sequential monitor's format
    # (the HPO regex contract — reference metrics.py:23-39)
    for k, res in enumerate(evals_results):
        logger.info("Train cross validation fold %d", (k % kfold) + 1)
        for r in range(num_round):
            parts = [
                "{}-{}:{:.5f}".format(data_name, metric_name, res[data_name][metric_name][r])
                for data_name in res
                for metric_name in res[data_name]
            ]
            print("[{}]\t{}".format(r, "\t".join(parts)), flush=True)
    return forests, evals_results


def print_cv_metric(num_round, evals_results):
    """One stdout line with per-metric CV means (reference train.py:489-500)."""
    report = "[{}]".format(num_round)
    data_names = evals_results[0].keys()
    metric_names = evals_results[0]["train"].keys()
    for metric_name in metric_names:
        for data_name in data_names:
            values = [r[data_name][metric_name][-1] for r in evals_results]
            report += "\t{}-{}:{:.5f}".format(data_name, metric_name, float(np.mean(values)))
    print(report, flush=True)
