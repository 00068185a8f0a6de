"""Spot-safe checkpointing: save/resume + retention with SageMaker markers.

Contract parity with the reference (checkpointing.py:139-453):

* checkpoints are full serialized models named ``xgboost-checkpoint.<iter>``
  in the checkpoint dir; resume picks the highest iteration and training
  continues with ``num_round - iteration`` remaining rounds,
* writes are atomic (tempfile + rename),
* a daemon thread deletes all but the ``max_to_keep`` newest, deferring any
  file SageMaker is mid-upload (``.sagemaker-uploading`` marker present and
  ``.sagemaker-uploaded`` absent),
* ``SaveIntermediateModel`` overwrites ``<model_dir>/<model_name>`` every
  round so SIGTERM (spot interruption / HPO early stop) always leaves a
  fresh model behind.
"""

import json
import logging
import os
import queue
import re
import tempfile
import threading
import time
import weakref

from ..telemetry.spans import span
from ..telemetry.tracing import trace_span
from ..utils import integrity
from ..utils.faults import fault_point
from ..utils.retry import retry_transient

TEMP_FILE_SUFFIX = ".sagemaker-ignore"
FILE_LOCK_SUFFIX = ".sagemaker-uploading"
FILE_SAFE_SUFFIX = ".sagemaker-uploaded"
MANIFEST_SUFFIX = integrity.MANIFEST_SUFFIX

CHECKPOINT_FILENAME = "xgboost-checkpoint"

logger = logging.getLogger(__name__)

# live SaveCheckpointCallBack instances, for the abort path's final flush
# (training/watchdog.request_abort) — weak so a completed training run's
# callback doesn't linger here
_active_savers = weakref.WeakSet()


def _note_verify_fail(reason):
    from ..telemetry import REGISTRY

    REGISTRY.counter(
        "checkpoint_verify_fail_total",
        "Resume candidates rejected by digest or parse validation",
        {"reason": reason},
    ).inc()


def _checkpoint_usable(path):
    """Cheap integrity check for a checkpoint file.

    With a manifest sidecar (every checkpoint since the integrity layer),
    the sha256 digest is the verdict: a match proves the exact saved bytes
    and SHORT-CIRCUITS the full JSON parse (digesting streams the file
    once; parsing a multi-GB model JSON allocates its whole object tree), a
    mismatch rejects the candidate — stronger than the parse, which accepts
    any bit flip that stays inside JSON syntax.

    Manifest-less checkpoints (older runs) keep the parse fallback:
    checkpoints are full serialized models (forest/gblinear both emit JSON;
    the ``.ubj`` branch only triggers on an explicit suffix, which the
    extension-less ``xgboost-checkpoint.<iter>`` names never carry). A file
    killed mid-write — crash between temp-create and rename shouldn't leave
    one, but an interrupted upload-restore or disk-full truncation can — is
    empty or cuts off mid-JSON; both fail the parse.
    """
    try:
        if os.path.getsize(path) == 0:
            return False
    except OSError:
        return False
    manifest = integrity.read_manifest(path)
    if manifest is not None:
        try:
            integrity.verify_file_against_manifest(path, manifest)
            return True
        except integrity.IntegrityError as e:
            logger.warning("checkpoint digest verification failed: %s", e)
            _note_verify_fail("digest")
            return False
        except OSError:
            _note_verify_fail("io")
            return False
    try:
        with open(path, "rb") as f:
            json.loads(f.read().decode("utf-8"))
        return True
    except OSError:
        _note_verify_fail("io")
        return False
    except (ValueError, UnicodeDecodeError):
        _note_verify_fail("parse")
        return False


def load_checkpoint(checkpoint_dir):
    """-> (model path or None, next iteration number).

    Picks the highest-iteration checkpoint that actually *verifies* — the
    manifest digest where a sidecar exists, the JSON parse otherwise
    (``_checkpoint_usable``). A corrupt/partial/bit-flipped file is skipped
    with a warning and the next-highest takes over, so one bad file can't
    turn a resumable job into a from-scratch retrain or a crash loop. Also
    sweeps orphaned ``.sagemaker-ignore`` temp files left by a crash
    mid-``_atomic_save`` and orphaned ``.manifest`` sidecars whose
    checkpoint is gone (retention deleted it, or the pair was half-restored).
    """
    if not checkpoint_dir or not os.path.exists(checkpoint_dir):
        return None, 0
    pattern = re.compile(r"^{}\.([0-9]+)$".format(re.escape(CHECKPOINT_FILENAME)))
    found = []
    names = set(os.listdir(checkpoint_dir))
    for name in sorted(names):
        if name.endswith(TEMP_FILE_SUFFIX):
            try:
                os.remove(os.path.join(checkpoint_dir, name))
                logger.info("removed orphaned checkpoint temp file %s", name)
            except OSError:
                logger.debug("could not remove orphaned temp file %s", name)
            continue
        if name.endswith(MANIFEST_SUFFIX):
            if name[: -len(MANIFEST_SUFFIX)] not in names:
                try:
                    os.remove(os.path.join(checkpoint_dir, name))
                    logger.info("removed orphaned checkpoint manifest %s", name)
                except OSError:
                    logger.debug("could not remove orphaned manifest %s", name)
            continue
        m = pattern.match(name)
        if m:
            found.append((int(m.group(1)), name))
    for iteration, name in sorted(found, reverse=True):
        path = os.path.join(checkpoint_dir, name)
        if _checkpoint_usable(path):
            return path, iteration + 1
        logger.warning(
            "checkpoint %s is corrupt or partial; falling back to the "
            "next-highest iteration", name
        )
    return None, 0


def _atomic_save(
    model, directory, final_name, iteration=None, fingerprint=None, membership_log=None
):
    """tempfile + rename, with bounded transient-IO retries. Each attempt
    uses a fresh temp file and cleans up its own debris on failure, so a
    retried save can't leak ``.sagemaker-ignore`` orphans.

    With ``iteration``/``fingerprint`` (checkpoint saves), a manifest
    sidecar (``<final_name>.manifest``: sha256 + byte count + iteration +
    config fingerprint) is written after the model with the same
    atomic-retried semantics. The digest is taken from the temp file BEFORE
    the rename — it describes the exact bytes that became the checkpoint,
    not a re-read that could race a concurrent restore. Order matters:
    model first, manifest second, so a crash in between leaves a
    manifest-less checkpoint (degrades to the parse fallback) rather than a
    manifest describing a file that doesn't exist.

    Without them (the per-round intermediate model overwrite), NO manifest
    is written — a SIGTERM can land between the two renames on any round,
    and a sidecar describing the previous round's bytes would make serving
    reject the perfectly fresh model the spot-interruption contract just
    saved. Instead any stale sidecar for the name (e.g. the final-model
    manifest of a previous completed run in the same model_dir) is removed,
    keeping the invariant: a manifest, when present, describes the current
    bytes.
    """
    digest_box = {}
    want_manifest = iteration is not None or fingerprint is not None

    def _attempt():
        fault_point("checkpoint.save", path=final_name)
        with tempfile.NamedTemporaryFile(
            dir=directory, suffix=TEMP_FILE_SUFFIX, delete=False, mode="w"
        ) as tf:
            tmp = tf.name
        try:
            model.save_model(tmp)
            if want_manifest:
                digest_box["sha256"] = integrity.file_digest(tmp)
                digest_box["bytes"] = os.path.getsize(tmp)
                # re-saving an existing name (resume re-writes a rejected
                # iteration): drop the old sidecar BEFORE the rename, so a
                # crash in the rename->manifest window leaves new bytes
                # manifest-less (parse fallback) rather than new bytes +
                # a stale manifest that would verify-fail a good checkpoint
                try:
                    os.remove(
                        os.path.join(directory, final_name + MANIFEST_SUFFIX)
                    )
                except OSError:
                    pass
            os.rename(tmp, os.path.join(directory, final_name))
        except BaseException:
            try:
                os.remove(tmp)
            except OSError:
                pass
            raise

    # `checkpoint.write`: the write itself (serialise, digest, rename, with
    # its retries), on whichever thread runs it; the manifest's span follows.
    # Under SM_TRACE both nest below the callback's `checkpoint.save`, so a
    # slow storage volume shows up as a fat checkpoint.write in the timeline
    # (`covering`: the round record's `checkpoint` phase already holds it)
    with span("checkpoint.write", covering=True, attributes={"file": final_name}):
        retry_transient(_attempt, site="checkpoint.save")
    if not want_manifest:
        try:
            os.remove(os.path.join(directory, final_name + MANIFEST_SUFFIX))
        except OSError:
            pass
        return
    manifest = integrity.build_manifest(
        os.path.join(directory, final_name),
        iteration=iteration,
        fingerprint=fingerprint,
        digest=digest_box["sha256"],
        size=digest_box["bytes"],
        membership_log=membership_log,
    )
    _atomic_write_manifest(directory, final_name + MANIFEST_SUFFIX, manifest)


def _atomic_write_manifest(directory, manifest_name, manifest):
    """Write the manifest sidecar: tempfile + rename under ``retry_transient``
    with per-attempt temp cleanup — the same durability contract as the
    model write it describes (a manifest that can be torn by a crash would
    reject the healthy checkpoint it sits next to)."""

    def _attempt():
        fault_point("checkpoint.manifest", path=manifest_name)
        with tempfile.NamedTemporaryFile(
            dir=directory, suffix=TEMP_FILE_SUFFIX, delete=False, mode="w"
        ) as tf:
            tmp = tf.name
        integrity.dump_manifest_atomic(
            os.path.join(directory, manifest_name), manifest, tmp
        )

    with trace_span("checkpoint.manifest", attributes={"file": manifest_name}):
        retry_transient(_attempt, site="checkpoint.manifest")


def active_checkpoint_dirs():
    """Checkpoint dirs of live savers. The abort path writes its
    flight-recorder dump here when no explicit trace dir is configured:
    the checkpoint channel is uploaded/preserved by the platform, so the
    post-mortem survives the container."""
    return [s.checkpoint_dir for s in list(_active_savers) if s.checkpoint_dir]


def flush_checkpoints(timeout=10.0):
    """Abort-path flush: drain every live checkpoint deleter queue so the
    newest checkpoint files are settled on disk before the process exits
    (the per-round saves themselves are synchronous — the last completed
    round is already durable; this stops the background machinery cleanly).
    The join is bounded: when the deleter itself is wedged on the hung
    storage that triggered the abort, the exit must still happen.
    """
    for saver in list(_active_savers):
        try:
            saver.stop(timeout=timeout)
        except Exception:
            logger.exception("checkpoint flush failed for %r", saver)


class SaveCheckpointCallBack:
    """Save a checkpoint each round; background-delete stale ones."""

    SENTINEL = None

    def __init__(
        self,
        checkpoint_dir,
        start_iteration=0,
        max_to_keep=5,
        num_round=None,
        fingerprint=None,
        membership_provider=None,
    ):
        self.checkpoint_dir = checkpoint_dir
        self.max_to_keep = max_to_keep
        self.start_iteration = start_iteration
        self.num_round = num_round
        # config fingerprint stamped into every manifest sidecar; the resume
        # validator (utils/integrity.validate_resume) compares it on restart
        self.fingerprint = fingerprint
        # elastic membership: a zero-arg callable returning the current
        # transition log — called per save (not captured once) so a shrink
        # mid-generation lands in the very next sidecar
        self.membership_provider = membership_provider
        os.makedirs(checkpoint_dir, exist_ok=True)
        self.previous_checkpoints = {
            os.path.join(checkpoint_dir, f) for f in os.listdir(checkpoint_dir)
        }
        self.delete_queue = queue.Queue()
        self._start_deleter()
        _active_savers.add(self)

    def format_path(self, iteration):
        return os.path.join(
            self.checkpoint_dir, "{}.{}".format(CHECKPOINT_FILENAME, iteration)
        )

    def after_iteration(self, model, epoch, evals_log):
        # `checkpoint.save`: all the training thread spends on a round's
        # checkpoint (write, manifest, retention hand-off, status note)
        with span("checkpoint.save", covering=True, attributes={"round": epoch}):
            _atomic_save(
                model,
                self.checkpoint_dir,
                "{}.{}".format(CHECKPOINT_FILENAME, epoch),
                iteration=epoch,
                fingerprint=self.fingerprint,
                membership_log=(
                    self.membership_provider() if self.membership_provider else None
                ),
            )
            self.delete_queue.put(epoch - self.max_to_keep)
            # /status carries the last durably-saved checkpoint: the resume
            # point an operator would restart from if they killed the job now
            from ..telemetry import fleet

            fleet.note_status(
                last_checkpoint={"path": self.format_path(epoch), "round": epoch}
            )
            if self.num_round is not None and epoch + 1 >= self.num_round:
                self.stop()
        return False

    def after_training(self, model):
        self.stop()
        return model

    # ------------------------------------------------------------- deleter
    def _start_deleter(self):
        def _is_uploading(path):
            return os.path.isfile(path + FILE_LOCK_SUFFIX) and not os.path.isfile(
                path + FILE_SAFE_SUFFIX
            )

        def _remove(path):
            try:
                try:
                    os.remove(path)
                except OSError:
                    # checkpoint survived the delete (EACCES, upload-lock
                    # race): its sidecar must survive too — stripping the
                    # manifest from a live checkpoint would downgrade a later
                    # resume to the parse fallback, losing bit-rot detection.
                    # load_checkpoint sweeps the sidecar once the checkpoint
                    # is truly gone.
                    logger.debug("Failed to delete %s", path)
                else:
                    # the sidecar lives and dies with its checkpoint:
                    # retention must never leak one (a stale manifest next to
                    # a later re-used name would reject a good file)
                    try:
                        os.remove(path + MANIFEST_SUFFIX)
                    except OSError:
                        pass
            finally:
                self.delete_queue.task_done()

        def _drain():
            for iteration in iter(self.delete_queue.get, self.SENTINEL):
                path = self.format_path(iteration)
                if not os.path.isfile(path) or path in self.previous_checkpoints:
                    self.delete_queue.task_done()
                    continue
                if _is_uploading(path):
                    # SageMaker still uploading: requeue and revisit later
                    # (sleep so a lone stuck item doesn't busy-spin a core)
                    time.sleep(0.5)
                    self.delete_queue.put(iteration)
                    continue
                _remove(path)
            self.delete_queue.task_done()
            # training over: second pass removes stragglers regardless of locks
            self.delete_queue.put(self.SENTINEL)
            for iteration in iter(self.delete_queue.get, self.SENTINEL):
                _remove(self.format_path(iteration))
            self.delete_queue.task_done()

        self.thread = threading.Thread(target=_drain, daemon=True)
        self.thread.start()

    def stop(self, timeout=None):
        """Drain and join the deleter. ``timeout`` bounds the join for the
        abort path — a deleter wedged on hung storage must not keep the
        process from its exit (normal end-of-training keeps the full
        blocking drain)."""
        if self.thread.is_alive():
            self.delete_queue.put(self.SENTINEL)
            self.thread.join(timeout)


class SaveIntermediateModelCallBack:
    """Overwrite ``model_dir/model_name`` after every round (master only)."""

    def __init__(self, intermediate_model_dir, model_name, is_master):
        self.intermediate_model_dir = intermediate_model_dir
        self.model_name = model_name
        self.is_master = is_master
        os.makedirs(intermediate_model_dir, exist_ok=True)

    def after_iteration(self, model, epoch, evals_log):
        if self.is_master:
            _atomic_save(model, self.intermediate_model_dir, self.model_name)
        return False
