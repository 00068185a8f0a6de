"""Cross-rank consensus guard: prove the mesh agrees on the committed trees.

The distributed contract says every rank commits bit-identical trees (the
mesh build is proven equal to the one-device build at test time)
— but nothing *enforced* it at runtime. A diverged rank (flaky HBM bit
flips, a non-deterministic collective on a misbehaving fabric, version skew
after a partial restart) silently trains a forked ensemble: rank 0 saves
its fork, every serving host later loads whichever fork it's handed, and
no log line ever says so.

The :class:`ConsensusGuard` closes that hole. Every ``SM_CONSENSUS_EVERY``
committed rounds, each rank digests its forest's packed-tree bytes
(``utils.integrity.forest_digest`` — the host mirror of the u32-view
identity the bit-identity tests assert on, computed OFF the jitted round
path) and allgathers the hex digests over the cluster framing
(``parallel/distributed.Cluster.synchronize`` on a dedicated port). Any
disagreement:

* emits one ``training.divergence`` record carrying every rank's digest
  (the runbook artifact: the odd digest out names the bad rank),
* counts ``consensus_divergence_total``,
* takes the whole job down with ``EXIT_CONSENSUS_DIVERGENCE`` (81) through
  PR 3's abort machinery — rank 0 broadcasts an abort frame (carrying the
  exit code) to every peer before aborting itself; every other rank saw
  the same allgathered digests and aborts locally. Restart resumes from
  the last digest-verified checkpoint instead of training the fork to
  completion.

Env-gated and inert by default: ``SM_CONSENSUS_EVERY`` unset/0 means no
guard object, no sockets, no digest work. The ``consensus.check`` fault
point lets chaos drills perturb one rank's digest deterministically (the
injectable stand-in for a real memory fault).
"""

import logging

from ..constants import EXIT_CONSENSUS_DIVERGENCE
from ..telemetry import REGISTRY
from ..telemetry.emit import emit_metric
from ..utils.envconfig import env_float, env_int, env_port
from ..utils.faults import fault_point
from ..utils.integrity import forest_digest

logger = logging.getLogger(__name__)

CONSENSUS_EVERY_ENV = "SM_CONSENSUS_EVERY"
CONSENSUS_PORT_ENV = "SM_CONSENSUS_PORT"
CONSENSUS_TIMEOUT_ENV = "SM_CONSENSUS_TIMEOUT_S"

# NOT the rendezvous (9099), heartbeat (9199), or abort (9299) ports: the
# digest allgather must never collide with an in-flight conversation there
DEFAULT_CONSENSUS_PORT = 9399

# membership registered by algorithm_train._pre_exec over the RE-FORMED
# cluster (hosts without data already exited); None until a multi-host job
# registers — single-host jobs never do, and the guard degrades to a local
# digest (trivially consistent, but the fault point stays drillable)
_hosts = None
_current_host = None


def consensus_every():
    return env_int(CONSENSUS_EVERY_ENV, 0, minimum=0)


def consensus_port():
    return env_port(CONSENSUS_PORT_ENV, DEFAULT_CONSENSUS_PORT)


def consensus_timeout_s():
    return env_float(CONSENSUS_TIMEOUT_ENV, 60.0, minimum=0.1, maximum=3600.0)


def register_cluster(hosts, current_host):
    """Record the participating host list for guards built later
    (algorithm_train._pre_exec calls this on every participant)."""
    global _hosts, _current_host
    _hosts = sorted(hosts)
    _current_host = current_host


def _reset_for_tests():
    global _hosts, _current_host
    _hosts = None
    _current_host = None


def cluster_exchange(hosts, current_host, port=None, timeout=None, master_addr=None):
    """-> exchange fn (digest, round) -> rank-ordered digest list.

    One ``Cluster.synchronize`` allgather per consensus check on the
    dedicated consensus port — the same framed-JSON protocol (and the same
    trickle-proof deadlines) as the startup rendezvous, so a wedged peer
    degrades to a logged exchange failure, never a hang. ``master_addr``
    overrides DNS resolution of the master host (loopback drills).
    """
    from ..parallel.distributed import Cluster

    def _exchange(digest, rnd):
        cluster = Cluster(hosts, current_host, port=consensus_port() if port is None else port)
        if master_addr is not None:
            cluster.master_host = master_addr
        # world rides along so a rank whose membership drifted (missed an
        # elastic shrink, resumed at a stale world size) is caught as a
        # membership pathology, not misread as tree divergence
        return cluster.synchronize(
            {"digest": digest, "round": rnd, "world": len(hosts)},
            timeout=consensus_timeout_s() if timeout is None else timeout,
        )

    return _exchange


class ConsensusGuard:
    """Booster-protocol callback: digest + allgather every N rounds.

    ``exchange`` / ``abort_fn`` are injectable for tests and the dryrun
    drill; production wiring (``maybe_consensus_guard``) uses the cluster
    allgather and ``watchdog.coordinate_abort``/``request_abort``.
    """

    def __init__(
        self,
        every,
        hosts=None,
        current_host=None,
        port=None,
        timeout=None,
        master_addr=None,
        exchange=None,
        abort_fn=None,
    ):
        self.every = max(1, int(every))
        self.hosts = sorted(hosts) if hosts else None
        self.current_host = current_host
        self.rank = self.hosts.index(current_host) if self.hosts else 0
        self.world_size = len(self.hosts) if self.hosts else 1
        if exchange is not None:
            self.exchange = exchange
        elif self.world_size > 1:
            self.exchange = cluster_exchange(
                self.hosts, current_host, port=port, timeout=timeout,
                master_addr=master_addr,
            )
        else:
            self.exchange = lambda digest, rnd: [digest]
        self.abort_fn = abort_fn or self._default_abort
        self.checks = 0
        self.divergences = 0

    # ----------------------------------------------------- callback protocol
    def after_iteration(self, model, epoch, evals_log):
        if (epoch + 1) % self.every != 0:
            return False
        # tracer span (SM_TRACE): the digest + allgather as one tree node
        # under the round span — a consensus check stalled on a slow peer
        # is visible in the timeline (and in the flight recorder, since an
        # exit-81 abort leaves this span in_flight)
        from ..telemetry.tracing import trace_span

        with trace_span(
            "consensus.check", attributes={"round": epoch, "rank": self.rank}
        ):
            return self._check(model, epoch)

    def _check(self, model, epoch):
        digest = forest_digest(model)
        try:
            fault_point("consensus.check", round=epoch, rank=self.rank)
        except (OSError, ConnectionError) as e:
            # injected divergence: the drillable stand-in for a real memory
            # fault — this rank claims a perturbed digest
            logger.error(
                "consensus.check fault injected on rank %d: perturbing this "
                "rank's digest (%s)", self.rank, e
            )
            digest = "f" * 8 + digest[8:]
        self.checks += 1
        REGISTRY.counter(
            "consensus_checks_total",
            "Cross-rank committed-tree digest checks performed",
        ).inc()
        try:
            replies = self.exchange(digest, epoch)
        except Exception as e:
            # an unreachable peer here is the abort plane's / watchdog's
            # failure domain, not a divergence verdict — log and keep
            # training rather than abort on a transport blip
            logger.warning(
                "consensus digest exchange failed at round %d (%s); skipping "
                "this check", epoch, e
            )
            return False
        # the cluster exchange returns the full payload dicts so the round
        # can be validated; injected exchanges (tests, the dryrun drill) may
        # return bare digest lists
        if replies and isinstance(replies[0], dict):
            worlds = {int(r.get("world", self.world_size)) for r in replies}
            if worlds != {self.world_size}:
                # membership drift: a rank answering with a different world
                # size missed (or hasn't finished) an elastic membership
                # transition — its forest legitimately differs, so a digest
                # verdict would abort a healthy cluster. Skip; the drifted
                # rank either re-forms (its exchange keeps failing on the
                # wrong host list) or the abort plane takes it down.
                logger.warning(
                    "consensus exchange at round %d mixed world sizes %s "
                    "(this rank: %d); skipping this check as membership "
                    "drift, not divergence", epoch, sorted(worlds), self.world_size,
                )
                return False
            rounds = {int(r.get("round", epoch)) for r in replies}
            if rounds != {epoch}:
                # a check-index misalignment (one rank skipped a timed-out
                # exchange and this allgather mixed two check rounds) is a
                # transport pathology, NOT a divergence verdict: forests
                # from different rounds necessarily differ, and aborting on
                # that would take down a healthy cluster
                logger.warning(
                    "consensus exchange at round %d mixed check rounds %s; "
                    "skipping this check (ranks re-align at the next one)",
                    epoch, sorted(rounds),
                )
                return False
            digests = [r["digest"] for r in replies]
        else:
            digests = list(replies)
        if len(set(digests)) <= 1:
            return False
        self.divergences += 1
        REGISTRY.counter(
            "consensus_divergence_total",
            "Consensus checks that found ranks with diverged committed trees",
        ).inc()
        per_rank = {str(r): d for r, d in enumerate(digests)}
        emit_metric(
            "training.divergence",
            round=epoch,
            rank=self.rank,
            world_size=self.world_size,
            digests=per_rank,
        )
        logger.error(
            "CONSENSUS DIVERGENCE at round %d: committed trees disagree "
            "across ranks (%s) — aborting all ranks with exit code %d",
            epoch,
            ", ".join("rank {}={}".format(r, d[:12]) for r, d in sorted(per_rank.items())),
            EXIT_CONSENSUS_DIVERGENCE,
        )
        self.abort_fn(
            "consensus_divergence",
            EXIT_CONSENSUS_DIVERGENCE,
            round=epoch,
            digests=per_rank,
        )
        return False

    # ------------------------------------------------------------- internals
    def _default_abort(self, reason, exit_code, **fields):
        from . import watchdog

        if self.hosts and self.rank == 0:
            # rank 0 broadcasts the exit code to peers first — every rank
            # saw the same allgathered digests, but a peer that failed its
            # exchange mid-flight still gets taken down
            watchdog.coordinate_abort(
                self.hosts, self.current_host, reason, exit_code=exit_code, **fields
            )
        else:
            watchdog.request_abort(reason, exit_code, **fields)


def maybe_consensus_guard():
    """-> a ConsensusGuard when ``SM_CONSENSUS_EVERY`` > 0, else None.

    Uses the membership ``register_cluster`` recorded (multi-host) or runs
    single-rank (the digest work and fault point still execute, so the
    knob's overhead is measurable anywhere).
    """
    every = consensus_every()
    if every <= 0:
        return None
    return ConsensusGuard(every, hosts=_hosts, current_host=_current_host)
