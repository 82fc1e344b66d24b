"""Elastic multi-host coordination: retried cluster init, barriers, peer loss.

The reference's TF1 session + SimdMeshImpl assume a FIXED device assignment
for the run's lifetime — a single preempted host kills the whole pod job
permanently.  Here multi-host membership is an input the run negotiates
(docs/reliability.md "Multi-host elasticity"):

- :func:`initialize` wraps ``jax.distributed.initialize`` in the existing
  :class:`~homebrewnlp_tpu.reliability.retry.RetryPolicy` — a coordinator
  that is still coming up (the usual fleet-relaunch race) earns exponential
  backoff bounded by ``dist_init_timeout_s`` instead of an instant crash;
  retries count on ``hbnlp_dist_init_retries_total`` and the final join time
  lands on the ``hbnlp_dist_init_seconds`` gauge.
- :func:`barrier` is the barrier-with-timeout primitive over the distributed
  runtime's KV service (single-process: no-op); a peer that never shows up
  surfaces as :class:`BarrierTimeout` (a :class:`PeerLost`) instead of an
  unbounded hang.
- :func:`check_peers` is polled by the train loop every update: the fault
  sites ``peer`` / ``coordinator`` (``peer:die@step10``,
  ``coordinator:drop@step5``) raise :class:`PeerLost` /
  :class:`CoordinatorLost` so the whole detection -> checkpoint ->
  ``EXIT_PEER_LOST`` (87) -> lockstep fleet relaunch story is chaos-testable
  on CPU.  On real clusters the same exception classes wrap barrier
  timeouts and init give-ups — any host observing a peer failure cuts a
  checkpoint and exits 87, and the per-host supervisors
  (tools/supervise.py) relaunch the *fleet* together instead of letting one
  host spin alone against a dead collective.

Rank/coordinator plumbing: config knobs ``dist_coordinator`` /
``dist_num_processes`` / ``dist_process_id`` are overridden by the env vars
``HBNLP_DIST_COORDINATOR`` / ``HBNLP_DIST_NUM_PROCESSES`` /
``HBNLP_DIST_PROCESS_ID`` so ONE config file serves every host — the
supervisor injects the per-host rank into its child's environment.
"""
from __future__ import annotations

import dataclasses
import logging
import os
import time
import typing

from ..obs import spans
from ..obs.registry import REGISTRY, MetricsRegistry
from . import faults
from .retry import RetryPolicy, retry_call

LOG = logging.getLogger("homebrewnlp_tpu.reliability.dist")

ENV_COORDINATOR = "HBNLP_DIST_COORDINATOR"
ENV_NUM_PROCESSES = "HBNLP_DIST_NUM_PROCESSES"
ENV_PROCESS_ID = "HBNLP_DIST_PROCESS_ID"


class DistributedFailure(RuntimeError):
    """A multi-host failure this host detected (peer death, coordinator
    loss, barrier timeout).  The train loop cuts a checkpoint and exits
    ``EXIT_PEER_LOST`` (87) so the supervisor fleet relaunches in lockstep."""


class PeerLost(DistributedFailure):
    """Another host of the fleet died (or never arrived at a barrier)."""


class CoordinatorLost(DistributedFailure):
    """The jax.distributed coordinator is unreachable (init retries
    exhausted, or the connection dropped mid-run)."""


class BarrierTimeout(PeerLost):
    """A fleet barrier expired before every host arrived."""


@dataclasses.dataclass(frozen=True)
class DistSettings:
    """Resolved multi-host settings (env overrides config — see module
    docstring)."""
    coordinator: str
    num_processes: int
    process_id: int
    init_timeout_s: float = 300.0
    init_retries: int = 3
    barrier_timeout_s: float = 60.0

    @property
    def attempt_timeout_s(self) -> int:
        """Per-attempt ``initialization_timeout`` slice of the overall
        deadline: a slow coordinator must not consume the whole budget on
        attempt 1, or the retry counter never engages for exactly the
        failure mode it exists to survive."""
        if not self.init_timeout_s:
            return 300  # jax's own default join timeout
        return max(10, int(self.init_timeout_s / (self.init_retries + 1)))


class _InitCrash(Exception):
    """Non-retryable envelope for an injected ``dist_init:die`` crash (see
    initialize(): bare RuntimeError must stay retryable for real
    XlaRuntimeError init failures)."""

    def __init__(self, crash: BaseException):
        super().__init__(str(crash))
        self.crash = crash


#: module state: initialize() is once-per-process (jax.distributed refuses a
#: second init); tests reset via _reset_for_tests()
_STATE: typing.Dict[str, typing.Any] = {
    "initialized": False, "settings": None, "init_seconds": None}


def settings(cfg=None) -> typing.Optional[DistSettings]:
    """Resolve distributed settings from env (first) and ``cfg`` (second).
    Returns None when no multi-host world is configured (num_processes <= 1
    everywhere) — the single-host path stays byte-identical."""
    coord = os.environ.get(ENV_COORDINATOR,
                           getattr(cfg, "dist_coordinator", "") or "")
    nproc = int(os.environ.get(ENV_NUM_PROCESSES,
                               getattr(cfg, "dist_num_processes", 0) or 0))
    rank = int(os.environ.get(ENV_PROCESS_ID,
                              getattr(cfg, "dist_process_id", 0) or 0))
    if nproc <= 0 or (nproc == 1 and not coord):
        # no world configured; an EXPLICIT coordinator with nproc=1 (the
        # legacy --tpu addr,0,1 single-process pod slice) still initializes
        # so the distributed runtime comes up exactly as it always did
        return None
    if not coord:
        raise ValueError(
            f"dist_num_processes={nproc} but no coordinator address: set "
            f"dist_coordinator (or {ENV_COORDINATOR}) to host:port")
    if not 0 <= rank < nproc:
        raise ValueError(
            f"dist_process_id={rank} out of range for "
            f"dist_num_processes={nproc}")
    return DistSettings(
        coordinator=coord, num_processes=nproc, process_id=rank,
        init_timeout_s=float(getattr(cfg, "dist_init_timeout_s", 300.0)),
        init_retries=int(getattr(cfg, "dist_init_retries", 3)),
        barrier_timeout_s=float(getattr(cfg, "dist_barrier_timeout_s", 60.0)))


def _jax_initialize(s: DistSettings) -> None:
    import jax
    jax.distributed.initialize(
        s.coordinator, num_processes=s.num_processes,
        process_id=s.process_id,
        initialization_timeout=s.attempt_timeout_s)


def initialize(cfg=None, *,
               registry: typing.Optional[MetricsRegistry] = None,
               init_fn: typing.Optional[
                   typing.Callable[[DistSettings], None]] = None,
               sleep: typing.Callable[[float], None] = time.sleep
               ) -> typing.Optional[float]:
    """Join the jax.distributed cluster under the retry policy.

    Returns the join time in seconds, or None when no multi-host world is
    configured.  A coordinator that stays unreachable past the retry budget
    (or ``dist_init_timeout_s``) raises :class:`CoordinatorLost` — the
    caller exits ``EXIT_PEER_LOST`` so the supervisor fleet retries the
    relaunch together rather than crash-looping one host.

    The fault site ``dist_init`` fires inside each attempt, so
    ``dist_init:fail@1`` drills exactly this retry path."""
    s = settings(cfg)
    if s is None:
        return None
    if _STATE["initialized"]:
        # idempotent: main() initializes for every run mode and train()
        # re-checks for direct callers — the second call is expected
        LOG.info("jax.distributed already initialized (rank %d/%d); "
                 "keeping the existing cluster membership",
                 _STATE["settings"].process_id,
                 _STATE["settings"].num_processes)
        return _STATE["init_seconds"]
    reg = REGISTRY if registry is None else registry
    retries = reg.counter(
        "hbnlp_dist_init_retries_total",
        "jax.distributed.initialize attempts retried (coordinator "
        "unreachable or injected dist_init fault)")
    # real jax.distributed failures surface as jaxlib XlaRuntimeError (a
    # RuntimeError), not OSError — a coordinator still coming up after a
    # fleet relaunch MUST earn the backoff, so RuntimeError is retryable
    # here (bounded by attempts + deadline; config typos raise ValueError,
    # which still fails fast)
    policy = RetryPolicy(
        max_attempts=s.init_retries + 1, base_delay_s=1.0, max_delay_s=15.0,
        deadline_s=s.init_timeout_s or None,
        retryable=(OSError, TimeoutError, RuntimeError))

    def _connect() -> None:
        try:
            faults.hit("dist_init")
        except faults.FaultInjectedCrash as e:
            # 'die' is documented NON-retryable, but it subclasses
            # RuntimeError which this policy (rightly) retries for real
            # XlaRuntimeError init failures — smuggle it past the retry
            # loop so the drill kills the process like a real bug would
            raise _InitCrash(e) from e
        (init_fn or _jax_initialize)(s)

    t0 = time.monotonic()
    try:
        retry_call(_connect, site="dist_init", policy=policy, registry=reg,
                   sleep=lambda d: (retries.inc(), sleep(d)))
    except _InitCrash as e:
        raise e.crash
    except policy.retryable as e:
        raise CoordinatorLost(
            f"jax.distributed.initialize({s.coordinator!r}, rank "
            f"{s.process_id}/{s.num_processes}) failed after "
            f"{s.init_retries + 1} attempt(s) / {s.init_timeout_s:.0f}s "
            f"deadline: {e}") from e
    elapsed = time.monotonic() - t0
    _STATE.update(initialized=True, settings=s, init_seconds=elapsed)
    reg.gauge("hbnlp_dist_init_seconds",
              "wall seconds jax.distributed.initialize took to join the "
              "cluster (the elastic-recovery cost of a fleet relaunch)",
              fn=lambda: _STATE["init_seconds"] or 0.0)
    LOG.info("joined distributed cluster as rank %d/%d via %s in %.2fs",
             s.process_id, s.num_processes, s.coordinator, elapsed)
    return elapsed


def active() -> bool:
    return bool(_STATE["initialized"])


def init_seconds() -> typing.Optional[float]:
    return _STATE["init_seconds"]


def barrier(name: str, timeout_s: typing.Optional[float] = None) -> None:
    """Wait until every process reaches the named barrier, bounded by
    ``timeout_s`` (default: the resolved ``dist_barrier_timeout_s``).

    Single-process (or before :func:`initialize`): no-op.  A timeout raises
    :class:`BarrierTimeout` — a missing peer must surface as a peer-lost
    exit (87), never an unbounded hang the watchdog can only observe."""
    import jax
    if jax.process_count() <= 1:
        return
    s = _STATE["settings"]
    if timeout_s is None:
        timeout_s = s.barrier_timeout_s if s is not None else 60.0
    client = None
    try:
        from jax._src import distributed as _jdist
        client = getattr(_jdist.global_state, "client", None)
    except Exception:  # pragma: no cover - jax internals moved
        client = None
    if client is not None and hasattr(client, "wait_at_barrier"):
        try:
            # the span pair is the fleet trace merge's clock reference:
            # every rank LEAVES a barrier at nearly the same true instant,
            # so matching span END times across ranks carry the inter-rank
            # clock offset (obs/fleet.py::estimate_offsets).  Ambient no-op
            # when spans are off — the single-host path pays nothing.
            with spans.span("dist/barrier", barrier=name):
                client.wait_at_barrier(name, int(timeout_s * 1000))
            return
        except Exception as e:
            raise BarrierTimeout(
                f"barrier {name!r} expired after {timeout_s:.0f}s — a peer "
                f"never arrived ({type(e).__name__}: {e})") from e
    # no KV client (unusual toolchain): fall back to the device-level sync,
    # which has no timeout — log so a hang here is attributable
    LOG.warning("distributed runtime exposes no wait_at_barrier; barrier "
                "%r falls back to sync_global_devices (no timeout)", name)
    from jax.experimental import multihost_utils
    with spans.span("dist/barrier", barrier=name):
        multihost_utils.sync_global_devices(name)


def check_peers(step: int) -> None:
    """Poll the distributed fault sites for this global step (train loop,
    once per update; inert without an armed plan).

    ``peer:die@stepN`` raises :class:`PeerLost`; ``coordinator:drop@stepN``
    raises :class:`CoordinatorLost`.  Both are caller-implemented actions
    (like ``grads:nan``): the site is the detection point, the loop's
    reaction — checkpoint, then ``EXIT_PEER_LOST`` — is the code under
    test."""
    for action in faults.take("peer", value=step):
        if action == "die":
            raise PeerLost(f"peer host lost at step {step} (injected)")
        LOG.error("peer fault site: unsupported action %r ignored", action)
    for action in faults.take("coordinator", value=step):
        if action == "drop":
            raise CoordinatorLost(
                f"coordinator connection dropped at step {step} (injected)")
        LOG.error("coordinator fault site: unsupported action %r ignored",
                  action)


#: env kill-switch for the degraded-resume mesh search (the suggestion costs
#: one abstract trace — seconds; "0" skips it)
ENV_MESH_SUGGEST = "HBNLP_MESH_SUGGEST"


def suggest_mesh(cfg, world_size: int, *,
                 device_kind: str = "", traces=None):
    """World-size renegotiation consults the mesh searcher
    (analysis/mesh_search.py): the best DP/SP/PP/TP factorization of
    ``world_size`` devices for this config under its declared structure,
    plus the predicted step-time delta vs the ``axis_sizes`` fallback the
    runtime would otherwise silently build.

    Returns a :class:`~homebrewnlp_tpu.analysis.mesh_search.MeshSuggestion`,
    or None when the search cannot run (declared seq x pipe structure does
    not factor the world, unpriceable device, or ``HBNLP_MESH_SUGGEST=0``)
    — those cases stay operator-assisted, as docs/reliability.md documents
    for coordinator-mode fleets."""
    if os.environ.get(ENV_MESH_SUGGEST, "1") == "0":
        return None
    from ..analysis import mesh_search  # lazy: jax-heavy, resume-path only
    try:
        return mesh_search.suggest(
            cfg, world_size, device_kind=device_kind, traces=traces,
            config_name=os.path.basename(
                str(getattr(cfg, "model_path", "") or "config")))
    except Exception as e:
        LOG.warning("mesh search for world_size=%d unavailable (%s: %s); "
                    "falling back to the folded axis_sizes mesh",
                    world_size, type(e).__name__, e)
        return None


def log_mesh_suggestion(cfg, mesh, n_devices: typing.Optional[int] = None
                        ) -> typing.Optional[typing.Any]:
    """Degraded-resume replacement for the old "axis shrunk" fold warnings:
    log the searcher's chosen mesh and its predicted step-time delta vs the
    mesh actually built.  ``n_devices`` is the AVAILABLE device count (the
    world the searcher factors) — it can exceed ``mesh.size`` when the
    batch-bound data axis dropped devices out of the built mesh.
    Best-effort — never raises, returns the suggestion (or None) so
    callers/tests can inspect it."""
    world = int(n_devices) if n_devices else int(mesh.size)
    try:
        suggestion = suggest_mesh(cfg, world)
    except Exception:  # pragma: no cover - suggest_mesh already guards
        return None
    built = {k: int(v) for k, v in dict(mesh.shape).items()}
    unused = ""
    if world > int(mesh.size):
        unused = (f" ({world - int(mesh.size)} of {world} device(s) left "
                  f"out of the built mesh)")
    if suggestion is None:
        LOG.warning(
            "resuming degraded on %d device(s) (tpu_size=%d) with mesh "
            "%s%s; no searched suggestion available", world,
            int(getattr(cfg, "tpu_size", 1)), built, unused)
        return None
    LOG.warning(
        "resuming degraded on %d device(s) (tpu_size=%d), built mesh %s%s; "
        "%s", world, int(getattr(cfg, "tpu_size", 1)), built, unused,
        suggestion.describe())
    return suggestion


def _reset_for_tests() -> None:
    _STATE.update(initialized=False, settings=None, init_seconds=None)
