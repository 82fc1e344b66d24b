#!/usr/bin/env python
"""Serving fleet supervisor: N engine replicas behind the health-aware router.

The serving twin of ``tools/supervise.py`` (docs/reliability.md "Serving
resilience"): spawn N ``main.py --run_mode web_api`` replicas on adjacent
ports, run the replica router (serve/router.py) in-process in front of
them, and keep the set alive:

- **spawn** — replica i serves on ``--base-port + i`` with its /healthz
  exporter on ``--base-obs-port + i``; the router health-gates on the
  latter.  On a TPU host every replica is pinned to its own chip
  (``replica_chip_envs``), and more replicas than chips is refused at
  start.  ``--fault-plan i:PLAN`` injects a chaos plan
  (``HBNLP_FAULT_PLAN``, reliability/faults.py) into exactly one replica —
  how the chaos-serve drill kills replica 0 mid-run.
- **health-watch + relaunch** — a dead replica (child exit) relaunches
  with exponential backoff (reliability/retry.py's RetryPolicy supplies
  the schedule); a shared ``serve_aot_cache_dir`` in the config makes the
  relaunch warm (AOT deserialization instead of recompilation).
  Optionally (``--unhealthy-restart-s``) a replica whose healthz stays
  unreachable that long is SIGTERMed so the same relaunch path recovers a
  wedged-but-alive process.
- **postings** — each replica slot posts exits/readiness/tombstones into
  ``--fleet-dir`` through supervise.py's FleetCoordinator scheme, so fleet
  tooling sees serving replicas exactly like training ranks.
- **drain** — SIGTERM drains the router (stop admitting, finish in-flight
  bounded by ``--grace-deadline-s``), then SIGTERMs every replica (their
  own grace drain), bounded-waits, SIGKILLs stragglers, tombstones, exits.

Stdlib-only, loadable on a broken jax install (the children pay for jax;
the supervisor must outlive exactly their failures).

Usage:
  python tools/graftserve.py --model configs/serve.json --replicas 2 \\
      --router-port 8080
"""
from __future__ import annotations

import argparse
import importlib.util
import logging
import os
import signal
import sys
import threading
import time
import typing
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _load_light(name: str, relpath: str):
    """Load a stdlib-only module by FILE PATH, bypassing the package
    __init__ (which imports jax via config.py) — supervise.py house
    style."""
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, relpath))
    mod = importlib.util.module_from_spec(spec)
    # registered BEFORE exec: dataclass-bearing modules (retry.py) look
    # themselves up through sys.modules while their class bodies execute
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


# load order matters: sync first (the lock recorder), then the registry,
# then the modules that find both through sys.modules
_sync = _load_light("hbnlp_sync", "homebrewnlp_tpu/sync.py")
sys.modules.setdefault("hbnlp_sync", _sync)
make_lock = _sync.make_lock

_registry = _load_light("hbnlp_obs_registry",
                        "homebrewnlp_tpu/obs/registry.py")
sys.modules.setdefault("hbnlp_obs_registry", _registry)
REGISTRY = _registry.REGISTRY

_supervise = _load_light("hbnlp_supervise", "tools/supervise.py")
FleetCoordinator = _supervise.FleetCoordinator
SubprocessLauncher = _supervise.SubprocessLauncher

_retry = _load_light("hbnlp_retry",
                     "homebrewnlp_tpu/reliability/retry.py")
RetryPolicy = _retry.RetryPolicy

# the usage meter before the router: router.status() federates the
# replicas' per-tenant usage blocks through obs/usage.py::merge_usage and
# finds the module through sys.modules when loaded by file path
_usage = _load_light("hbnlp_obs_usage", "homebrewnlp_tpu/obs/usage.py")

router_mod = _load_light("hbnlp_router", "homebrewnlp_tpu/serve/router.py")

LOG = logging.getLogger("homebrewnlp_tpu.graftserve")


class ReplicaSupervisor:
    """One replica slot: spawn, watch, relaunch with backoff, drain.

    Runs on its own thread; ``stop()`` (the drain path) SIGTERMs the child
    — the replica's web_api handler turns that into its own graceful
    drain — and ends the relaunch loop."""

    def __init__(self, index: int, cmd: typing.Sequence[str],
                 env: dict, obs_url: str,
                 fleet: typing.Optional[FleetCoordinator] = None,
                 policy: typing.Optional[RetryPolicy] = None,
                 unhealthy_restart_s: float = 0.0,
                 registry=None):
        self.index = index
        self.obs_url = obs_url.rstrip("/")
        self.launcher = SubprocessLauncher(list(cmd), env=dict(env))
        self.fleet = fleet
        self.policy = policy if policy is not None else RetryPolicy(
            max_attempts=1_000_000, base_delay_s=0.5, max_delay_s=30.0)
        self.unhealthy_restart_s = float(unhealthy_restart_s)
        reg = registry if registry is not None else REGISTRY
        self._relaunches = reg.counter(
            "hbnlp_graftserve_relaunches_total",
            "replica relaunches by slot", labelnames=("replica",))
        self._stop = threading.Event()
        self.thread = threading.Thread(target=self._run, daemon=True,
                                       name=f"replica-sup-{index}")

    def start(self) -> None:
        self.thread.start()

    def stop(self) -> None:
        """Begin the slot's shutdown: no more relaunches, SIGTERM the
        child (its own grace drain runs).  Join with :meth:`wait`."""
        self._stop.set()
        self.launcher.terminate()

    def kill(self) -> None:
        """Straggler escalation after the drain window: SIGKILL."""
        with self.launcher._lock:
            p = self.launcher._proc
        if p is not None and p.poll() is None:
            try:
                p.kill()
            except OSError:
                pass

    def wait(self, timeout_s: float) -> bool:
        self.thread.join(timeout=timeout_s)
        return not self.thread.is_alive()

    def _watch_health(self, stop: threading.Event) -> None:
        """Wedged-process recovery: when healthz (including a 503 from a
        stalled decode loop, or a wedged snapshot's timeout) has answered
        nothing but errors for ``unhealthy_restart_s`` straight, SIGTERM
        the child so the relaunch loop recovers it."""
        last_ok = time.monotonic()
        while not stop.wait(1.0):
            try:
                with urllib.request.urlopen(self.obs_url + "/healthz",
                                            timeout=2.0):
                    last_ok = time.monotonic()
                    continue
            except Exception:  # noqa: BLE001 - any failure counts
                pass
            if time.monotonic() - last_ok >= self.unhealthy_restart_s:
                LOG.warning("replica %d healthz dead for %.0fs; SIGTERM "
                            "for relaunch", self.index,
                            self.unhealthy_restart_s)
                self.launcher.terminate()
                return

    def _run(self) -> None:
        attempt = 0
        while not self._stop.is_set():
            t0 = time.monotonic()
            if self.fleet is not None:
                self.fleet.post_ready(0)
            hstop = threading.Event()
            hthread = None
            if self.unhealthy_restart_s:
                hthread = threading.Thread(
                    target=self._watch_health, args=(hstop,), daemon=True,
                    name=f"replica-health-{self.index}")
                hthread.start()
            rc = self.launcher()
            hstop.set()
            if self.fleet is not None:
                self.fleet.post_exit(rc)
                self.fleet.advance()
            if self._stop.is_set():
                LOG.info("replica %d exited rc=%d during drain", self.index,
                         rc)
                return
            # long-lived children reset the backoff schedule: only rapid
            # death loops climb the exponential
            if time.monotonic() - t0 > 60.0:
                attempt = 0
            delay = self.policy.delay(attempt)
            attempt += 1
            self._relaunches.labels(replica=f"replica{self.index}").inc()
            LOG.warning("replica %d died rc=%d; relaunching in %.1fs "
                        "(warm via the shared AOT cache)", self.index, rc,
                        delay)
            if self._stop.wait(delay):
                return


def host_tpu_chips() -> int:
    """TPU chips on this host, counted WITHOUT jax — the supervisor must
    never hold a chip, and a process that touched JAX does.  The installed
    runtime exposes one ``/dev/vfio/<n>`` device per chip (one entry on the
    one-chip v5e machine, ``0``..``3`` on the 2x2 host, where two children
    pinned this way each opened their own chip at once: chip runs of
    PR 21)."""
    try:
        return sum(1 for name in os.listdir("/dev/vfio") if name.isdigit())
    except OSError:
        return 0


def replica_chip_envs(n_replicas: int, environ: typing.Mapping[str, str],
                      n_chips: typing.Optional[int] = None
                      ) -> typing.List[typing.Dict[str, str]]:
    """Per-replica environment additions that give every replica its OWN
    chip.  A chip belongs to one process at a time, so N children started
    with the parent's environment all reach for the same device: the first
    wins and the relaunch loop retries the rest for ever.  Each replica is
    pinned to one chip through libtpu's own variables; when the host has
    fewer chips than replicas the fleet refuses to start.  On the CPU
    platform (``JAX_PLATFORMS=cpu``, or no chip on the host) replicas share
    the host and nothing is pinned."""
    platforms = environ.get("JAX_PLATFORMS", "")
    chips = host_tpu_chips() if n_chips is None else n_chips
    if platforms.split(",")[0] == "cpu" or chips == 0:
        return [{} for _ in range(n_replicas)]
    if n_replicas > chips:
        raise SystemExit(
            f"graftserve: {n_replicas} replicas need {n_replicas} chips, "
            f"this host has {chips} — one replica per chip (a second "
            f"process cannot open a chip the first one holds)")
    return [{"TPU_VISIBLE_CHIPS": str(i),
             "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
             "TPU_PROCESS_BOUNDS": "1,1,1"} for i in range(n_replicas)]


def build_replica_cmd(cfg_path: str, port: int, obs_port: int
                      ) -> typing.List[str]:
    return [sys.executable, os.path.join(REPO, "main.py"),
            "--model", cfg_path, "--run_mode", "web_api",
            "--port", str(port), "--obs_port", str(obs_port)]


def parse_args(argv=None):
    p = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        usage="graftserve.py --model CFG [options]")
    p.add_argument("--model", required=True, help="JSON config path")
    p.add_argument("--replicas", type=int, default=2)
    p.add_argument("--base-port", type=int, default=8100,
                   help="replica i serves on base-port + i")
    p.add_argument("--base-obs-port", type=int, default=9100,
                   help="replica i's /healthz exporter on base-obs-port + i")
    p.add_argument("--router-port", type=int, default=8080)
    p.add_argument("--router-host", type=str, default="127.0.0.1")
    p.add_argument("--health-interval-s", type=float, default=0.5)
    p.add_argument("--health-timeout-s", type=float, default=2.0)
    p.add_argument("--failover-retries", type=int, default=1)
    p.add_argument("--grace-deadline-s", type=float, default=30.0)
    p.add_argument("--backoff-base", type=float, default=0.5,
                   help="seconds before the first relaunch (doubles up to "
                        "--backoff-max; long-lived children reset it)")
    p.add_argument("--backoff-max", type=float, default=30.0)
    p.add_argument("--unhealthy-restart-s", type=float, default=0.0,
                   help=">0: SIGTERM a replica whose healthz has been "
                        "unreachable this long (wedged-process recovery); "
                        "0 disables")
    p.add_argument("--fleet-dir", type=str, default="",
                   help="shared dir for FleetCoordinator postings (exit/"
                        "ready/tombstone per replica slot); empty disables")
    p.add_argument("--fault-plan", action="append", default=[],
                   metavar="INDEX:PLAN",
                   help="inject a chaos plan (HBNLP_FAULT_PLAN) into one "
                        "replica, e.g. '0:replica:die@req5'; repeatable")
    return p.parse_args(argv)


def main(argv=None) -> int:
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s graftserve %(levelname)s %(message)s")
    args = parse_args(argv)
    plans: typing.Dict[int, str] = {}
    for spec in args.fault_plan:
        idx, _, plan = spec.partition(":")
        plans[int(idx)] = plan
    policy = RetryPolicy(max_attempts=1_000_000,
                         base_delay_s=args.backoff_base,
                         max_delay_s=args.backoff_max)
    replicas = []
    sups: typing.List[ReplicaSupervisor] = []
    chip_envs = replica_chip_envs(args.replicas, os.environ)
    for i in range(args.replicas):
        port = args.base_port + i
        obs_port = args.base_obs_port + i
        url = f"http://127.0.0.1:{port}"
        obs_url = f"http://127.0.0.1:{obs_port}"
        replicas.append(router_mod.Replica(url, obs_url,
                                           name=f"replica{i}"))
        env = {**os.environ, **chip_envs[i]}
        if i in plans:
            env["HBNLP_FAULT_PLAN"] = plans[i]
        fleet = (FleetCoordinator(args.fleet_dir, rank=i,
                                  world_size=args.replicas)
                 if args.fleet_dir else None)
        sups.append(ReplicaSupervisor(
            i, build_replica_cmd(args.model, port, obs_port), env, obs_url,
            fleet=fleet, policy=policy,
            unhealthy_restart_s=args.unhealthy_restart_s))
    router = router_mod.Router(
        replicas, health_interval_s=args.health_interval_s,
        health_timeout_s=args.health_timeout_s,
        failover_retries=args.failover_retries)
    server = router_mod.serve_router(router, host=args.router_host,
                                     port=args.router_port, background=True)
    LOG.info("router on %s:%d over %d replica(s); replica ports %d..%d "
             "(obs %d..%d)", args.router_host, server.server_address[1],
             args.replicas, args.base_port,
             args.base_port + args.replicas - 1, args.base_obs_port,
             args.base_obs_port + args.replicas - 1)
    for sup in sups:
        sup.start()
    done = threading.Event()

    def _drain_all():
        # drain order matters: router first (stop admitting, finish
        # relaying in-flight), THEN the replicas' own grace drains — the
        # reverse would 503 streams the router still carries
        LOG.info("drain: router stops admitting (grace %.0fs)",
                 args.grace_deadline_s)
        server.drain(args.grace_deadline_s)
        for sup in sups:
            sup.stop()
        deadline = time.monotonic() + args.grace_deadline_s
        for sup in sups:
            sup.wait(max(0.1, deadline - time.monotonic()))
        for sup in sups:
            if not sup.wait(0.0):
                LOG.warning("replica %d ignored SIGTERM; SIGKILL",
                            sup.index)
                sup.kill()
                sup.wait(5.0)
            if sup.fleet is not None:
                sup.fleet.post_final(0)
        done.set()

    def _on_signal(signum, frame):
        threading.Thread(target=_drain_all, daemon=True,
                         name="graftserve-drain").start()

    signal.signal(signal.SIGTERM, _on_signal)
    signal.signal(signal.SIGINT, _on_signal)
    while not done.wait(timeout=1.0):
        pass
    server.server_close()
    LOG.info("graftserve: drained and stopped")
    return 0


if __name__ == "__main__":
    sys.exit(main())
