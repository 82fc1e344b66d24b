"""Live health surface: /metrics + /healthz HTTP server and hang watchdog.

``start_server`` runs a stdlib ``ThreadingHTTPServer`` on a daemon thread
(zero deps — same choice as ``serve/rest.py``) exposing:

- ``GET /metrics``  — the registry in Prometheus text format (0.0.4)
- ``GET /healthz``  — JSON: last-completed-step, EMA step time, seconds
  since the last step, feeder liveness; HTTP 200 while healthy, 503 once
  the run looks stalled (so a k8s-style probe can act on it)

``Watchdog`` is the opaque-death insurance: a daemon thread that, when no
step completes within ``factor`` x the EMA step time, dumps every Python
thread's stack plus per-device ``memory_stats()`` to
``<model_path>/diagnostics/hang_*.txt`` — the two artifacts a post-mortem
of a wedged run actually needs (which actor is blocked, and whether HBM
crept).  It fires once per stall and re-arms when steps resume; it never
kills the run.
"""
from __future__ import annotations

import json
import logging
import os
import sys
import threading
import time
import traceback
import typing
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from . import compile_log, spans
from .registry import REGISTRY, MetricsRegistry
from ..sync import make_lock

LOG = logging.getLogger("homebrewnlp_tpu.obs")


class Health:
    """Thread-safe record of run liveness, shared by /healthz + watchdog.

    ``step_completed`` is called from the metric drain (a step counts as
    completed when its metrics materialized — the async loop's definition
    of done); the EMA step time smooths over checkpoint pauses."""

    def __init__(self, stall_factor: float = 10.0, ema_alpha: float = 0.2,
                 min_stall_s: float = 5.0, max_pause_s: float = 600.0,
                 startup_stall_s: float = 600.0):
        """``min_stall_s`` floors the stall threshold: sub-millisecond CPU
        steps must not flip /healthz to 503.  ``max_pause_s`` bounds a
        declared pause — a checkpoint save hung past it reads as stalled
        again (and the watchdog dumps), otherwise a wedged save would hide
        behind its own pause forever.  ``startup_stall_s`` is the generous
        absolute bound used BEFORE a step cadence exists (compiling /
        restoring / first step): a run wedged in startup — the classic
        opaque death — still reads as stalled after it.  Health owns the
        threshold (``stall_threshold``); /healthz and the Watchdog both
        consult it, so the two consumers of the liveness signal cannot
        disagree."""
        self._lock = make_lock("obs.exporter.Health._lock")
        self.stall_factor = float(stall_factor) if stall_factor else 10.0
        self.ema_alpha = ema_alpha
        self.min_stall_s = float(min_stall_s)
        self.max_pause_s = float(max_pause_s)
        self.startup_stall_s = float(startup_stall_s)
        self.started = time.time()
        self._last_step: typing.Optional[int] = None
        self._last_wall: typing.Optional[float] = None
        self._last_dispatch: typing.Optional[float] = None
        self._ema_step_s: typing.Optional[float] = None
        self._done = False
        self._paused_for: typing.Optional[str] = None
        self._pause_wall = 0.0
        self._feeder_probe: typing.Optional[typing.Callable[[], bool]] = None
        self._util_probe: typing.Optional[
            typing.Callable[[], typing.Dict[str, float]]] = None

    def step_completed(self, step: int,
                       dispatch_wall: typing.Optional[float] = None) -> None:
        """``dispatch_wall``: when the step was DISPATCHED.  The EMA must
        measure the training cadence from dispatch spacing — a checkpoint
        or profiler ``flush()`` drains the whole in-flight window
        back-to-back, and those near-zero drain gaps would collapse the
        EMA (and with it the stall threshold) if completion times were
        used.  Stall detection itself keys on real completion time."""
        now = time.time()
        t = dispatch_wall if dispatch_wall is not None else now
        with self._lock:
            if self._last_dispatch is not None:
                dt = t - self._last_dispatch
                if dt > 0:
                    self._ema_step_s = (
                        dt if self._ema_step_s is None else
                        self.ema_alpha * dt
                        + (1 - self.ema_alpha) * self._ema_step_s)
            self._last_dispatch = t
            self._last_step = int(step)
            self._last_wall = now

    def set_feeder_probe(self, fn: typing.Callable[[], bool]) -> None:
        with self._lock:
            self._feeder_probe = fn

    def set_utilization_probe(
            self, fn: typing.Callable[[], typing.Dict[str, float]]) -> None:
        """Render-time utilization callback (mfu / tokens_per_sec / goodput,
        wired by ``Obs.watch_utilization``): /healthz carries the same
        figures a dashboard scrapes from /metrics, so a human curl answers
        'is it alive AND is it fast' in one request."""
        with self._lock:
            self._util_probe = fn

    def begin_pause(self, reason: str) -> None:
        """Declare an expected no-steps window (checkpoint save): /healthz
        stays healthy and the watchdog holds fire until ``end_pause`` —
        bounded by ``max_pause_s`` (a save hung past it is a stall)."""
        with self._lock:
            self._paused_for = reason
            self._pause_wall = time.time()

    def end_pause(self) -> None:
        """End the declared pause and restart the stall clock — the paused
        interval must not count toward the next stall measurement, NOR
        toward the next dispatch-spacing EMA sample (shifting
        ``_last_dispatch`` forward by the pause excludes it, so a 60s save
        cannot inflate the stall threshold)."""
        with self._lock:
            pause_dur = (time.time() - self._pause_wall
                         if self._paused_for is not None else 0.0)
            self._paused_for = None
            if self._last_wall is not None:
                self._last_wall = time.time()
            if self._last_dispatch is not None:
                self._last_dispatch += pause_dur

    def paused_for(self) -> typing.Optional[str]:
        with self._lock:
            return self._paused_for

    def paused_seconds(self) -> typing.Optional[float]:
        with self._lock:
            if self._paused_for is None:
                return None
            return time.time() - self._pause_wall

    def stall_threshold(self) -> typing.Optional[float]:
        """Seconds without a completed step that count as a stall; None
        before any step spacing is known.  The ONE definition both
        /healthz and the Watchdog use."""
        ema = self.ema_step_seconds()
        if ema is None or ema <= 0:
            return None
        return max(self.stall_factor * ema, self.min_stall_s)

    def stalled(self) -> bool:
        """True when the run looks wedged: past the stall threshold with no
        declared pause, inside a pause that exceeded ``max_pause_s``, or —
        before any cadence exists — past the absolute ``startup_stall_s``
        bound (so a compile/restore/first-step hang is not invisible)."""
        paused_s = self.paused_seconds()
        if paused_s is not None:
            return paused_s > self.max_pause_s
        t = self.stall_threshold()
        since = self.seconds_since_last_step()
        if t is not None and since is not None:
            return since > t
        if self.startup_stall_s <= 0:
            return False  # startup bound disabled (cfg.watchdog_startup_s=0)
        anchor = since if since is not None else time.time() - self.started
        return anchor > self.startup_stall_s

    def mark_done(self) -> None:
        with self._lock:
            self._done = True

    # -- reads ---------------------------------------------------------------
    def last_step(self) -> typing.Optional[int]:
        with self._lock:
            return self._last_step

    def ema_step_seconds(self) -> typing.Optional[float]:
        with self._lock:
            return self._ema_step_s

    def seconds_since_last_step(self) -> typing.Optional[float]:
        with self._lock:
            if self._last_wall is None:
                return None
            return time.time() - self._last_wall

    def snapshot(self) -> dict:
        with self._lock:
            last_step, last_wall = self._last_step, self._last_wall
            ema, done, probe = self._ema_step_s, self._done, self._feeder_probe
            paused, util_probe = self._paused_for, self._util_probe
        since = None if last_wall is None else time.time() - last_wall
        feeder_alive = None
        if probe is not None:
            try:
                feeder_alive = bool(probe())
            except Exception:
                feeder_alive = False
        if done:
            status = "done"
        elif self.stalled():  # checked FIRST: a wedged startup is a stall
            status = "stalled"
        elif last_step is None:
            status = "starting"  # compiling / restoring: no step yet
        else:
            status = "ok"  # includes a declared pause within max_pause_s
        utilization = None
        if util_probe is not None:
            try:
                utilization = {k: round(float(v), 6)
                               for k, v in util_probe().items()}
            except Exception:
                utilization = None
        paused_s = self.paused_seconds()
        return {"status": status,
                "utilization": utilization,
                "last_completed_step": last_step,
                "ema_step_seconds": None if ema is None else round(ema, 6),
                "seconds_since_last_step": (None if since is None
                                            else round(since, 3)),
                "paused_for": paused,
                "paused_seconds": (None if paused_s is None
                                   else round(paused_s, 3)),
                "feeder_alive": feeder_alive,
                "uptime_seconds": round(time.time() - self.started, 3),
                "stall_factor": self.stall_factor}


def device_memory_stats() -> typing.Dict[str, dict]:
    """Per-device ``memory_stats()`` (bytes in use / limit / peak where the
    backend reports them); {} on backends without stats (CPU) or before jax
    imported."""
    out: typing.Dict[str, dict] = {}
    try:
        import jax
        for i, d in enumerate(jax.devices()):
            try:
                stats = d.memory_stats()
            except Exception:
                stats = None
            if stats:
                out[str(i)] = {k: int(v) for k, v in stats.items()
                               if isinstance(v, (int, float))}
    except Exception:
        pass
    return out


# -- HTTP server -------------------------------------------------------------

class _ObsServer(ThreadingHTTPServer):
    daemon_threads = True
    registry: MetricsRegistry
    health: typing.Optional[Health]
    #: optional serving-SLO summary callable (serve/slo.py::ServeSLO.summary)
    #: merged into /healthz as the ``slo`` block
    slo_probe: typing.Optional[typing.Callable[[], dict]] = None
    #: fleet identity (obs/fleet.py::identity — rank, world_size,
    #: coordinator, generation) merged into /healthz so ANY scraped
    #: endpoint is self-describing in a multi-host fleet
    identity: typing.Optional[dict] = None
    #: optional SLO burn-rate summary callable (obs/slo_alerts.py::
    #: SLOAlerts.summary) merged into /healthz as the ``alerts`` block
    alerts_probe: typing.Optional[typing.Callable[[], dict]] = None
    #: optional per-tenant usage/capacity summary callable
    #: (obs/usage.py::UsageMeter.summary) merged into /healthz as the
    #: ``usage`` block the router federates across replicas
    usage_probe: typing.Optional[typing.Callable[[], dict]] = None


class _Handler(BaseHTTPRequestHandler):
    def _send(self, status: int, body: bytes, ctype: str) -> None:
        self.send_response(status)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_GET(self):
        path, _, query = self.path.partition("?")
        if path == "/metrics":
            # content negotiation: the OpenMetrics flavor (exemplars +
            # ``# EOF``) only on explicit request — the default stays
            # byte-identical Prometheus 0.0.4 (the fleet parser contract)
            accept = self.headers.get("Accept", "")
            openmetrics = ("application/openmetrics-text" in accept
                           or "openmetrics=1" in query)
            if openmetrics and hasattr(self.server.registry,
                                       "render_openmetrics"):
                body = self.server.registry.render_openmetrics().encode()
                self._send(200, body, "application/openmetrics-text; "
                                      "version=1.0.0; charset=utf-8")
                return
            body = self.server.registry.render().encode()
            self._send(200, body, "text/plain; version=0.0.4; charset=utf-8")
        elif path == "/healthz":
            health = self.server.health
            # no Health wired (serve-mode exporter): report only what this
            # endpoint can attest to — a probe must not read "ok" as
            # "the engine is alive"
            snap = health.snapshot() if health is not None else \
                {"status": "metrics-only", "last_completed_step": None}
            ident = getattr(self.server, "identity", None)
            if ident:
                snap["identity"] = ident
            probe = getattr(self.server, "slo_probe", None)
            if probe is not None:
                # serving SLO summary (p50/p95/p99 per phase + error rate)
                # next to liveness — one curl answers "alive AND meeting SLO"
                try:
                    snap["slo"] = probe()
                except Exception:  # noqa: BLE001 - must not break the probe
                    snap["slo"] = None
            aprobe = getattr(self.server, "alerts_probe", None)
            if aprobe is not None:
                # SLO burn-rate alert state (obs/slo_alerts.py) — the block
                # graftwatch --check gates on
                try:
                    snap["alerts"] = aprobe()
                except Exception:  # noqa: BLE001 - must not break the probe
                    snap["alerts"] = None
            uprobe = getattr(self.server, "usage_probe", None)
            if uprobe is not None:
                # per-tenant usage + capacity accounting (obs/usage.py) —
                # the block graftmeter reads and the router federates
                try:
                    snap["usage"] = uprobe()
                except Exception:  # noqa: BLE001 - must not break the probe
                    snap["usage"] = None
            status = 503 if snap["status"] == "stalled" else 200
            self._send(status, json.dumps(snap).encode(), "application/json")
        else:
            self.send_error(404)

    def log_message(self, fmt, *args):  # quiet on stdout; debug-level only
        LOG.debug("obs %s %s", self.address_string(), fmt % args)


def start_server(port: int, registry: typing.Optional[MetricsRegistry] = None,
                 health: typing.Optional[Health] = None,
                 host: str = "127.0.0.1",
                 slo_probe: typing.Optional[typing.Callable[[], dict]] = None,
                 identity: typing.Optional[dict] = None,
                 alerts_probe: typing.Optional[
                     typing.Callable[[], dict]] = None,
                 usage_probe: typing.Optional[
                     typing.Callable[[], dict]] = None) -> _ObsServer:
    """Start the exporter on a daemon thread; ``port=0`` binds an ephemeral
    port (read it back from ``server.server_address[1]``).  ``slo_probe``
    (the REST layer's ``ServeSLO.summary``) adds a ``slo`` block to
    /healthz; ``identity`` (obs/fleet.py) adds the self-describing
    ``identity`` block every fleet-scraped endpoint must carry;
    ``alerts_probe`` (obs/slo_alerts.py::SLOAlerts.summary) adds the SLO
    burn-rate ``alerts`` block; ``usage_probe``
    (obs/usage.py::UsageMeter.summary) adds the per-tenant ``usage``
    block."""
    server = _ObsServer((host, port), _Handler)
    server.registry = registry if registry is not None else REGISTRY
    server.health = health
    server.slo_probe = slo_probe
    server.identity = identity
    server.alerts_probe = alerts_probe
    server.usage_probe = usage_probe
    thread = threading.Thread(target=server.serve_forever,
                              name="obs-exporter", daemon=True)
    server._thread = thread
    thread.start()
    return server


def stop_server(server: _ObsServer) -> None:
    server.shutdown()
    server.server_close()
    server._thread.join(timeout=5.0)


# -- diagnostics dump + watchdog ---------------------------------------------

_DUMP_SEQ = [0]
_DUMP_LOCK = make_lock("obs.exporter._DUMP_LOCK")


def dump_diagnostics(model_path: str, health: typing.Optional[Health] = None,
                     reason: str = "manual",
                     extra: typing.Optional[dict] = None) -> str:
    """Write thread stacks + device memory stats + health snapshot to
    ``<model_path>/diagnostics/hang_<ts>_<n>.txt``; returns the path.
    ``extra`` ({section name: json-able}) appends caller context — the
    watchdog passes the fleet straggler report so a stall dump says
    whether this rank was the fleet's straggler before it wedged."""
    outdir = os.path.join(model_path, "diagnostics")
    os.makedirs(outdir, exist_ok=True)
    with _DUMP_LOCK:
        _DUMP_SEQ[0] += 1
        seq = _DUMP_SEQ[0]
    path = os.path.join(
        outdir, time.strftime(f"hang_%Y%m%d_%H%M%S_{seq}.txt"))
    lines = [f"reason: {reason}",
             f"time: {time.strftime('%Y-%m-%d %H:%M:%S')}",
             f"pid: {os.getpid()}"]
    if health is not None:
        lines.append("health: " + json.dumps(health.snapshot()))
    mem = device_memory_stats()
    lines.append("device_memory_stats: "
                 + (json.dumps(mem, indent=1) if mem else "(unavailable)"))
    for section, doc in (extra or {}).items():
        try:
            lines.append(f"{section}: " + json.dumps(doc, sort_keys=True))
        except (TypeError, ValueError):
            lines.append(f"{section}: {doc!r}")
    # latest graftprof window (main.py writes it at profiler stop): where
    # device time was going BEFORE the stall is exactly the third artifact
    # a hang post-mortem wants next to thread stacks and memory
    summary_path = os.path.join(model_path, "profile_summary.json")
    if os.path.exists(summary_path):
        try:
            with open(summary_path) as f:
                lines.append("profile_summary: "
                             + json.dumps(json.load(f), sort_keys=True))
        except Exception as e:
            lines.append(f"profile_summary: (unreadable: {e})")
    names = {t.ident: t.name for t in threading.enumerate()}
    lines.append("")
    for ident, frame in sorted(sys._current_frames().items()):
        lines.append(f"--- thread {names.get(ident, '?')} (ident {ident}) "
                     f"---")
        lines.extend(l.rstrip("\n") for l in traceback.format_stack(frame))
        lines.append("")
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")
    LOG.warning("diagnostics dumped to %s (%s)", path, reason)
    return path


def startup_position() -> str:
    """Where a run that has finished no step stands: the live spans each
    thread is inside (``setup/*`` or the first ``step``; with ``obs_spans``)
    and the last thing JAX traced, lowered or built."""
    tracer = spans.get_tracer()
    if tracer is None:
        where = "no span tracer (obs_spans off)"
    else:
        where = "; ".join(
            f"{thread} inside {' > '.join(stack)}"
            for thread, stack in sorted(tracer.open_spans().items())
        ) or "no span open"
    last = compile_log.LOG.last()
    if last is None:
        return f"{where}; JAX has built nothing yet"
    return (f"{where}; JAX's last: {compile_log.describe(last)} ended "
            f"{time.perf_counter() - last.t1:.1f}s ago")


class Watchdog(threading.Thread):
    """Dump diagnostics when ``Health.stalled()`` trips — no step within
    ``stall_factor`` x the EMA step time (floored at ``min_stall_s``), or a
    declared pause exceeding ``max_pause_s`` (a hung checkpoint save must
    not hide behind its own pause).  One dump per stall; re-arms when steps
    resume.  ``factor``/``min_stall_s``/``max_pause_s``, when given, are
    written INTO the shared Health so /healthz and the watchdog can never
    disagree about what counts as stalled."""

    _ARMED = object()

    def __init__(self, health: Health, model_path: str,
                 factor: typing.Optional[float] = None, poll_s: float = 1.0,
                 min_stall_s: typing.Optional[float] = None,
                 max_pause_s: typing.Optional[float] = None,
                 registry: typing.Optional[MetricsRegistry] = None,
                 extra_fn: typing.Optional[
                     typing.Callable[[], dict]] = None,
                 flight=None):
        super().__init__(name="obs-watchdog", daemon=True)
        self.health = health
        self.model_path = model_path
        #: optional {section: doc} provider inlined into each stall dump
        #: (Obs wires the fleet straggler summary here)
        self.extra_fn = extra_fn
        #: optional flight recorder (obs/flight.py): a stall also writes
        #: an incident bundle when its ``watchdog`` trigger is armed
        self.flight = flight
        # stall visibility beyond the diagnostics dir: the supervisor and
        # alerting watch this counter on /metrics instead of scraping files
        reg = registry if registry is not None else REGISTRY
        self._stalls = reg.counter(
            "hbnlp_watchdog_stalls_total",
            "hang-watchdog stall dumps fired (one per distinct stall)")
        if factor is not None:
            health.stall_factor = float(factor)
        if min_stall_s is not None:
            health.min_stall_s = float(min_stall_s)
        if max_pause_s is not None:
            health.max_pause_s = float(max_pause_s)
        self.poll_s = poll_s
        self.dumps: typing.List[str] = []
        self._stop_evt = threading.Event()  # NOT _stop: Thread uses that name
        # armed-state sentinel: must be distinct from step values INCLUDING
        # None (a startup stall has last_step None)
        self._fired_at_step: typing.Any = self._ARMED

    def run(self) -> None:
        while not self._stop_evt.wait(self.poll_s):
            self._check()

    def _check(self) -> None:
        h = self.health
        step = h.last_step()
        if not h.stalled():
            self._fired_at_step = self._ARMED  # steps flowing / benign
            return                             # pause: re-arm
        if (self._fired_at_step is not self._ARMED
                and self._fired_at_step == step):
            return  # already dumped for this stall
        self._fired_at_step = step
        self._stalls.inc()
        paused_s = h.paused_seconds()
        threshold = h.stall_threshold()
        if paused_s is not None:
            why = (f"declared pause {h.paused_for()!r} exceeded "
                   f"max_pause_s ({paused_s:.1f}s > {h.max_pause_s}s)")
        elif threshold is None:
            why = (f"no step cadence established within startup_stall_s "
                   f"({h.startup_stall_s}s) — {startup_position()}")
        else:
            why = (f"no step completed in "
                   f"{h.seconds_since_last_step():.2f}s (threshold "
                   f"{threshold:.2f}s = max({h.stall_factor} x "
                   f"EMA {h.ema_step_seconds():.4f}s, {h.min_stall_s}s))")
        extra = None
        if self.extra_fn is not None:
            try:
                extra = {"fleet": self.extra_fn()}
            except Exception as e:  # noqa: BLE001 - the dump must land
                extra = {"fleet": {"error": repr(e)}}
        self.dumps.append(dump_diagnostics(
            self.model_path, h,
            reason=f"watchdog: {why}, last step {step}", extra=extra))
        if self.flight is not None:
            try:
                self.flight.dump("watchdog", extra={"why": why,
                                                    "last_step": step})
            except Exception:  # noqa: BLE001 - the text dump already landed
                pass

    def stop(self, timeout: float = 5.0) -> None:
        self._stop_evt.set()
        self.join(timeout)
