"""REST API on the stdlib HTTP server.

Endpoint-compatible with the reference's FastAPI app (/root/reference/src/
rest_api.py:13-89): POST /encode {prompt}, /decode {prompt: [ids]},
/token_completion {prompt|tokens, temperature, response_len, asynchronous},
/completion (same, returns text), /check_tokens.  fastapi/uvicorn are not in
the image, so this uses ``http.server.ThreadingHTTPServer`` — zero deps, and
the threaded wrapper serializes sampler calls exactly like the reference's
Manager-queue bridge.
"""
from __future__ import annotations

import json
import logging
import os
import queue
import threading
import time
import typing
import uuid
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np

from ..config import Config
from ..obs import exporter as obs_exporter
from ..obs import spans
from ..obs.registry import REGISTRY
from ..obs.usage import clean_tenant
from ..reliability import faults
from . import slo as slo_mod
from .interface import (CompletionEngine, InterfaceWrapper,
                        QueueDeadlineExceeded)

LOG = logging.getLogger("homebrewnlp_tpu.serve.rest")


def request_metrics(registry=None):
    """(counter, histogram) for REST request records, resolved ONCE per
    server (docs/observability.md) — the per-request path only pays the
    labels lookup + update.  Label values must be a MATCHED endpoint (or
    the fixed ``other`` bucket for unmatched requests): labelling with the
    raw request path would let a scanner grow the label set, and the
    registry, without bound."""
    reg = registry if registry is not None else REGISTRY
    return (reg.counter("hbnlp_serve_requests_total", "REST requests "
                        "served", labelnames=("method", "path", "status")),
            reg.histogram("hbnlp_serve_request_seconds",
                          "REST request latency", labelnames=("path",),
                          buckets=slo_mod.SERVE_LATENCY_BUCKETS))


def _sanitize_tokens(tokens: typing.Sequence[int], vocab: int) -> typing.List[int]:
    # the reference clamps out-of-vocab ids (rest_api.py:42-53)
    return [min(max(int(t), 0), vocab - 1) for t in tokens]


class _SseStream:
    """Iterator facade over a streaming generator carrying the abandon
    hook: a generator cannot take attributes, so this thin wrapper holds
    the engine-side ``fetch.cancel`` for the SSE writer — on client
    disconnect the handler calls :meth:`cancel` and the scheduler's reap
    pass frees the lane + KV blocks instead of decoding the abandoned
    stream to completion (docs/reliability.md "Serving resilience")."""

    def __init__(self, it, cancel=None):
        self._it = it
        self._cancel = cancel

    def __iter__(self):
        return self

    def __next__(self):
        return next(self._it)

    def cancel(self) -> None:
        if self._cancel is not None:
            self._cancel()


def _request_xid(headers) -> str:
    """Resolve the request's correlation id: the client's ``X-Request-Id``
    if present, else the trace-id field of a W3C ``traceparent`` header,
    else a fresh server-generated id.  Capped so a hostile header cannot
    bloat logs/spans; the id is echoed back on every response."""
    xid = (headers.get("X-Request-Id") or "").strip()
    if not xid:
        parts = (headers.get("traceparent") or "").strip().split("-")
        if len(parts) >= 2 and parts[1] and parts[1].strip("0"):
            xid = parts[1]
    if not xid:
        xid = uuid.uuid4().hex[:16]
    return xid[:128]


def _request_tenant(headers, header_name: str = "X-Tenant") -> str:
    """Resolve the request's tenant identity from the configured header
    (``usage_tenant_header``): the validated value, or ``anon`` for
    missing/invalid/reserved values (obs/usage.py::clean_tenant).  Rides
    next to the correlation id through log lines, span trails, flight
    trails, and the usage meter's accounts."""
    return clean_tenant(headers.get(header_name))


class RestAPI:
    def __init__(self, cfg: Config, params: dict):
        self.cfg = cfg
        # the engine's samplers carry the TTFT hook: the graph notifies the
        # host at the first sampled token, tagged with the request id the
        # ambient SLO record supplies (docs/observability.md "Serving SLOs").
        # serve_max_batch > 1 (on a KV-cache-eligible config) swaps the
        # serialized InterfaceWrapper for the continuous-batching scheduler
        # (serve/engine.py); the default keeps the serialized path
        # bit-identical to the pre-engine behavior
        from .engine import BatchEngine, BatchInterface, use_batch_engine
        # streaming (serve_stream, default on): the batch engine pushes
        # token chunks from its host loop; the serialized samplers arm the
        # per-row token callback (traced stream flag — a buffered request
        # never pays a host round-trip).  serve_stream=False keeps the
        # samplers callback-free and every stream=true request buffered.
        streaming = bool(getattr(cfg, "serve_stream", True))
        token_cb = slo_mod.dispatch_token_row if streaming else None
        if use_batch_engine(cfg):
            self.engine = BatchEngine(
                cfg, params,
                first_token_callback=slo_mod.dispatch_first_token)
            self.wrapper = BatchInterface(self.engine)
        else:
            if int(getattr(cfg, "serve_max_batch", 1)) > 1:
                LOG.warning(
                    "serve_max_batch=%d requested but the config is not "
                    "KV-cache eligible; serving stays serialized",
                    cfg.serve_max_batch)
            self.engine = CompletionEngine(
                cfg, params,
                first_token_callback=slo_mod.dispatch_first_token,
                token_callback=token_cb)
            self.wrapper = InterfaceWrapper(self.engine)
        self.streaming = streaming

    # -- endpoints -----------------------------------------------------------
    def encode(self, body: dict) -> dict:
        return {"tokens": self.engine.tokenizer.encode(body["prompt"])}

    def decode(self, body: dict) -> dict:
        toks = _sanitize_tokens(body["prompt"], self.cfg.vocab_size)
        return {"completion": self.engine.tokenizer.decode(toks)}

    def check_tokens(self, body: dict) -> dict:
        toks = body["prompt"]
        return {"tokens": _sanitize_tokens(toks, self.cfg.vocab_size)}

    def _truncation(self, body: dict) -> typing.Tuple[dict, dict]:
        """Optional per-request top_k/top_p -> (sampler kwargs, echo dict).

        Requested values are silently bucketed for the compile cache
        (interface.effective_truncation), so completion responses echo the
        EFFECTIVE values actually sampled with (e.g. top_k=3 -> top_k: 4)."""
        from .interface import effective_truncation
        kwargs = {"top_k": (None if body.get("top_k") is None
                            else int(body["top_k"])),
                  "top_p": (None if body.get("top_p") is None
                            else float(body["top_p"]))}
        k, p = effective_truncation(self.cfg, **kwargs)
        return kwargs, {"top_k": k, "top_p": p}

    @staticmethod
    def _stamp_prompt_tokens(n: int) -> None:
        # engine-agnostic prompt-size stamp for the usage meter: the
        # ambient SLO record exists on every handler thread, and the
        # endpoint is the one place that knows the parsed token count
        rec = slo_mod.current()
        if rec is not None:
            rec.prompt_tokens = int(n)

    def token_completion(self, body: dict) -> dict:
        toks = _sanitize_tokens(body.get("prompt", body.get("tokens", [])),
                                self.cfg.vocab_size)
        self._stamp_prompt_tokens(len(toks))
        kwargs, echo = self._truncation(body)
        out = self.wrapper.complete(
            toks, float(body.get("temperature", self.cfg.sampling_temperature)),
            int(body.get("response_len", 64)), **kwargs)
        return dict({"completion": np.asarray(out).tolist()}, **echo)

    def completion(self, body: dict) -> dict:
        ids = self.engine.tokenizer.encode(body["prompt"])
        self._stamp_prompt_tokens(len(ids))
        kwargs, echo = self._truncation(body)
        out = self.wrapper.complete(
            ids, float(body.get("temperature", self.cfg.sampling_temperature)),
            int(body.get("response_len", 64)), **kwargs)
        return dict({"completion": self.engine.tokenizer.decode(
            np.asarray(out)[len(ids):])}, **echo)

    # -- streaming (docs/observability.md "Streaming and inter-token
    # latency"): ``stream: true`` on a completion endpoint answers SSE —
    # one ``data:`` event per token chunk as the engine emits it, then a
    # final event carrying the exact buffered-response payload + ``done``.
    # The generator is primed BEFORE headers go out, so admission shedding
    # still maps to a clean 503.
    def _stream(self, toks: typing.List[int], body: dict,
                decode_text: bool, prompt_len: int):
        cfg = self.cfg
        self._stamp_prompt_tokens(prompt_len)
        kwargs, echo = self._truncation(body)
        sink: "queue.Queue" = queue.Queue()
        fetch = self.wrapper.complete(
            toks, float(body.get("temperature", cfg.sampling_temperature)),
            int(body.get("response_len", 64)), asynchronous=True,
            token_sink=sink, **kwargs)
        poll = max(0.01, float(cfg.default_sleep_duration))
        deadline = float(getattr(cfg, "serve_queue_deadline_s", 0.0))
        t0 = time.monotonic()
        state: dict = {"done": False, "result": None, "error": None,
                       "thread": None}

        def do_fetch():
            try:
                state["result"] = fetch()
            except BaseException as e:  # noqa: BLE001 - re-raised in gen
                state["error"] = e
            state["done"] = True

        def gen():
            # the deadline-cancel protocol lives in fetch(), but fetch()
            # BLOCKS until completion once the request is admitted — run
            # it on a side thread so a still-QUEUED request past the
            # deadline is cancelled (the error surfaces on the next poll)
            # while an admitted request's chunks keep streaming instead
            # of bursting at the end
            while True:
                try:
                    item = sink.get(timeout=poll)
                except queue.Empty:
                    if state["error"] is not None:
                        raise state["error"]
                    if (deadline and state["thread"] is None
                            and not state["done"]
                            and time.monotonic() - t0 > deadline):
                        t = threading.Thread(target=do_fetch, daemon=True)
                        state["thread"] = t
                        t.start()
                    continue
                if item is None:
                    break
                yield ({"text": self.engine.tokenizer.decode(item)}
                       if decode_text else {"tokens": list(item)})
            # sentinel delivered: the result lands immediately after
            if state["thread"] is not None:
                state["thread"].join()
            elif not state["done"]:
                do_fetch()
            if state["error"] is not None:
                raise state["error"]
            out = np.asarray(state["result"])
            final = ({"completion": self.engine.tokenizer.decode(
                          out[prompt_len:])} if decode_text
                     else {"completion": out.tolist()})
            yield dict(final, done=True, **echo)
        return _SseStream(gen(), getattr(fetch, "cancel", None))

    def token_completion_stream(self, body: dict):
        toks = _sanitize_tokens(body.get("prompt", body.get("tokens", [])),
                                self.cfg.vocab_size)
        return self._stream(toks, body, decode_text=False,
                            prompt_len=len(toks))

    def completion_stream(self, body: dict):
        ids = self.engine.tokenizer.encode(body["prompt"])
        return self._stream(ids, body, decode_text=True,
                            prompt_len=len(ids))

    ENDPOINTS = ("encode", "decode", "check_tokens", "token_completion",
                 "completion")
    #: endpoints honoring ``stream: true`` (SSE) when serve_stream is on
    STREAM_ENDPOINTS = ("token_completion", "completion")


class _ApiServer(ThreadingHTTPServer):
    """REST server owning an optional obs exporter: any teardown path —
    ``shutdown()``, ``server_close()``, or the context-manager exit (which
    calls ``server_close``) — also stops the exporter, exactly once, and
    detaches this server's queue probe from the SLO gauges (the registry
    outlives the server; a still-bound probe would pin the engine and its
    params forever)."""

    _obs_server = None
    _slo_probe = None
    _kv_probe = None
    _lane_probe = None
    _batch_wrapper = None
    _watchdog = None
    #: (registry, collector fn) pair for the usage meter's render-time
    #: collector — detached on teardown (the registry outlives the server;
    #: a still-registered collector would pin the meter and keep stale
    #: tenant series on /metrics)
    _usage_collector = None
    #: graceful-drain latch (docs/reliability.md "Serving resilience"):
    #: once set, new completion POSTs answer 503 while in-flight streams
    #: run to completion — flipped by drain(), read lock-free in do_POST
    #: (a stale read only delays the refusal by one request)
    draining = False
    health = None

    def drain(self, grace_deadline_s: float = 30.0) -> bool:
        """Graceful drain state machine: (1) stop admitting — the latch
        above 503s new completions and ``/healthz`` flips to ``draining``
        so the router sheds this replica; (2) finish in-flight streams,
        bounded by ``grace_deadline_s``; (3) stop serving.  Returns True
        when every in-flight request finished inside the grace window
        (zero 5xx to drained clients), False when the deadline cut the
        wait short.  Call from any thread EXCEPT a handler thread
        (``shutdown()`` would deadlock waiting on serve_forever)."""
        self.draining = True
        if self.health is not None:
            self.health.set_draining(True)
        deadline = time.monotonic() + max(0.0, float(grace_deadline_s))
        clean = True
        while self.slo.inflight() > 0:
            if time.monotonic() >= deadline:
                clean = False
                break
            time.sleep(0.05)
        self.shutdown()
        return clean

    def shutdown(self):
        super().shutdown()
        self._stop_obs()

    def server_close(self):
        super().server_close()
        self._stop_obs()

    def _stop_obs(self):
        obs, self._obs_server = self._obs_server, None
        if obs is not None:
            obs_exporter.stop_server(obs)
        wd, self._watchdog = self._watchdog, None
        if wd is not None:
            wd.stop()
        probe, self._slo_probe = self._slo_probe, None
        if probe is not None:
            self.slo.clear_queue_probe(probe)
        kv, self._kv_probe = self._kv_probe, None
        if kv is not None:
            self.slo.clear_kv_blocks_probe(kv)
        lane, self._lane_probe = self._lane_probe, None
        if lane is not None:
            self.slo.clear_lane_probe(lane)
        w, self._batch_wrapper = self._batch_wrapper, None
        if w is not None:
            try:  # detach the occupancy sinks: registry outlives the server
                w.set_batch_observer(None)
                if hasattr(w, "set_step_observer"):
                    w.set_step_observer(None)
            except Exception:  # noqa: BLE001
                pass
        uc, self._usage_collector = self._usage_collector, None
        if uc is not None:
            reg, fn = uc
            try:
                reg.unregister_collector(fn)
            except Exception:  # noqa: BLE001
                pass


def serve(cfg: Config, params: dict, host: str = "127.0.0.1",
          port: int = 8000, background: bool = False, api=None,
          registry=None, obs_port: typing.Optional[int] = None):
    """``api`` (tests) substitutes a prebuilt endpoint object; ``registry``
    overrides the process-default obs registry the request log records to.
    When ``cfg.obs_port`` is set — or ``obs_port`` is passed explicitly
    (0 = ephemeral, for tests/bench) — a /metrics + /healthz exporter runs
    alongside, its ``/healthz`` carrying the ``slo`` summary block, and is
    torn down with the returned server (docs/observability.md).

    Every request gets an id and a phase-attributed SLO record
    (parse -> queue wait -> prefill -> decode -> respond, serve/slo.py);
    a completion whose engine-queue wait exceeds
    ``cfg.serve_queue_deadline_s`` (or that arrives past
    ``serve_queue_limit``) is answered 503 with a Retry-After hint instead
    of hanging."""
    api = api if api is not None else RestAPI(cfg, params)
    endpoints = getattr(api, "ENDPOINTS", RestAPI.ENDPOINTS)
    req_count, req_latency = request_metrics(registry)
    serve_slo = slo_mod.ServeSLO(registry)
    wrapper = getattr(api, "wrapper", None)
    # one bound-method object, installed AND remembered: clear_queue_probe
    # compares by identity, and each `wrapper.queue_depth` access makes a
    # fresh bound method
    slo_probe = (wrapper.queue_depth
                 if wrapper is not None and hasattr(wrapper, "queue_depth")
                 else None)
    if slo_probe is not None:
        serve_slo.set_queue_probe(slo_probe)
    # continuous-batching hooks: the engine samples lane occupancy into
    # hbnlp_serve_batch_size each decode step and exposes the KV pool's
    # free-block level; both detach with the server (probe pinning hazard,
    # see _ApiServer)
    kv_probe = (wrapper.kv_blocks_free
                if wrapper is not None and hasattr(wrapper, "kv_blocks_free")
                else None)
    if kv_probe is not None:
        serve_slo.set_kv_blocks_probe(kv_probe)
    if wrapper is not None and hasattr(wrapper, "set_batch_observer"):
        wrapper.set_batch_observer(serve_slo.observe_batch)
    # token-level hooks (docs/observability.md "Streaming and inter-token
    # latency"): the engine's per-iteration phase decomposition, the live
    # lane-occupancy gauge, the Retry-After lane divisor, and — when a
    # serving trace is configured — the request span trails routed onto
    # the engine's tracer so one Chrome trace holds request anatomy,
    # decode phases, and lane timelines
    lane_probe = (wrapper.active_lanes
                  if wrapper is not None and hasattr(wrapper, "active_lanes")
                  else None)
    if lane_probe is not None:
        serve_slo.set_lane_probe(lane_probe)
    if wrapper is not None and hasattr(wrapper, "set_step_observer"):
        wrapper.set_step_observer(serve_slo.observe_step)
    if wrapper is not None and hasattr(wrapper, "lane_count"):
        serve_slo.set_lane_count(wrapper.lane_count())
    # -- tracing + flight recorder + SLO alerting (docs/observability.md
    # "Request tracing" / "Flight recorder" / "SLO alerting").  One shared
    # SpanTracer carries request trails, engine phases, and lane timelines:
    # the engine's own (serve_trace_path) when it made one, else a fresh
    # ring sized by flight_buffer_spans handed TO the engine so its spans
    # land in the same trace GET /debugz/trace serves.
    cap = (int(getattr(cfg, "flight_buffer_spans", 0) or 0)
           if cfg is not None else 0)
    engine = getattr(api, "engine", None)
    tracer = getattr(engine, "tracer", None)
    if tracer is None and cap > 0:
        tracer = spans.SpanTracer(max_events=cap)
        if engine is not None and hasattr(engine, "tracer"):
            # the scheduler thread only READS this attribute; assignment
            # happens here, before any request reaches the engine
            engine.tracer = tracer
    if tracer is not None:
        serve_slo.tracer = tracer
    flight = None
    alerts = None
    if cap > 0 and cfg is not None:
        from ..obs import fleet
        from ..obs.flight import FlightRecorder
        from ..train.metrics import config_hash
        try:
            chash = config_hash(cfg)
        except Exception:  # noqa: BLE001 - hash is evidence, not a gate
            chash = ""
        flight = FlightRecorder(
            max_spans=cap,
            triggers=tuple(getattr(cfg, "flight_dump_triggers",
                                   ("watchdog", "error", "slo", "manual"))),
            model_path=str(getattr(cfg, "model_path", "") or ""),
            config_hash=chash,
            identity=fleet.identity(cfg),
            registry=registry if registry is not None else REGISTRY)
        flight.tracer = tracer
    objectives = (dict(getattr(cfg, "slo_objectives", {}) or {})
                  if cfg is not None else {})
    if objectives:
        from ..obs.slo_alerts import SLOAlerts
        on_alert = None
        if flight is not None and flight.wants("slo"):
            def on_alert(key, info, _flight=flight):
                _flight.dump("slo", extra={"alert": info})
        alerts = SLOAlerts(objectives,
                           registry=(registry if registry is not None
                                     else REGISTRY), on_alert=on_alert)
        if flight is not None:
            flight.set_alerts_probe(alerts.summary)
    # -- replica liveness (docs/reliability.md "Serving resilience"):
    # EngineHealth turns the scheduler's iteration stamps into the
    # /healthz status the router health-gates on — stalled (503: a decode
    # iteration outlived watchdog_factor x its EMA), draining (SIGTERM
    # grace drain), or ok.  The serialized InterfaceWrapper path carries
    # no iteration stamps, so its health only ever reports ok/draining.
    health = None
    watchdog = None
    # no wrapper (stub APIs) → no liveness to attest: /healthz stays
    # "metrics-only" rather than claiming an engine is alive
    if cfg is not None and wrapper is not None:
        health = slo_mod.EngineHealth(
            factor=float(getattr(cfg, "watchdog_factor", 0.0) or 0.0),
            min_stall_s=float(getattr(cfg, "serve_watchdog_min_stall_s",
                                      1.0)))
        if wrapper is not None and hasattr(wrapper, "set_health"):
            wrapper.set_health(health)
            if health.factor > 0:
                # the watchdog thread only pays for evidence (stall
                # counter + flight bundle); detection is EngineHealth's
                watchdog = slo_mod.ServeWatchdog(
                    health, flight=flight,
                    registry=registry if registry is not None else REGISTRY)
                watchdog.start()
    # -- per-tenant usage metering (docs/observability.md "Usage metering
    # & capacity"): every finalized request lands in the meter's bounded
    # top-K accounts, rendered onto /metrics through the registry's
    # collector hook and onto /healthz as the `usage` block.  The flops
    # price sheet is traced once at startup (static step costs — the same
    # analytic counter graftcost uses); usage_top_k=0 turns it all off.
    meter = None
    tenant_header = (str(getattr(cfg, "usage_tenant_header", "X-Tenant")
                         or "X-Tenant") if cfg is not None else "X-Tenant")
    usage_top_k = (int(getattr(cfg, "usage_top_k", 0) or 0)
                   if cfg is not None else 0)
    if usage_top_k > 0:
        from ..obs import usage as usage_mod
        pricing = (usage_mod.price_serve_executables(cfg, params)
                   if params is not None else None)
        # an unknown accelerator raises here (devices.py): a capacity
        # report priced from a guessed or missing peak is worse than none
        from ..analysis.cost_model import serve_capacity_ceiling
        meter = usage_mod.UsageMeter(usage_top_k,
                                     capacity=serve_capacity_ceiling(),
                                     pricing=pricing)
        usage_registry = registry if registry is not None else REGISTRY
        usage_registry.register_collector(meter.prom_lines)
        if flight is not None:
            flight.set_usage_probe(meter.summary)

    class Handler(BaseHTTPRequestHandler):
        #: in-flight record for the correlation-header hook (end_headers);
        #: reset per request — the handler instance outlives one request
        _rec = None
        _wall_recv = 0.0

        def end_headers(self):
            # one choke point every response path funnels through
            # (send_error included): echo the correlation id + the wall
            # clocks graftload pairs into its clock-offset estimate
            rec = self._rec
            if rec is not None and rec.xid:
                self.send_header("X-Request-Id", rec.xid)
                self.send_header("X-Server-Recv-S",
                                 f"{self._wall_recv:.6f}")
                self.send_header("X-Server-Send-S", f"{time.time():.6f}")
            super().end_headers()

        def do_POST(self):
            if self.path.rstrip("/") == "/debugz/dump":
                self._rec = None
                self._debugz_dump()
                return
            self._wall_recv = time.time()
            name = self.path.strip("/")
            known = name in endpoints
            label = f"/{name}" if known else "other"
            rec = serve_slo.begin(label)
            rec.xid = _request_xid(self.headers)
            rec.tenant = _request_tenant(self.headers, tenant_header)
            self._rec = rec
            prev = slo_mod.set_current(rec)
            status = 500
            try:
                if not known:
                    status = 404
                    self.send_error(404)
                    return
                if name in ("token_completion", "completion"):
                    if getattr(self.server, "draining", False):
                        # graceful drain: in-flight streams finish, new
                        # completions get a clean retryable refusal — the
                        # router stopped sending here the moment /healthz
                        # flipped to draining, so this only catches the
                        # poll-gap race (and a racer's 503 lands before any
                        # body byte, squarely in the failover window)
                        status = 503
                        payload = json.dumps(
                            {"error": "draining: replica is shutting down",
                             "retry_after_s": 1.0}).encode()
                        self.send_response(503)
                        self.send_header("Retry-After", "1")
                        self.send_header("Content-Type", "application/json")
                        self.send_header("Content-Length",
                                         str(len(payload)))
                        self.end_headers()
                        self.wfile.write(payload)
                        return
                    # chaos (reliability/faults.py `replica` site, polled
                    # once per completion request): `die` hard-kills this
                    # replica mid-request — the router observes the dropped
                    # connection; `wedge_healthz` hangs the health snapshot
                    # so only the router's poll TIMEOUT can catch it
                    for action in faults.take("replica"):
                        if action == "die":
                            os._exit(1)
                        elif (action == "wedge_healthz"
                              and health is not None):
                            health.wedge()
                try:
                    length = int(self.headers.get("Content-Length", 0))
                    body = json.loads(self.rfile.read(length) or b"{}")
                    rec.mark_parsed()
                    stream_fn = (
                        getattr(api, name + "_stream", None)
                        if body.get("stream")
                        and name in getattr(api, "STREAM_ENDPOINTS", ())
                        and getattr(api, "streaming", True) else None)
                    if stream_fn is not None:
                        # SSE: the buffered path below stays byte-identical
                        # — this branch only exists when the client asked
                        status = self._stream_sse(stream_fn, body, name)
                        return
                    with spans.span(f"serve/{name}"):
                        result = getattr(api, name)(body)
                    payload = json.dumps(result).encode()
                    status = 200
                    self.send_response(200)
                    self.send_header("Content-Type", "application/json")
                    self.send_header("Content-Length", str(len(payload)))
                    self.end_headers()
                    self.wfile.write(payload)
                except QueueDeadlineExceeded as e:
                    # the engine queue is the serialization bottleneck this
                    # module measures; when it exceeds the configured
                    # deadline the client gets a retryable answer, not a hang
                    status = 503
                    retry = serve_slo.retry_after_s(e.deadline_s)
                    payload = json.dumps(
                        {"error": str(e), "retry_after_s": retry}).encode()
                    self.send_response(503)
                    self.send_header("Retry-After", str(retry))
                    self.send_header("Content-Type", "application/json")
                    self.send_header("Content-Length", str(len(payload)))
                    self.end_headers()
                    self.wfile.write(payload)
                except Exception as e:
                    status = 500
                    self.send_error(500, str(e))
            finally:
                slo_mod.set_current(prev)
                # structured per-request record: registry metrics + a
                # debug-level log line, quiet on stdout by default; finish()
                # closes the SLO record (phase histograms + span trail)
                dt = time.perf_counter() - rec.t_arrival
                req_count.labels(method="POST", path=label,
                                 status=str(status)).inc()
                req_latency.labels(path=label).observe(dt)
                serve_slo.finish(rec, status)
                if meter is not None:
                    try:  # at-most-once: finalize() guards re-entry itself
                        meter.finalize(rec, status)
                    except Exception:  # noqa: BLE001 - metering must not 500
                        pass
                if flight is not None:
                    try:
                        trail = flight.observe_request(rec)
                        if status >= 500 and flight.wants("error"):
                            flight.dump("error",
                                        extra={"request": trail})
                    except Exception:  # noqa: BLE001 - evidence, not a gate
                        pass
                if alerts is not None:
                    try:
                        alerts.observe(status=status, ttft_s=rec.ttft_s(),
                                       e2e_s=rec.e2e_s(),
                                       queue_wait_s=rec.queue_wait_s())
                    except Exception:  # noqa: BLE001 - alerting must not 500
                        pass
                LOG.debug("request id=%d xid=%s tenant=%s method=POST "
                          "path=%s status=%d latency_ms=%.1f", rec.rid,
                          rec.xid or "-", rec.tenant or "-", label, status,
                          dt * 1e3)

        def _send_json(self, status: int, payload: dict) -> None:
            data = json.dumps(payload, default=str).encode()
            self.send_response(status)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

        def _debugz_dump(self) -> None:
            """``POST /debugz/dump``: force a manual incident bundle to
            disk and return it inline (``graftwatch --dump`` validates the
            inline copy without filesystem access to the server)."""
            if flight is None:
                self.send_error(404, "flight recorder disabled "
                                     "(flight_buffer_spans=0)")
                return
            from ..obs import flight as flight_mod
            path = flight.dump("manual", force=True)
            doc = flight.bundle("manual")
            self._send_json(200, {
                "path": path, "bundle": doc,
                "problems": flight_mod.validate_bundle(doc)})

        def do_GET(self):
            # debug surfaces only — /metrics and /healthz live on the obs
            # exporter's port; these need the live tracer/recorder closure
            self._rec = None
            path = self.path.split("?", 1)[0].rstrip("/")
            if path == "/debugz/trace":
                if tracer is None:
                    self.send_error(404, "no span tracer (set "
                                         "flight_buffer_spans or "
                                         "serve_trace_path)")
                    return
                self._send_json(200, tracer.chrome_trace())
            elif path == "/debugz/flight":
                if flight is None:
                    self.send_error(404, "flight recorder disabled "
                                         "(flight_buffer_spans=0)")
                    return
                self._send_json(200, flight.status())
            else:
                self.send_error(404)

        def _stream_sse(self, stream_fn, body: dict, name: str) -> int:
            """Drain a streaming endpoint as Server-Sent Events.  The
            generator is PRIMED before any header goes out (admission
            shedding / queue-deadline still answer a clean 503 via the
            caller's except); after the first chunk the response is
            committed — a mid-stream engine failure is delivered as a
            final ``error`` event on the open stream, and a client
            disconnect (the routine SSE ending) is absorbed here: headers
            are already on the wire, so letting it escape would make
            do_POST stack a 500 status line onto a committed 200."""
            with spans.span(f"serve/{name}", stream=True):
                gen = stream_fn(body)
                first = next(gen)
                self.send_response(200)
                self.send_header("Content-Type", "text/event-stream")
                self.send_header("Cache-Control", "no-cache")
                self.send_header("Connection", "close")
                self.end_headers()
                try:
                    self._sse_event(first)
                    for event in gen:
                        self._sse_event(event)
                except OSError as e:  # client went away mid-stream
                    # reclaim promptly: flag the request cancelled so the
                    # scheduler's next reap pass frees the lane and its KV
                    # blocks for queued work instead of decoding the
                    # abandoned stream to completion
                    cancel = getattr(gen, "cancel", None)
                    if cancel is not None:
                        cancel()
                        # the usage finalize in do_POST's finally closes
                        # this request's books the moment we return; wait
                        # (bounded) for the reap to settle block-seconds
                        # onto the record so the abandoned stream is still
                        # billed the KV capacity it actually held
                        rec = self._rec
                        if meter is not None and rec is not None:
                            deadline = time.monotonic() + 10.0
                            while (rec.kv_block_seconds is None
                                   and time.monotonic() < deadline):
                                time.sleep(0.01)
                    LOG.debug("SSE client disconnected: xid=%s %s",
                              self._rec.xid or "-" if self._rec else "-", e)
                except Exception as e:  # noqa: BLE001 - headers are out
                    try:
                        self._sse_event(
                            {"error": f"{type(e).__name__}: {e}"[:200]})
                    except OSError:  # disconnected while failing: give up
                        LOG.debug("SSE client gone before error event: "
                                  "xid=%s",
                                  self._rec.xid or "-" if self._rec else "-")
            return 200

        def _sse_event(self, event: dict) -> None:
            self.wfile.write(b"data: " + json.dumps(event).encode()
                             + b"\n\n")
            self.wfile.flush()

        def log_message(self, fmt, *args):
            # per-request records go through the registry metrics; raw
            # http.server chatter stays at debug level, off stdout
            LOG.debug("%s %s", self.address_string(), fmt % args)

    server = _ApiServer((host, port), Handler)
    server.api = api  # whoever stops the server closes api.wrapper after it
    server.slo = serve_slo  # tests/bench read summaries off the live server
    server.usage = meter  # per-tenant usage meter (None when top_k=0)
    server._usage_collector = ((registry if registry is not None
                                else REGISTRY, meter.prom_lines)
                               if meter is not None else None)
    server.flight = flight  # incident bundles / debugz surfaces
    server.alerts = alerts  # SLO burn-rate evaluator (None w/o objectives)
    server.tracer = tracer  # the shared serving span ring
    server.health = health  # replica liveness (router health gate + drain)
    server._watchdog = watchdog
    server._slo_probe = slo_probe
    server._kv_probe = kv_probe
    server._lane_probe = lane_probe
    server._batch_wrapper = (wrapper if wrapper is not None
                             and hasattr(wrapper, "set_batch_observer")
                             else None)
    eff_obs = (obs_port if obs_port is not None
               else (getattr(cfg, "obs_port", 0) if cfg is not None else 0))
    if obs_port is not None or eff_obs:
        try:
            from ..obs import fleet
            server._obs_server = obs_exporter.start_server(
                eff_obs, registry=registry if registry is not None
                else REGISTRY, health=health,
                slo_probe=serve_slo.summary,
                identity=fleet.identity(cfg),
                alerts_probe=(alerts.summary if alerts is not None
                              else None),
                usage_probe=(meter.summary if meter is not None
                             else None))
        except OSError:
            server.server_close()  # don't leak the bound REST socket
            raise
    if background:
        thread = threading.Thread(target=server.serve_forever, daemon=True)
        thread.start()
        return server
    try:
        server.serve_forever()
    finally:
        server._stop_obs()
