"""Fleet-resilient serving tests (docs/reliability.md "Serving
resilience"): the health-aware replica router (``serve/router.py``) —
tiering, shedding, transparent pre-commit failover preserving
``X-Request-Id``, at-most-once past the first relayed byte, deadline-
bounded drain — plus the engine-side liveness stack it health-gates on:
``EngineHealth``/``ServeWatchdog`` stall detection, SSE client-disconnect
lane/KV reclamation, replica drain, and the graftserve chaos drill
(``replica:die`` behind a 2-replica router, ``@slow`` — the CI
``chaos-serve`` job runs it explicitly)."""
from __future__ import annotations

import http.client
import json
import os
import queue
import signal
import socket
import subprocess
import sys
import threading
import time
import typing
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

sys.path.insert(0, os.path.dirname(__file__))
from backend import mixer_config  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import graftload  # noqa: E402

from homebrewnlp_tpu.models import init_params  # noqa: E402
from homebrewnlp_tpu.obs.registry import MetricsRegistry  # noqa: E402
from homebrewnlp_tpu.reliability import faults  # noqa: E402
from homebrewnlp_tpu.serve import RestAPI, serve  # noqa: E402
from homebrewnlp_tpu.serve.interface import RequestCancelled  # noqa: E402
from homebrewnlp_tpu.serve.router import (Replica, Router,  # noqa: E402
                                          classify_health, serve_router)
from homebrewnlp_tpu.serve.slo import (EngineHealth,  # noqa: E402
                                       ServeWatchdog)
from homebrewnlp_tpu.utils import random_text_batch  # noqa: E402


# -- fake replicas (stdlib HTTP, no engine) -----------------------------------


class _FakeHandler(BaseHTTPRequestHandler):
    def log_message(self, *a):
        pass

    def do_GET(self):
        srv = self.server
        if self.path.split("?", 1)[0].strip("/") != "healthz":
            self.send_error(404)
            return
        code, doc = srv.health
        body = json.dumps(doc).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_POST(self):
        srv = self.server
        path = self.path.split("?", 1)[0].strip("/")
        n = int(self.headers.get("Content-Length", 0))
        body = json.loads(self.rfile.read(n) or b"{}")
        xid = self.headers.get("X-Request-Id", "")
        with srv.lock:
            srv.seen.append((path, xid))
        mode = srv.mode
        if mode == "die":        # death BEFORE any response byte
            self.connection.close()
            return
        if mode == "http500":
            self.send_error(500, "injected")
            return
        if mode == "sse_mid":    # commit the first SSE event, then die
            first = b'data: {"tokens": [1]}\n\n'
            self.send_response(200)
            self.send_header("Content-Type", "text/event-stream")
            self.send_header("Content-Length", "4096")  # never delivered
            if xid:
                self.send_header("X-Request-Id", xid)
            self.end_headers()
            self.wfile.write(first)
            self.wfile.flush()
            return               # handler returns -> connection closes
        if srv.delay_s:
            time.sleep(srv.delay_s)
        out = {"completion": list(body.get("prompt") or []) + [7, 7]}
        payload = json.dumps(out).encode()
        self.send_response(200)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(payload)))
        if xid:
            self.send_header("X-Request-Id", xid)
        self.end_headers()
        self.wfile.write(payload)


class FakeReplica:
    """A canned backend: POST /token_completion per ``mode``, GET /healthz
    per the mutable ``health`` (code, payload) pair."""

    def __init__(self, mode: str = "ok",
                 health: tuple = (200, {"status": "ok"})):
        self.server = ThreadingHTTPServer(("127.0.0.1", 0), _FakeHandler)
        self.server.daemon_threads = True
        self.server.mode = mode
        self.server.health = health
        self.server.delay_s = 0.0
        self.server.seen = []
        self.server.lock = threading.Lock()
        threading.Thread(target=self.server.serve_forever,
                         daemon=True).start()

    @property
    def url(self) -> str:
        return f"http://127.0.0.1:{self.server.server_address[1]}"

    @property
    def seen(self):
        with self.server.lock:
            return list(self.server.seen)

    def close(self):
        self.server.shutdown()
        self.server.server_close()


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _post(url: str, body: dict, xid: typing.Optional[str] = None,
          timeout: float = 30.0):
    data = json.dumps(body).encode()
    hdr = {"Content-Type": "application/json"}
    if xid:
        hdr["X-Request-Id"] = xid
    req = urllib.request.Request(url + "/token_completion", data=data,
                                 headers=hdr)
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read() or b"{}"), r.headers


def _router_over(replicas, registry=None, **kw) -> Router:
    reg = registry if registry is not None else MetricsRegistry()
    kw.setdefault("health_interval_s", 30.0)  # no background re-polls
    return Router(replicas, registry=reg, **kw)


# -- health tiering (pure) ----------------------------------------------------


def test_classify_health_tiers():
    assert classify_health(200, {"status": "ok"}) == ("ok", "ok")
    assert classify_health(200, {"status": "stalled"})[0] == "down"
    assert classify_health(503, {"status": "stalled"})[0] == "down"
    assert classify_health(200, {"status": "draining"})[0] == "down"
    assert classify_health(404, {"status": "ok"})[0] == "down"
    assert classify_health(200, None)[0] == "down"
    tier, reason = classify_health(
        200, {"status": "ok", "alerts": {"firing": ["ttft_p95_s"]}})
    assert tier == "degraded" and "ttft_p95_s" in reason
    tier, reason = classify_health(
        200, {"status": "ok", "slo": {"kv_blocks_free": 0}})
    assert tier == "degraded" and "kv" in reason
    # a free pool keeps the replica fully routable
    assert classify_health(
        200, {"status": "ok", "slo": {"kv_blocks_free": 3}})[0] == "ok"


def test_fault_plan_accepts_serve_sites_and_req_trigger():
    rules = faults.parse_plan(
        "replica:die@req5;serve_step:stall@3;replica:wedge_healthz@2;"
        "serve_step:fail@1")
    assert [(r.site, r.action, r.at) for r in rules] == [
        ("replica", "die", 5), ("serve_step", "stall", 3),
        ("replica", "wedge_healthz", 2), ("serve_step", "fail", 1)]


# -- selection ----------------------------------------------------------------


def test_pick_prefers_healthy_least_inflight_then_degraded():
    router = _router_over([Replica("http://127.0.0.1:1", name="a"),
                           Replica("http://127.0.0.1:2", name="b"),
                           Replica("http://127.0.0.1:3", name="c")])
    a, b, c = router.replicas
    router.observe_poll(a, "ok", "ok", {})
    router.observe_poll(b, "ok", "ok", {})
    router.observe_poll(c, "degraded", "kv pool exhausted", {})
    first = router.pick()
    assert first in (a, b) and first.inflight == 1
    second = router.pick()          # least-inflight: the OTHER healthy one
    assert second in (a, b) and second is not first
    third = router.pick()           # both healthy busy 1, still preferred
    assert third in (a, b)
    # healthy ones exhausted by `tried` -> degraded fallback
    assert router.pick(tried=[a, b]) is c
    # nothing left at all
    assert router.pick(tried=[a, b, c]) is None
    assert router.m_healthy.value() == 2.0


def test_mark_down_demotes_until_next_good_poll():
    router = _router_over([Replica("http://127.0.0.1:1", name="a")])
    (a,) = router.replicas
    router.observe_poll(a, "ok", "ok", {})
    assert router.pick() is a
    router.release(a)
    router.mark_down(a, "request failed: connect/send")
    assert router.pick() is None
    assert router.m_healthy.value() == 0.0
    router.observe_poll(a, "ok", "ok", {})  # the next successful poll
    assert router.pick() is a


# -- proxying / failover ------------------------------------------------------


def _run_router(router: Router):
    server = serve_router(router, port=0, background=True)
    return server, f"http://127.0.0.1:{server.server_address[1]}"


def test_router_routes_and_preserves_request_id():
    rep = FakeReplica()
    router = _router_over([Replica(rep.url, name="r0")])
    server, url = _run_router(router)
    try:
        time.sleep(0.2)  # initial health poll
        status, out, hdrs = _post(url, {"prompt": [1, 2]}, xid="keep-me")
        assert status == 200 and out["completion"] == [1, 2, 7, 7]
        assert hdrs.get("X-Request-Id") == "keep-me"
        assert hdrs.get("X-Replica") == "r0"
        assert rep.seen == [("token_completion", "keep-me")]
        # a router-minted id when the client sends none
        status, _, hdrs = _post(url, {"prompt": [3]})
        assert status == 200 and rep.seen[-1][1] == hdrs.get("X-Request-Id")
    finally:
        router.stop()
        server.shutdown()
        server.server_close()
        rep.close()


@pytest.mark.parametrize("failure", ["refused", "http500", "die"])
def test_router_failover_preserves_xid_and_counts(failure):
    """Replica death the router can see — connection refused, a 5xx, a
    connection dropped before any response byte — fails over transparently
    under the SAME X-Request-Id, and the merged trace shows both attempts
    under that one id."""
    if failure == "refused":
        bad_url, bad = f"http://127.0.0.1:{_free_port()}", None
    else:
        bad = FakeReplica(mode=failure)
        bad_url = bad.url
    good = FakeReplica()
    reg = MetricsRegistry()
    router = _router_over([Replica(bad_url, name="bad"),
                           Replica(good.url, name="good")], registry=reg)
    server, url = _run_router(router)
    try:
        time.sleep(0.2)
        bad_state, good_state = router.replicas
        # pin the pick order: only `bad` reads healthy, `good` is the
        # degraded fallback the failover retry reaches
        router.observe_poll(bad_state, "ok", "ok", {})
        router.observe_poll(good_state, "degraded", "kv pool exhausted", {})
        status, out, hdrs = _post(url, {"prompt": [9]}, xid="xid-fo")
        assert status == 200 and out["completion"] == [9, 7, 7]
        assert hdrs.get("X-Request-Id") == "xid-fo"
        assert hdrs.get("X-Replica") == "good"
        assert good.seen == [("token_completion", "xid-fo")]
        # the handler notes the terminal outcome AFTER relaying the last
        # body byte, so the client can get here first: poll briefly
        deadline = time.monotonic() + 5.0
        while (router.m_requests.value(replica="good", outcome="ok") < 1.0
               and time.monotonic() < deadline):
            time.sleep(0.02)
        assert router.m_failovers.value() == 1.0
        assert router.m_requests.value(replica="bad",
                                       outcome="failover") == 1.0
        assert router.m_requests.value(replica="good", outcome="ok") == 1.0
        # the failed replica was demoted on the spot
        assert not bad_state.healthy
        # merged trace: both attempts, one id, distinct pids for replicas
        doc = router.merged_trace(timeout_s=1.0)
        attempts = [e for e in doc["traceEvents"]
                    if e.get("pid") == 0 and e.get("ph") == "X"]
        assert [a["args"]["outcome"] for a in attempts] == ["failover", "ok"]
        assert {a["args"]["xid"] for a in attempts} == {"xid-fo"}
        names = {e["args"]["name"] for e in doc["traceEvents"]
                 if e.get("name") == "process_name"}
        assert {"router", "bad", "good"} <= names
    finally:
        router.stop()
        server.shutdown()
        server.server_close()
        good.close()
        if bad is not None:
            bad.close()


def test_router_sheds_stalled_and_draining_replicas():
    stalled = FakeReplica(health=(503, {"status": "stalled"}))
    draining = FakeReplica(health=(200, {"status": "draining"}))
    good = FakeReplica()
    router = _router_over(
        [Replica(stalled.url, name="stalled"),
         Replica(draining.url, name="draining"),
         Replica(good.url, name="good")],
        health_interval_s=0.1)
    server, url = _run_router(router)
    try:
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            s, d, g = router.replicas
            if g.healthy and not s.healthy and not d.healthy:
                break
            time.sleep(0.05)
        assert [r.healthy for r in router.replicas] == [False, False, True]
        assert router.replicas[0].reason == "stalled"
        assert router.replicas[1].reason == "draining"
        for i in range(4):
            status, _, hdrs = _post(url, {"prompt": [i]})
            assert status == 200 and hdrs.get("X-Replica") == "good"
        assert stalled.seen == [] and draining.seen == []
        assert len(good.seen) == 4
    finally:
        router.stop()
        server.shutdown()
        server.server_close()
        for r in (stalled, draining, good):
            r.close()


def test_router_at_most_once_past_first_sse_byte():
    """A replica that dies AFTER the first relayed SSE byte must not be
    retried — the client already holds a prefix; the router truncates."""
    dying = FakeReplica(mode="sse_mid")
    spare = FakeReplica()
    reg = MetricsRegistry()
    router = _router_over([Replica(dying.url, name="dying"),
                           Replica(spare.url, name="spare")], registry=reg)
    server, url = _run_router(router)
    try:
        time.sleep(0.2)
        dying_state, spare_state = router.replicas
        router.observe_poll(dying_state, "ok", "ok", {})
        router.observe_poll(spare_state, "ok", "ok", {})
        # pin the rr cursor so the dying replica takes this request
        router._rr = 0 if router.replicas[0] is dying_state else 1
        conn = http.client.HTTPConnection("127.0.0.1",
                                          server.server_address[1],
                                          timeout=10)
        conn.request("POST", "/token_completion",
                     body=json.dumps({"prompt": [1], "stream": True}),
                     headers={"Content-Type": "application/json",
                              "X-Request-Id": "amo-1"})
        resp = conn.getresponse()
        assert resp.status == 200
        first = resp.read1(8192)
        assert first.startswith(b"data: ")    # the committed prefix
        with pytest.raises((http.client.HTTPException, OSError)):
            while resp.read1(8192):           # stream dies mid-flight
                pass
            raise http.client.IncompleteRead(b"")  # clean-EOF short read
        conn.close()
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:     # handler finishes async
            if reg.render().count("truncated"):
                break
            time.sleep(0.05)
        assert router.m_requests.value(replica="dying",
                                       outcome="truncated") == 1.0
        assert router.m_failovers.value() == 0.0
        assert spare.seen == []                # NEVER retried past commit
    finally:
        router.stop()
        server.shutdown()
        server.server_close()
        dying.close()
        spare.close()


def test_router_503_when_no_replica_is_routable():
    router = _router_over([Replica(f"http://127.0.0.1:{_free_port()}",
                                   name="gone")])
    server, url = _run_router(router)
    try:
        time.sleep(0.3)  # initial poll marks it down
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(url, {"prompt": [1]}, xid="nope")
        assert ei.value.code == 503
        body = json.loads(ei.value.read())
        assert "no healthy replica" in body["error"]
        assert ei.value.headers.get("Retry-After") is not None
        assert ei.value.headers.get("X-Request-Id") == "nope"
    finally:
        router.stop()
        server.shutdown()
        server.server_close()


def test_router_drain_finishes_inflight_sheds_new_and_bounds_deadline():
    slow = FakeReplica()
    slow.server.delay_s = 0.8
    router = _router_over([Replica(slow.url, name="slow")],
                          health_interval_s=0.1)
    server, url = _run_router(router)
    try:
        time.sleep(0.3)
        results: dict = {}

        def go():
            results["inflight"] = _post(url, {"prompt": [1]}, xid="in-fl")

        t = threading.Thread(target=go, daemon=True)
        t.start()
        deadline = time.monotonic() + 5.0
        while server.inflight() == 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert server.inflight() == 1
        out: dict = {}
        dt = threading.Thread(
            target=lambda: out.setdefault("clean", server.drain(10.0)),
            daemon=True)
        t0 = time.monotonic()
        dt.start()
        while not router.draining and time.monotonic() < t0 + 5.0:
            time.sleep(0.005)
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(url, {"prompt": [2]})     # new admission: shed
        assert ei.value.code == 503
        assert "draining" in json.loads(ei.value.read())["error"]
        t.join(timeout=10.0)
        dt.join(timeout=10.0)
        assert results["inflight"][0] == 200   # in-flight finished
        assert out["clean"] is True
        assert time.monotonic() - t0 < 10.0    # bounded, not open-ended
    finally:
        server.server_close()
        slow.close()


def test_router_drain_gives_up_at_the_deadline():
    stuck = FakeReplica()
    stuck.server.delay_s = 8.0
    router = _router_over([Replica(stuck.url, name="stuck")],
                          health_interval_s=0.1)
    server, url = _run_router(router)
    try:
        time.sleep(0.3)
        t = threading.Thread(
            target=lambda: _post(url, {"prompt": [1]}, timeout=20.0),
            daemon=True)
        t.start()
        deadline = time.monotonic() + 5.0
        while server.inflight() == 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        t0 = time.monotonic()
        assert server.drain(grace_deadline_s=0.2) is False
        assert time.monotonic() - t0 < 5.0
    finally:
        server.server_close()
        stuck.close()


# -- EngineHealth / ServeWatchdog ---------------------------------------------


def test_engine_health_idle_engine_never_stalls():
    h = EngineHealth(factor=2.0, min_stall_s=0.05)
    h.iteration_completed(0.01)
    time.sleep(0.12)             # idle: nothing in flight, however long
    assert h.stalled() is None
    assert h.snapshot()["status"] == "ok"


def test_engine_health_flags_overdue_iteration_and_recovers():
    h = EngineHealth(factor=1.0, min_stall_s=0.05)
    h.iteration_completed(0.01)
    h.iteration_started()
    time.sleep(0.12)
    late = h.stalled()
    assert late is not None and late > 0.05
    snap = h.snapshot()
    assert snap["status"] == "stalled" and snap["overdue_s"] > 0.05
    h.iteration_completed(0.12)  # the books close: healthy again
    assert h.stalled() is None and h.snapshot()["status"] == "ok"


def test_engine_health_draining_and_unarmed_watchdog():
    h = EngineHealth(factor=0.0)          # watchdog unarmed
    h.iteration_started()
    time.sleep(0.05)
    assert h.stalled() is None            # no factor -> no stall verdict
    h.set_draining(True)
    assert h.snapshot()["status"] == "draining"
    h.set_draining(False)
    assert h.snapshot()["status"] == "ok"


def test_engine_health_wedge_hangs_snapshot(monkeypatch):
    monkeypatch.setattr(EngineHealth, "WEDGE_S", 0.3)
    h = EngineHealth()
    h.wedge()
    t0 = time.monotonic()
    assert h.snapshot()["status"] == "ok"
    assert time.monotonic() - t0 >= 0.3   # the router's poll TIMEOUT trips


def test_serve_watchdog_fires_once_per_stall():
    reg = MetricsRegistry()
    dumps: list = []

    class Flight:
        def wants(self, reason):
            return True

        def dump(self, reason, extra=None):
            dumps.append((reason, extra))

    h = EngineHealth(factor=1.0, min_stall_s=0.05)
    h.iteration_completed(0.01)
    wd = ServeWatchdog(h, flight=Flight(), registry=reg, poll_s=0.02)
    wd.start()
    try:
        h.iteration_started()
        time.sleep(0.3)           # well past the threshold: one stall
        count = reg.counter("hbnlp_serve_watchdog_stalls_total", "").value()
        assert count == 1.0       # one per stall, not one per poll
        assert len(dumps) == 1 and dumps[0][0] == "watchdog"
        assert dumps[0][1]["overdue_s"] > 0.05
        h.iteration_completed(0.3)
        time.sleep(0.1)           # recovery re-arms
        h.iteration_started()
        time.sleep(0.3)
        assert reg.counter("hbnlp_serve_watchdog_stalls_total",
                           "").value() == 2.0
    finally:
        wd.stop()
        wd.join(timeout=2.0)


# -- engine-backed: cancel reclamation, stall e2e, replica drain --------------


def _engine_cfg(**over):
    base = dict(depth=1, sequence_length=32, heads=2, features_per_head=16,
                vocab_size=32, train_batch_size=1, sampling_temperature=0.0,
                use_autoregressive_sampling=True, serve_max_batch=2,
                watchdog_factor=1.5, serve_watchdog_min_stall_s=0.3)
    base.update(over)
    return mixer_config(**base)


@pytest.fixture(scope="module")
def engine_setup():
    cfg = _engine_cfg()
    params, _ = init_params(cfg, random_text_batch(cfg))
    return cfg, params


@pytest.fixture(scope="module")
def live_batch_server(engine_setup):
    cfg, params = engine_setup
    reg = MetricsRegistry()
    api = RestAPI(cfg, params)
    server = serve(cfg, None, port=0, background=True, registry=reg,
                   obs_port=0, api=api)
    yield server, cfg, reg
    server.shutdown()
    server.server_close()


def _wait_engine_idle(wrapper, free0: int, timeout_s: float = 30.0) -> None:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if (wrapper.kv_blocks_free() == free0
                and wrapper.active_lanes() == 0):
            return
        time.sleep(0.05)
    raise AssertionError(
        f"engine never reclaimed: free={wrapper.kv_blocks_free()} "
        f"(want {free0}), lanes={wrapper.active_lanes()}")


def test_cancel_raises_request_cancelled_and_reclaims(live_batch_server):
    """Satellite bugfix: a cancelled request's lane + KV blocks come back
    promptly — the scheduler's reap pass, not lane exhaustion, ends it."""
    server, cfg, reg = live_batch_server
    wrapper = server._batch_wrapper
    free0 = wrapper.kv_blocks_free()
    sink: "queue.Queue" = queue.Queue()
    fetch = wrapper.complete([1, 2, 3, 4], temperature=0.0, response_len=24,
                             asynchronous=True, token_sink=sink)
    assert sink.get(timeout=120.0) is not None   # generation is live
    fetch.cancel()
    with pytest.raises(RequestCancelled):
        fetch()
    _wait_engine_idle(wrapper, free0)
    # the token sink was closed (None sentinel), not left hanging
    items = []
    while True:
        item = sink.get(timeout=10.0)
        if item is None:
            break
        items.append(item)


def test_sse_client_disconnect_frees_lane_and_blocks(live_batch_server):
    server, cfg, reg = live_batch_server
    wrapper = server._batch_wrapper
    free0 = wrapper.kv_blocks_free()
    port = server.server_address[1]
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    conn.request("POST", "/token_completion",
                 body=json.dumps({"prompt": [1, 2, 3, 4],
                                  "temperature": 0.0, "response_len": 24,
                                  "stream": True}),
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200
    assert resp.read1(8192)       # at least the first SSE event arrived
    resp.close()                  # client vanishes mid-stream (owns the
    conn.close()                  # socket once Connection: close is up)
    _wait_engine_idle(wrapper, free0)
    # the replica still serves after the abandonment
    url = f"http://127.0.0.1:{port}"
    status, out, _ = _post(url, {"prompt": [5, 6], "temperature": 0.0,
                                 "response_len": 4}, timeout=120.0)
    assert status == 200 and len(out["completion"]) == 6


def test_stall_flips_healthz_and_router_routes_around(live_batch_server,
                                                      monkeypatch):
    """The e2e chain: ``serve_step:stall`` chaos wedges the decode loop ->
    EngineHealth flags the overdue iteration -> /healthz answers 503
    stalled -> the router's poll sheds the replica -> pick() routes to the
    healthy peer -> the loop recovers -> the next poll restores it."""
    server, cfg, reg = live_batch_server
    url = f"http://127.0.0.1:{server.server_address[1]}"
    obs_url = f"http://127.0.0.1:{server._obs_server.server_address[1]}"
    # warm request: the jit compile must not be the EMA
    _post(url, {"prompt": [1, 2, 3], "temperature": 0.0, "response_len": 4},
          timeout=300.0)
    health = server.health
    assert health is not None and health.factor > 0
    for _ in range(60):            # wash the compile out of the cadence
        health.iteration_completed(0.02)
    peer = FakeReplica()
    router = _router_over([Replica(url, obs_url, name="real"),
                           Replica(peer.url, name="peer")],
                          health_timeout_s=2.0)
    real, peer_state = router.replicas
    router.poll_replica(real)
    router.poll_replica(peer_state)
    assert real.healthy and peer_state.healthy
    monkeypatch.setenv("HBNLP_SERVE_STALL_S", "2.5")
    faults.install("serve_step:stall@1")
    try:
        t = threading.Thread(
            target=lambda: _post(url, {"prompt": [5, 6, 7],
                                       "temperature": 0.0,
                                       "response_len": 4}, timeout=300.0),
            daemon=True)
        t.start()
        saw_stall = False
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline:
            router.poll_replica(real)
            if not real.healthy and real.reason == "stalled":
                saw_stall = True
                break
            time.sleep(0.05)
        assert saw_stall, f"healthz never flipped (last: {real.reason!r})"
        picked = router.pick()     # routed AROUND the stalled replica
        assert picked is peer_state
        router.release(picked)
        t.join(timeout=300.0)
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            router.poll_replica(real)
            if real.healthy:
                break
            time.sleep(0.1)
        assert real.healthy        # recovered once the stall passed
        assert reg.counter("hbnlp_serve_watchdog_stalls_total",
                           "").value() >= 1.0
    finally:
        faults.reset()
        peer.close()


def test_replica_drain_finishes_inflight_and_sheds_new(engine_setup):
    cfg, params = engine_setup
    reg = MetricsRegistry()
    api = RestAPI(cfg, params)
    server = serve(cfg, None, port=0, background=True, registry=reg,
                   obs_port=0, api=api)
    url = f"http://127.0.0.1:{server.server_address[1]}"
    obs_url = f"http://127.0.0.1:{server._obs_server.server_address[1]}"
    try:
        results: dict = {}

        def go():
            results["inflight"] = _post(
                url, {"prompt": [1, 2, 3], "temperature": 0.0,
                      "response_len": 24}, timeout=300.0)

        t = threading.Thread(target=go, daemon=True)
        t.start()
        deadline = time.monotonic() + 120.0
        while server.slo.inflight() == 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert server.slo.inflight() >= 1
        out: dict = {}
        dt = threading.Thread(
            target=lambda: out.setdefault("clean", server.drain(120.0)),
            daemon=True)
        dt.start()
        t0 = time.monotonic()
        while not server.draining and time.monotonic() < t0 + 10.0:
            time.sleep(0.005)
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(url, {"prompt": [9], "temperature": 0.0,
                        "response_len": 4})
        assert ei.value.code == 503
        assert "draining" in json.loads(ei.value.read())["error"]
        # the health snapshot the router polls flips to draining too
        snap = json.loads(urllib.request.urlopen(
            obs_url + "/healthz", timeout=10).read())
        assert snap["status"] == "draining"
        t.join(timeout=300.0)
        dt.join(timeout=300.0)
        assert results["inflight"][0] == 200    # zero-5xx drain
        assert out["clean"] is True
    finally:
        server.server_close()


# -- chaos drill: replica:die behind a live 2-replica fleet (@slow) ----------


def _drill_cfg(tmp_path) -> str:
    raw = dict(
        model_mode="gpt", use_video=False, use_language=True,
        sequence_length=12, features_per_head=16, heads=2, depth=1,
        vocab_size=32, train_batch_size=1, calc_accuracy=False,
        memory_reduction_strategy="revnet", group_linear_factor=2,
        intermediate_feed_forward_multiplier_multiplier=0.5,
        block_config=[
            {"layer": ["norm-shift-scale-features-group",
                       "bottleneck_group_linear-in:relu-mid:relu-mid:norm-"
                       "mid:shift-mid:scale-mid:features"]},
        ],
        sampling_temperature=0.0, use_autoregressive_sampling=True,
        serve_max_batch=3, use_checkpointing=False,
        watchdog_factor=3.0, serve_watchdog_min_stall_s=1.0,
        model_path=str(tmp_path / "model"),
    )
    path = tmp_path / "cfg.json"
    path.write_text(json.dumps(raw))
    return str(path)


def _healthy_replicas(router_url: str) -> int:
    try:
        req = urllib.request.Request(router_url + "/healthz")
        with urllib.request.urlopen(req, timeout=5) as r:
            return int(json.loads(r.read()).get("healthy", 0))
    except urllib.error.HTTPError as e:
        try:
            return int(json.loads(e.read()).get("healthy", 0))
        except (ValueError, OSError):
            return 0
    except OSError:
        return 0


@pytest.mark.slow
def test_chaos_drill_replica_die_behind_router(tmp_path):
    """The CI ``chaos-serve`` drill: 2 real replicas (graftserve), a
    closed-loop graftload at concurrency 16, ``replica:die`` hard-killing
    replica 0 mid-run.  Goodput must recover (>= 80% of requests OK, the
    chaos-tolerant verdict), the merged trace must hold zero request-id
    collisions, the router must have counted the failovers, and the
    supervisor must relaunch the dead replica back to a 2-healthy fleet
    with every surviving obs surface green (graftwatch --check)."""
    cfg_path = _drill_cfg(tmp_path)
    base_port, obs_port = _free_port(), _free_port()
    router_port = _free_port()
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jitcache"))
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "tools", "graftserve.py"),
         "--model", cfg_path, "--replicas", "2",
         "--base-port", str(base_port), "--base-obs-port", str(obs_port),
         "--router-port", str(router_port),
         "--health-interval-s", "0.25", "--backoff-base", "0.25",
         "--grace-deadline-s", "15",
         "--fault-plan", "0:replica:die@req5"],
        env=env, cwd=REPO)
    router_url = f"http://127.0.0.1:{router_port}"
    try:
        deadline = time.monotonic() + 600.0
        while time.monotonic() < deadline:
            if _healthy_replicas(router_url) >= 2:
                break
            assert proc.poll() is None, "graftserve died during startup"
            time.sleep(1.0)
        assert _healthy_replicas(router_url) >= 2, "fleet never came up"
        trace_path = str(tmp_path / "merged.json")
        report = graftload.drive(
            router_url, n_requests=48, concurrency=16, response_len=4,
            temperature=0.0, seed=11, vocab=32, min_prompt=2, max_prompt=4,
            timeout_s=300.0, targets=[router_url],
            router_metrics_url=router_url, trace_out=trace_path)
        c = report["client"]
        assert not c["truncated"]
        # goodput recovery: the chaos-tolerant verdict (error count
        # bounded by peak in-flight at the kill) AND the 80% floor
        assert graftload.check_ok(report, chaos_tolerant=True), c
        assert c["n_ok"] >= 0.8 * c["n_requests"], c
        # the kill actually happened and the router absorbed it
        rr = report.get("router") or {}
        assert rr.get("failovers", 0) >= 1, rr
        assert rr.get("failover_column_consistent", False), rr
        assert rr.get("client_ok_matches_router", False), rr
        # zero id collisions in the merged trace
        doc = json.load(open(trace_path))
        xids = [e["args"]["xid"] for e in doc["traceEvents"]
                if e.get("pid") == 0 and e.get("name") == "client/request"]
        assert len(xids) == len(set(xids)) == 48
        # the supervisor relaunched replica 0: fleet back to 2-healthy
        deadline = time.monotonic() + 600.0
        while time.monotonic() < deadline:
            if _healthy_replicas(router_url) >= 2:
                break
            time.sleep(1.0)
        assert _healthy_replicas(router_url) >= 2, "fleet never recovered"
        # every replica's obs surface is green again
        for i in range(2):
            rc = subprocess.run(
                [sys.executable, os.path.join(REPO, "tools",
                                              "graftwatch.py"),
                 "--metrics-url", f"http://127.0.0.1:{obs_port + i}",
                 "--check"], env=env, cwd=REPO, timeout=60).returncode
            assert rc == 0, f"graftwatch --check failed for replica {i}"
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=120)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=30)
