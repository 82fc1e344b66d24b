"""Per-tenant usage metering tests (``obs/usage.py``; docs/observability.md
"Usage metering & capacity"): tenant validation, the Misra-Gries top-K
sketch (exactness, tail fold, bounded memory under a 10k-distinct-tenant
drill), the at-most-once finalize guard, billing rules (tokens/flops on
200s only), KV block-second settlement against hand-built lane timelines,
flops pricing against the cost model's jaxpr anchor, the router's exact
cross-replica federation, and the LIVE loop: graftload ``--tenants``
client counts reconciling EXACTLY with the server's metered totals under
buffered, streamed, chunked-prefill, SSE-disconnect and ``replica:die``
failover traffic (the ``@slow`` drill — the CI ``meter-smoke`` job runs
the live arms explicitly)."""
from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time
import types
import typing
import urllib.error
import urllib.request

import pytest

sys.path.insert(0, os.path.dirname(__file__))
from backend import mixer_config  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import graftload  # noqa: E402
import graftmeter  # noqa: E402

from homebrewnlp_tpu.models import init_params  # noqa: E402
from homebrewnlp_tpu.obs import usage as usage_mod  # noqa: E402
from homebrewnlp_tpu.obs.flight import (FlightRecorder,  # noqa: E402
                                        request_trail)
from homebrewnlp_tpu.obs.registry import MetricsRegistry  # noqa: E402
from homebrewnlp_tpu.obs.usage import (ANON, OTHER,  # noqa: E402
                                       HeavyHitters, UsageMeter,
                                       clean_tenant, merge_usage,
                                       price_serve_executables)
from homebrewnlp_tpu.serve import RestAPI, serve  # noqa: E402
from homebrewnlp_tpu.utils import random_text_batch  # noqa: E402


class _Rec:
    """A finished-request stand-in carrying exactly the fields
    UsageMeter.finalize reads off a RequestRecord."""

    def __init__(self, tenant: str, prompt: int = 3, gen: int = 4,
                 qw: float = 0.01, kv: float = 0.5, lane: float = 0.2):
        self.tenant = tenant
        self.prompt_tokens = prompt
        self.tokens_generated = gen
        self.kv_block_seconds = kv
        self.lane_seconds = lane
        self.usage_done = False
        self._qw = qw

    def queue_wait_s(self):
        return self._qw


# -- tenant identity ----------------------------------------------------------


def test_clean_tenant_validation():
    assert clean_tenant("acme-prod") == "acme-prod"
    assert clean_tenant("a.b:c_d-9") == "a.b:c_d-9"
    # missing / empty / whitespace-only -> anon
    for bad in (None, "", "   "):
        assert clean_tenant(bad) == ANON
    # bad charset, over-long -> anon (never a 400: identity is advisory)
    assert clean_tenant('evil"label') == ANON
    assert clean_tenant("x" * 65) == ANON
    assert clean_tenant("has space") == ANON
    # reserved rows cannot be claimed or spoofed into distinct series
    assert clean_tenant(OTHER) == ANON
    assert clean_tenant(ANON) == ANON


def test_config_usage_knobs_validate():
    cfg = mixer_config(depth=1, sequence_length=12, heads=2,
                       features_per_head=16, vocab_size=32,
                       train_batch_size=1)
    assert cfg.usage_top_k == 32
    assert cfg.usage_tenant_header == "X-Tenant"
    with pytest.raises(ValueError, match="usage_top_k"):
        mixer_config(depth=1, sequence_length=12, heads=2,
                     features_per_head=16, vocab_size=32,
                     train_batch_size=1, usage_top_k=-1)


# -- the sketch ---------------------------------------------------------------


def test_heavy_hitters_topk_exact_and_bounded():
    hh = HeavyHitters(3)
    for _ in range(10):
        hh.admit("big")
    for i in range(5):
        hh.admit(f"small{i}")
    # the bound: never more than k slots, ever
    assert len(hh.weight) <= 3
    # the Frequent guarantee: frequency > n/(k+1) stays tracked
    assert "big" in hh.weight


def test_heavy_hitters_eviction_reports_freed_slots():
    hh = HeavyHitters(2)
    assert hh.admit("a") == (True, [])
    assert hh.admit("b") == (True, [])
    # full table, miss: every weight decrements, both zero out, newcomer
    # takes a freed slot — the evicted names come back for the fold
    tracked, evicted = hh.admit("c")
    assert tracked and sorted(evicted) == ["a", "b"]
    assert "c" in hh.weight and len(hh.weight) <= 2


def test_10k_tenant_drill_bounded_memory_and_metrics():
    top_k = 32
    meter = UsageMeter(top_k)
    reg = MetricsRegistry()
    reg.register_collector(meter.prom_lines)
    for i in range(10_000):
        meter.finalize(_Rec(f"tenant{i}"), 200)
    # memory bound: at most K exact rows + the fold row, no matter how
    # many distinct tenants hit the server
    assert len(meter._tenants) <= top_k
    assert len(meter._sketch.weight) <= top_k
    # /metrics stays bounded: 5 families x (K+1) children + HELP/TYPE
    text = reg.render()
    tenant_lines = [ln for ln in text.splitlines()
                    if ln.startswith("hbnlp_serve_") and "tenant=" in ln]
    assert 0 < len(tenant_lines) <= (top_k + 1) * 6
    s = meter.summary()
    assert s["tracked_tenants"] <= top_k
    assert s["folds"] > 0
    # exact-totals invariant: every one of the 10k records landed in
    # exactly one row; the rows sum back to the overall totals TO THE TOKEN
    assert s["totals"]["requests"] == 10_000
    for field in ("requests", "prompt_tokens", "generated_tokens"):
        assert sum(r[field] for r in s["per_tenant"].values()) \
            == s["totals"][field]
    assert not graftmeter.row_sum_problems(s)


def test_fold_moves_exact_accumulators_into_other():
    meter = UsageMeter(1)
    meter.finalize(_Rec("a", prompt=10, gen=20), 200)
    meter.finalize(_Rec("b", prompt=1, gen=2), 200)   # evicts a -> other
    s = meter.summary()
    per = s["per_tenant"]
    assert OTHER in per
    # a's exact accumulators moved whole into other (series restart on
    # re-admission is the consumer's clamp problem, not a token leak)
    assert per[OTHER]["prompt_tokens"] == 10
    assert per[OTHER]["generated_tokens"] == 20
    assert s["totals"]["prompt_tokens"] == 11
    assert s["totals"]["generated_tokens"] == 22


# -- finalize semantics -------------------------------------------------------


def test_finalize_at_most_once():
    meter = UsageMeter(4)
    rec = _Rec("t0")
    assert meter.finalize(rec, 200) is True
    assert meter.finalize(rec, 200) is False   # SSE-disconnect double call
    assert meter.summary()["totals"]["requests"] == 1


def test_billing_rules_tokens_on_200_only():
    meter = UsageMeter(4, pricing={"prefill_flops": 100.0,
                                   "decode_flops_per_token": 10.0})
    meter.finalize(_Rec("t0", prompt=5, gen=7), 200)
    meter.finalize(_Rec("t0", prompt=5, gen=7, kv=0.25, lane=0.1), 503)
    row = meter.summary()["per_tenant"]["t0"]
    assert row["requests"] == 2 and row["errors"] == 1
    # tokens + flops billed for the 200 only (the client-verifiable
    # counts); capacity (block/lane seconds) accrues for BOTH
    assert row["prompt_tokens"] == 5 and row["generated_tokens"] == 7
    assert row["flops"] == pytest.approx(100.0 + 10.0 * 7)
    assert row["kv_block_seconds"] == pytest.approx(0.75)
    assert row["lane_seconds"] == pytest.approx(0.3)


def test_price_formula_and_missing_pricing():
    meter = UsageMeter(4, pricing={"prefill_flops": 7.0,
                                   "decode_flops_per_token": 3.0})
    assert meter.price(100, 5) == pytest.approx(7.0 + 15.0)
    assert meter.price(100, 0) == pytest.approx(7.0)
    assert UsageMeter(4).price(100, 5) is None


# -- KV block-seconds against a hand-built lane timeline ----------------------


def test_settle_kv_block_seconds_timeline():
    from homebrewnlp_tpu.serve.engine import BatchEngine
    now = time.perf_counter()
    rec = types.SimpleNamespace(kv_blocks=None, kv_block_seconds=None,
                                lane_seconds=None)
    req = types.SimpleNamespace(rec=rec, n_blocks=3, t_alloc=now - 2.0,
                                t_admitted=now - 1.5)
    BatchEngine._settle_kv(None, req)
    # 3 blocks held for ~2s of wall -> ~6 block-seconds; lane time runs
    # from admission (decode occupancy), not allocation
    assert rec.kv_blocks == 3
    assert rec.kv_block_seconds == pytest.approx(6.0, abs=0.5)
    assert rec.lane_seconds == pytest.approx(1.5, abs=0.5)
    # allocation-only (admission failed before t_admitted): falls back to
    # the alloc stamp so capacity consumed pre-failure still accrues
    rec2 = types.SimpleNamespace(kv_blocks=None, kv_block_seconds=None,
                                 lane_seconds=None)
    req2 = types.SimpleNamespace(rec=rec2, n_blocks=2,
                                 t_alloc=time.perf_counter() - 1.0,
                                 t_admitted=None)
    BatchEngine._settle_kv(None, req2)
    assert rec2.kv_block_seconds == pytest.approx(2.0, abs=0.5)
    assert rec2.lane_seconds == pytest.approx(1.0, abs=0.5)
    # no record attached: settlement is a no-op, not a crash
    BatchEngine._settle_kv(None, types.SimpleNamespace(rec=None))


# -- flops pricing vs the cost-model anchor -----------------------------------


def test_price_serve_executables_matches_jaxpr_anchor():
    import functools

    import jax

    from homebrewnlp_tpu.serve import engine as serve_engine
    from homebrewnlp_tpu.train.flops import jaxpr_flops
    cfg = mixer_config(depth=1, sequence_length=12, heads=2,
                       features_per_head=16, vocab_size=32,
                       train_batch_size=1, sampling_temperature=0.0,
                       use_autoregressive_sampling=True, serve_max_batch=2)
    params, _ = init_params(cfg, random_text_batch(cfg))
    sheet = price_serve_executables(cfg, params)
    assert sheet is not None
    patch = sheet["patch"]
    rows, n_lanes = sheet["rows"], sheet["n_lanes"]
    assert rows == int(cfg.sequence_length) // patch and n_lanes == 2
    # the anchor: the SAME analytic counter (train/flops.py::jaxpr_flops)
    # over the SAME executables the scheduler compiles must agree exactly
    decode_abs, prefill_abs, _ = serve_engine.abstract_exec_args(
        cfg, params, rows, n_lanes)
    dec = functools.partial(serve_engine.decode_body, cfg, rows, n_lanes,
                            None)
    anchor = float(jaxpr_flops(jax.make_jaxpr(dec)(*decode_abs)))
    assert sheet["decode_step_flops"] == pytest.approx(anchor, rel=1e-9)
    assert anchor > 0 and sheet["prefill_flops"] > 0
    # the marginal per-token price spreads one step over lanes x patch
    assert sheet["decode_flops_per_token"] * n_lanes * patch \
        == pytest.approx(sheet["decode_step_flops"])
    # a non-traceable config prices to None, never raises
    assert price_serve_executables(object(), params) is None


# -- registry collector hook --------------------------------------------------


def test_registry_collector_hook_render_and_unregister():
    reg = MetricsRegistry()
    lines = ["# HELP x_total t", "# TYPE x_total counter",
             'x_total{tenant="a"} 1']
    fn = lambda: list(lines)  # noqa: E731
    reg.register_collector(fn)
    reg.register_collector(fn)      # idempotent
    assert reg.render().count('x_total{tenant="a"} 1') == 1
    om = reg.render_openmetrics()
    # collector lines render BEFORE the EOF terminator
    assert om.index('x_total{tenant="a"} 1') < om.index("# EOF")
    reg.unregister_collector(fn)
    assert "x_total" not in reg.render()
    reg.unregister_collector(fn)    # no-op, no raise


def test_registry_collector_failure_is_contained():
    reg = MetricsRegistry()
    reg.counter("ok_total", "t").inc()

    def bad():
        raise RuntimeError("collector died")

    reg.register_collector(bad)
    assert "ok_total" in reg.render()   # scrape survives the bad collector


# -- capacity + rates ---------------------------------------------------------


def test_capacity_utilization_and_saturation():
    cap = {"device_kind": "TPU v4", "n_devices": 4,
           "peak_flops_per_s": 100.0}
    rates = {"window_s": 10.0, "flops_per_s": 25.0, "tokens_per_s": 50.0,
             "mean_inflight": 2.0}
    out = usage_mod._capacity_block(cap, rates)
    assert out["capacity_utilization"] == pytest.approx(0.25)
    # mean in-flight 2 at 25% utilization projects saturation at depth 8
    assert out["projected_saturation_concurrency"] == pytest.approx(8.0)
    # CPU hosts price no peak: utilization honestly None, never 0
    out = usage_mod._capacity_block({"device_kind": "cpu", "n_devices": 1,
                                     "peak_flops_per_s": None}, rates)
    assert out["capacity_utilization"] is None
    assert out["projected_saturation_concurrency"] is None
    assert usage_mod._capacity_block(None, rates) is None


def test_serve_capacity_ceiling_shape():
    from homebrewnlp_tpu.analysis.cost_model import serve_capacity_ceiling
    cap = serve_capacity_ceiling()
    assert set(cap) == {"device_kind", "n_devices", "peak_flops_per_s"}
    assert cap["n_devices"] >= 1
    if cap["device_kind"] == "cpu":     # the tier-1 environment
        assert cap["peak_flops_per_s"] is None


def test_summary_rates_from_window():
    meter = UsageMeter(4)
    meter.finalize(_Rec("t0"), 200)
    time.sleep(0.02)
    meter.finalize(_Rec("t0", prompt=7, gen=9), 200)
    rates = meter.summary()["rates"]
    assert rates is not None and rates["window_s"] > 0
    # the window spans finalize #1 -> #2, so it carries request #2's tokens
    assert rates["tokens_per_s"] > 0


# -- federation ---------------------------------------------------------------


def _metered(top_k: int, tenants: typing.Dict[str, int]) -> dict:
    m = UsageMeter(top_k)
    for name, n in tenants.items():
        for _ in range(n):
            m.finalize(_Rec(name), 200)
    return m.summary()


def test_merge_usage_sums_exactly_and_refolds():
    a = _metered(8, {"t0": 3, "t1": 2})
    b = _metered(8, {"t1": 4, "t2": 1})
    merged = merge_usage([a, b, None, {"bogus": True}], top_k=8)
    assert merged["replicas"] == 2
    per = merged["per_tenant"]
    # disjoint accounts of disjoint requests: counters SUM exactly
    assert per["t0"]["requests"] == 3
    assert per["t1"]["requests"] == 6
    assert per["t2"]["requests"] == 1
    assert merged["totals"]["requests"] == 10
    assert merged["totals"]["prompt_tokens"] == sum(
        r["prompt_tokens"] for r in per.values())
    # re-fold: a tighter fleet top-K folds the tail into other but loses
    # nothing — the totals still balance to the token
    refolded = merge_usage([a, b], top_k=1)
    rper = refolded["per_tenant"]
    assert set(rper) == {"t1", OTHER}   # t1 has the token volume
    assert sum(r["requests"] for r in rper.values()) == 10
    assert not graftmeter.row_sum_problems(refolded)
    assert merge_usage([None, {}], top_k=4) is None


def test_router_status_federates_usage():
    from homebrewnlp_tpu.serve.router import Replica, Router
    router = Router([Replica("http://a", "http://a", name="r0"),
                     Replica("http://b", "http://b", name="r1")],
                    health_interval_s=3600.0)
    try:
        for state, block in zip(router.replicas,
                                (_metered(8, {"t0": 2}),
                                 _metered(8, {"t0": 1, "t1": 5}))):
            state.healthy = True
            state.snapshot = {"status": "ok", "usage": block}
        doc = router.status()
        usage = doc.get("usage")
        assert usage is not None and usage["replicas"] == 2
        assert usage["per_tenant"]["t0"]["requests"] == 3
        assert usage["per_tenant"]["t1"]["requests"] == 5
        # a replica set with no usage blocks federates to no usage key
        for state in router.replicas:
            state.snapshot = {"status": "ok"}
        assert "usage" not in router.status()
    finally:
        router.stop()


# -- flight recorder carries the tenant + the usage snapshot ------------------


def test_request_trail_and_bundle_carry_usage():
    from homebrewnlp_tpu.serve.slo import RequestRecord
    rec = RequestRecord(7, path="/token_completion")
    rec.xid, rec.tenant, rec.status = "x-7", "acme", 200
    trail = request_trail(rec)
    assert trail["tenant"] == "acme"
    fr = FlightRecorder()
    fr.set_usage_probe(lambda: {"totals": {"requests": 9}})
    doc = fr.bundle("manual")
    assert doc["usage"] == {"totals": {"requests": 9}}
    fr.set_usage_probe(None)
    assert FlightRecorder().bundle("manual")["usage"] is None


# -- graftload / graftmeter pure arms -----------------------------------------


_PROM = """# HELP hbnlp_serve_tokens_total t
# TYPE hbnlp_serve_tokens_total counter
hbnlp_serve_tokens_total{{tenant="t0",kind="prompt"}} {p0}
hbnlp_serve_tokens_total{{tenant="t0",kind="generated"}} {g0}
hbnlp_serve_tokens_total{{tenant="t1",kind="prompt"}} {p1}
hbnlp_serve_tokens_total{{tenant="t1",kind="generated"}} {g1}
"""


def test_graftload_usage_reconcile_exact_and_mismatch():
    before = _PROM.format(p0=10, g0=5, p1=0, g1=0)
    after = _PROM.format(p0=16, g0=13, p1=4, g1=8)
    deltas = graftload.tenant_token_deltas(before, after)
    assert deltas[("t0", "prompt")] == 6
    client = {"t0": {"requests": 2, "ok": 2, "prompt_tokens": 6,
                     "generated_tokens": 8},
              "t1": {"requests": 1, "ok": 1, "prompt_tokens": 4,
                     "generated_tokens": 8}}
    rep = graftload.usage_reconcile_report(client, deltas)
    assert rep["tokens_match"] is True
    assert rep["client_tokens_total"] == rep["server_tokens_total"] == 26
    # one server-side token short: EXACT means a one-token miss fails
    short = graftload.tenant_token_deltas(
        before, _PROM.format(p0=16, g0=12, p1=4, g1=8))
    rep = graftload.usage_reconcile_report(client, short)
    assert rep["tokens_match"] is False
    assert "t0" in rep["mismatches"]
    # foreign traffic in the window fails rather than being absorbed
    foreign = dict(deltas)
    foreign[("anon", "prompt")] = 3.0
    rep = graftload.usage_reconcile_report(client, foreign)
    assert rep["tokens_match"] is False
    assert rep["server_extra_rows"] == {"anon/prompt": 3}
    assert "skipped" in graftload.usage_reconcile_report(None, deltas)


def test_graftload_check_ok_gates_on_usage_arm():
    base = {"client": {"truncated": False, "n_requests": 4, "n_ok": 4,
                       "error_rate": 0.0, "peak_inflight": 2},
            "reconcile": {"within_tolerance": True}}
    good = dict(base, usage_reconcile={"tokens_match": True})
    bad = dict(base, usage_reconcile={"tokens_match": False,
                                      "mismatches": {"t0": {}}})
    assert graftload.check_ok(good)
    assert not graftload.check_ok(bad)
    # the usage arm binds chaos drills too: failover must not double-bill
    assert not graftload.check_ok(bad, chaos_tolerant=True)
    assert graftload.check_ok(base)   # no arm -> prior behavior unchanged


def test_graftmeter_row_sum_and_reconcile():
    s = _metered(4, {"t0": 2, "t1": 1})
    assert graftmeter.row_sum_problems(s) == []
    broken = json.loads(json.dumps(s))
    broken["per_tenant"]["t0"]["prompt_tokens"] += 1
    assert any("prompt_tokens" in p
               for p in graftmeter.row_sum_problems(broken))
    assert graftmeter.row_sum_problems(None)
    ok, _ = graftmeter.reconcile(
        {"usage_reconcile": {"tokens_match": True}}, s)
    assert ok
    ok, reasons = graftmeter.reconcile(
        {"usage_reconcile": {"tokens_match": False,
                             "client_tokens_total": 9,
                             "server_tokens_total": 8}}, s)
    assert not ok and any("mismatch" in r for r in reasons)
    # absolute fallback: client counts vs the meter's lifetime totals
    client = {"t0": {"prompt_tokens": 6, "generated_tokens": 8},
              "t1": {"prompt_tokens": 3, "generated_tokens": 4}}
    ok, _ = graftmeter.reconcile({"client": {"per_tenant": client}}, s)
    assert ok
    client["t0"]["prompt_tokens"] = 7
    ok, _ = graftmeter.reconcile({"client": {"per_tenant": client}}, s)
    assert not ok


def test_graftmeter_deltas_clamp_fold_restarts():
    prev = {"wall_time_s": 0.0, "tokens": {"t0": {"prompt": 100.0}}}
    cur = {"wall_time_s": 2.0, "tokens": {"t0": {"prompt": 10.0},
                                          "t1": {"prompt": 8.0}}}
    out = graftmeter.deltas(prev, cur)
    # t0 was folded + re-admitted (series restarted): live rate clamps to
    # 0 instead of going negative
    assert out["per_tenant"]["t0"]["tokens_per_s"] == 0.0
    assert out["per_tenant"]["t1"]["tokens_per_s"] == pytest.approx(4.0)


# -- live server: exact reconciliation under real traffic ---------------------


def _engine_cfg(**over):
    base = dict(depth=1, sequence_length=32, heads=2, features_per_head=16,
                vocab_size=32, train_batch_size=1, sampling_temperature=0.0,
                use_autoregressive_sampling=True, serve_max_batch=2,
                # chunked admission prefill ON: reconciliation must stay
                # exact when prompts land chunk by chunk
                serve_prefill_chunk_tokens=8)
    base.update(over)
    return mixer_config(**base)


@pytest.fixture(scope="module")
def live_server():
    cfg = _engine_cfg()
    params, _ = init_params(cfg, random_text_batch(cfg))
    reg = MetricsRegistry()
    api = RestAPI(cfg, params)
    server = serve(cfg, None, port=0, background=True, registry=reg,
                   obs_port=0, api=api)
    yield server, cfg, reg
    server.shutdown()
    server.server_close()


def _obs_url(server) -> str:
    return f"http://127.0.0.1:{server._obs_server.server_address[1]}"


def test_live_tenant_reconciliation_buffered(live_server, tmp_path):
    server, cfg, reg = live_server
    url = f"http://127.0.0.1:{server.server_address[1]}"
    report = graftload.drive(
        url, metrics_url=_obs_url(server), n_requests=9, concurrency=3,
        response_len=4, temperature=0.0, seed=5, vocab=32, min_prompt=2,
        max_prompt=4, timeout_s=300.0, tenants=3)
    arm = report.get("usage_reconcile")
    assert arm is not None, report
    assert arm.get("tokens_match") is True, arm
    assert set((report["client"]["per_tenant"] or {})) == {"t0", "t1", "t2"}
    # /healthz carries the capacity accounting
    with urllib.request.urlopen(_obs_url(server) + "/healthz",
                                timeout=10) as r:
        hz = json.loads(r.read())
    usage = hz.get("usage")
    assert usage is not None
    assert usage["totals"]["requests"] >= 9
    assert usage["capacity"] is not None        # ceiling block present
    assert "capacity_utilization" in usage["capacity"]
    # graftmeter --check: the books balance on the live surface, and the
    # graftload report reconciles through the CLI gate
    rpt = tmp_path / "load_report.json"
    rpt.write_text(json.dumps(report))
    rc = graftmeter.main(["--metrics-url", _obs_url(server), "--check",
                          "--load-report", str(rpt)])
    assert rc == 0


def test_live_tenant_reconciliation_streaming(live_server):
    server, cfg, reg = live_server
    url = f"http://127.0.0.1:{server.server_address[1]}"
    report = graftload.drive(
        url, metrics_url=_obs_url(server), n_requests=6, concurrency=2,
        response_len=4, temperature=0.0, seed=6, vocab=32, min_prompt=2,
        max_prompt=4, timeout_s=300.0, stream=True, tenants=2)
    arm = report.get("usage_reconcile")
    assert arm is not None and arm.get("tokens_match") is True, arm


def test_sse_disconnect_finalizes_exactly_once(live_server):
    import http.client
    server, cfg, reg = live_server
    wrapper = server._batch_wrapper
    free0 = wrapper.kv_blocks_free()
    before = graftload.parse_prom(reg.render())

    def count(name, **labels):
        metrics = graftload.parse_prom(reg.render())
        return sum(v for lab, v in metrics.get(name, [])
                   if all(lab.get(k) == s for k, s in labels.items()))

    req_before = count("hbnlp_serve_tenant_requests_total", tenant="drop")
    conn = http.client.HTTPConnection(
        "127.0.0.1", server.server_address[1], timeout=120)
    conn.request("POST", "/token_completion",
                 body=json.dumps({"prompt": [1, 2, 3, 4],
                                  "temperature": 0.0, "response_len": 24,
                                  "stream": True}),
                 headers={"Content-Type": "application/json",
                          "X-Tenant": "drop"})
    resp = conn.getresponse()
    assert resp.status == 200
    assert resp.read1(8192)
    resp.close()        # client vanishes mid-stream
    conn.close()
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline:
        if (wrapper.kv_blocks_free() == free0
                and wrapper.active_lanes() == 0):
            break
        time.sleep(0.05)
    # the abandoned request finalized EXACTLY once...
    assert count("hbnlp_serve_tenant_requests_total", tenant="drop") \
        == req_before + 1
    # ...and billed at most the plan: whether the engine finished before
    # noticing the drop or reaped the lane mid-stream, tokens_generated
    # is capped at actuals and block-seconds settle on the exit path
    gen = count("hbnlp_serve_tokens_total", tenant="drop",
                kind="generated")
    gen -= sum(v for lab, v in
               before.get("hbnlp_serve_tokens_total", [])
               if lab.get("tenant") == "drop"
               and lab.get("kind") == "generated")
    assert 0 <= gen <= 24
    assert count("hbnlp_serve_kv_block_seconds_total", tenant="drop") > 0


# -- the failover drill: exact metering across a replica kill (@slow) ---------


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _fetch_or_empty(url: str) -> str:
    try:
        return graftload.fetch_metrics(url, timeout_s=5.0)
    except Exception:  # noqa: BLE001 - dead/mid-relaunch replica scrapes as 0
        return ""


@pytest.mark.slow
def test_usage_drill_replica_die_exact_reconciliation(tmp_path):
    """A 2-replica fleet, ``replica:die`` killing replica 0 on its FIRST
    proxied request (pre-commit, so every request fails over and is
    metered exactly once on the survivor): graftload's client-side token
    counts must equal the fleet-summed server deltas TO THE TOKEN, and
    the router's ``/healthz`` must carry the federated usage block."""
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
    cfg_path = tmp_path / "cfg.json"
    cfg_path.write_text(json.dumps(raw))
    base_port, obs_port, router_port = (_free_port(), _free_port(),
                                        _free_port())
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jitcache"))
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "tools", "graftserve.py"),
         "--model", str(cfg_path), "--replicas", "2",
         "--base-port", str(base_port), "--base-obs-port", str(obs_port),
         "--router-port", str(router_port),
         "--health-interval-s", "0.25", "--backoff-base", "0.25",
         "--grace-deadline-s", "15",
         "--fault-plan", "0:replica:die@req1"],
        env=env, cwd=REPO)
    router_url = f"http://127.0.0.1:{router_port}"
    obs_urls = [f"http://127.0.0.1:{obs_port + i}" for i in range(2)]

    def healthy() -> int:
        try:
            with urllib.request.urlopen(router_url + "/healthz",
                                        timeout=5) as r:
                return int(json.loads(r.read()).get("healthy", 0))
        except Exception:  # noqa: BLE001
            return 0

    try:
        deadline = time.monotonic() + 600.0
        while time.monotonic() < deadline and healthy() < 2:
            assert proc.poll() is None, "graftserve died during startup"
            time.sleep(1.0)
        assert healthy() >= 2, "fleet never came up"
        befores = [_fetch_or_empty(u) for u in obs_urls]
        report = graftload.drive(
            router_url, n_requests=24, concurrency=8, response_len=4,
            temperature=0.0, seed=12, vocab=32, min_prompt=2,
            max_prompt=4, timeout_s=300.0, targets=[router_url],
            router_metrics_url=router_url, tenants=3)
        c = report["client"]
        assert not c["truncated"]
        assert graftload.check_ok(report, chaos_tolerant=True), c
        # fleet-summed run deltas: one account per request, no double or
        # zero billing across the kill + failover + relaunch
        deltas: dict = {}
        for b, u in zip(befores, obs_urls):
            for key, v in graftload.tenant_token_deltas(
                    b, _fetch_or_empty(u)).items():
                deltas[key] = deltas.get(key, 0.0) + v
        arm = graftload.usage_reconcile_report(c.get("per_tenant"), deltas)
        assert arm["tokens_match"] is True, arm
        # the router federates the replicas' usage blocks on /healthz —
        # even while degraded (503 with the status doc as its body).  The
        # block is rebuilt from each replica's latest health poll, so give
        # the poll loop a few beats to observe the final finalizes
        def router_usage():
            try:
                with urllib.request.urlopen(router_url + "/healthz",
                                            timeout=5) as r:
                    return json.loads(r.read()).get("usage")
            except urllib.error.HTTPError as e:
                return json.loads(e.read()).get("usage")
            except Exception:  # noqa: BLE001
                return None

        fed = router_usage()
        deadline = time.monotonic() + 30.0
        while (time.monotonic() < deadline
               and not (fed and fed["totals"]["requests"] >= c["n_ok"])):
            time.sleep(0.5)
            fed = router_usage()
        assert fed is not None and fed.get("replicas", 0) >= 1
        assert fed["totals"]["requests"] >= c["n_ok"]
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=120)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=30)
