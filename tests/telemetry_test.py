"""Device-side training telemetry (ISSUE 5): in-graph numerics riding the
deferred metric drain, anomaly policies (log / skip_step / halt) against an
injected NaN gradient scale, live MFU / tokens-per-sec / goodput from the
HLO cost-analysis path (reconciled against bench.py's figure), the
telemetry_interval thinning, config validation, and the supervisor's
cross-relaunch goodput accounting."""
import argparse
import json
import os

import numpy as np
import pytest

from homebrewnlp_tpu import main as cli
from homebrewnlp_tpu.obs import device_telemetry
from homebrewnlp_tpu.obs.registry import REGISTRY, MetricsRegistry
from homebrewnlp_tpu.reliability import EXIT_ANOMALY_HALT
from homebrewnlp_tpu.train.metrics import read_metric_rows

from .backend import tiny_config


def _args(steps, profile=""):
    return argparse.Namespace(steps=steps, profile=profile, workers=None)


def _losses(path):
    return [r["loss"] for r in read_metric_rows(str(path))]


# -- parity: telemetry must not perturb training -----------------------------

def test_telemetry_off_and_log_policy_keep_loss_sequence(tmp_path,
                                                         eight_devices):
    """Acceptance: telemetry off compiles the pre-telemetry graph, and
    telemetry on with anomaly_policy="log" is observe-only — all three loss
    sequences are bit-identical (grad*1.0 is exact in IEEE)."""
    cli.train(tiny_config(model_path=str(tmp_path / "off")), _args(8))
    cli.train(tiny_config(model_path=str(tmp_path / "log"),
                          telemetry_interval=1, anomaly_policy="log",
                          telemetry_groups=["embed"]), _args(8))
    cli.train(tiny_config(model_path=str(tmp_path / "skip"),
                          telemetry_interval=1, anomaly_policy="skip_step"),
              _args(8))
    off = _losses(tmp_path / "off")
    assert off == _losses(tmp_path / "log")
    # skip_step adds the in-graph mask, but with finite grads the selected
    # branch is the identical update
    assert off == _losses(tmp_path / "skip")


@pytest.mark.slow
def test_telemetry_parity_300_steps(tmp_path, eight_devices):
    """Satellite: 300 synthetic updates — telemetry off matches the PR-2
    sync-parity configuration, telemetry on (log) changes nothing."""
    base = dict(async_inflight_steps=0, device_prefetch_depth=0)
    cli.train(tiny_config(model_path=str(tmp_path / "off"), **base),
              _args(300))
    cli.train(tiny_config(model_path=str(tmp_path / "on"),
                          telemetry_interval=1, anomaly_policy="log", **base),
              _args(300))
    off, on = _losses(tmp_path / "off"), _losses(tmp_path / "on")
    assert len(off) == len(on) == 300
    assert off == on


# -- telemetry content -------------------------------------------------------

def test_telemetry_metrics_present_and_sane(tmp_path, eight_devices):
    cfg = tiny_config(model_path=str(tmp_path), telemetry_interval=1,
                      telemetry_groups=["embed", "body"])
    cli.train(cfg, _args(4))
    rows = read_metric_rows(str(tmp_path))
    assert len(rows) == 4
    for r in rows:
        assert r["telemetry/nonfinite_grads"] == 0.0
        assert r["telemetry/applied"] == 1.0
        assert r["telemetry/grad_scale"] == 1.0
        assert r["telemetry/param_norm"] > 0
        assert r["telemetry/update_norm"] > 0
        assert r["telemetry/update_ratio"] == pytest.approx(
            r["telemetry/update_norm"] / r["telemetry/param_norm"], rel=1e-4)
        assert r["telemetry/grad_norm/embed"] >= 0
        assert r["telemetry/grad_norm/body"] >= 0
        assert np.isfinite(r["loss"])


def test_telemetry_interval_thins_norms_keeps_sentinels(tmp_path,
                                                        eight_devices):
    cfg = tiny_config(model_path=str(tmp_path), telemetry_interval=3)
    cli.train(cfg, _args(7))
    rows = read_metric_rows(str(tmp_path))
    for i, r in enumerate(rows):
        # sentinels drain every step — anomaly detection is never thinned
        assert "telemetry/nonfinite_grads" in r
        assert "telemetry/applied" in r
        assert ("telemetry/param_norm" in r) == (i % 3 == 0), i


def test_thin_is_pure_and_keeps_sentinels():
    metrics = {"loss": 1.0, "telemetry/param_norm": 2.0,
               "telemetry/nonfinite_grads": 0, "telemetry/applied": 1.0,
               "telemetry/grad_scale": 1.0, "telemetry/grad_norm/x": 3.0}
    on_grid = device_telemetry.thin(dict(metrics), 6, 3)
    assert on_grid == metrics
    off_grid = device_telemetry.thin(dict(metrics), 7, 3)
    assert "telemetry/param_norm" not in off_grid
    assert "telemetry/grad_norm/x" not in off_grid
    assert off_grid["telemetry/nonfinite_grads"] == 0
    assert off_grid["loss"] == 1.0
    # interval <= 1: no thinning at all
    assert device_telemetry.thin(dict(metrics), 7, 1) == metrics


# -- anomaly policies --------------------------------------------------------

def test_skip_step_masks_one_update_and_training_continues(tmp_path,
                                                           eight_devices):
    """Acceptance: an injected non-finite gradient under skip_step skips
    exactly one update (a bit-exact no-op for params AND slots), increments
    hbnlp_anomaly_skips_total, and the run finishes with finite losses."""
    before = REGISTRY.counter("hbnlp_anomaly_skips_total").value()
    cfg = tiny_config(model_path=str(tmp_path / "inj"), telemetry_interval=1,
                      anomaly_policy="skip_step",
                      fault_plan="grads:nan@step3")
    cli.train(cfg, _args(6))
    rows = read_metric_rows(str(tmp_path / "inj"))
    assert [r["step"] for r in rows] == list(range(6))
    assert [r["telemetry/applied"] for r in rows] == [1, 1, 1, 0, 1, 1]
    assert rows[3]["telemetry/nonfinite_grads"] > 0
    assert rows[3]["telemetry/update_norm"] == 0.0  # true no-op
    assert all(np.isfinite(r["loss"]) for r in rows)
    assert REGISTRY.counter("hbnlp_anomaly_skips_total").value() == before + 1
    # the skipped update left params at their step-3 values: step 4's loss
    # differs from the uninjected run's, but training keeps descending
    ref = tiny_config(model_path=str(tmp_path / "ref"), telemetry_interval=1,
                      anomaly_policy="skip_step")
    cli.train(ref, _args(6))
    ref_rows = read_metric_rows(str(tmp_path / "ref"))
    # identical before the injection point
    assert [r["loss"] for r in rows[:4]] == [r["loss"] for r in ref_rows[:4]]


def test_log_policy_keeps_updates_applied(tmp_path, eight_devices):
    cfg = tiny_config(model_path=str(tmp_path), telemetry_interval=1,
                      anomaly_policy="log", fault_plan="grads:nan@step2")
    cli.train(cfg, _args(4))
    rows = read_metric_rows(str(tmp_path))
    assert rows[2]["telemetry/nonfinite_grads"] > 0
    # observe-only: the (poisoned) update applied, the run was not stopped
    assert [r["telemetry/applied"] for r in rows] == [1, 1, 1, 1]
    assert len(rows) == 4


def test_halt_policy_exits_with_distinct_code(tmp_path, eight_devices):
    cfg = tiny_config(model_path=str(tmp_path), telemetry_interval=1,
                      anomaly_policy="halt", fault_plan="grads:nan@step3")
    with pytest.raises(SystemExit) as e:
        cli.train(cfg, _args(12))
    assert e.value.code == EXIT_ANOMALY_HALT
    # the anomalous step's row IS in metrics.jsonl (written before the halt)
    rows = read_metric_rows(str(tmp_path))
    anomalous = [r for r in rows if r["telemetry/nonfinite_grads"] > 0]
    assert anomalous and anomalous[0]["step"] == 3


def test_halt_does_not_checkpoint_poisoned_params(tmp_path, eight_devices):
    """A halt exits BEFORE the end-of-run checkpoint: the newest saved state
    predates the anomaly, so the supervisor's relaunch resumes clean."""
    cfg = tiny_config(model_path=str(tmp_path), telemetry_interval=1,
                      anomaly_policy="halt", fault_plan="grads:nan@step3",
                      use_checkpointing=True, steps_per_checkpoint=2)
    with pytest.raises(SystemExit):
        cli.train(cfg, _args(12))
    manifests = [f for f in os.listdir(tmp_path / "ckpt")
                 if f.startswith("manifest_")]
    steps = sorted(int(f[len("manifest_"):-len(".json")]) for f in manifests)
    assert steps and steps[-1] <= 3  # nothing saved past the anomaly


def test_anomaly_monitor_rejects_unknown_policy():
    with pytest.raises(ValueError, match="anomaly_policy"):
        device_telemetry.AnomalyMonitor("explode", registry=MetricsRegistry())


def test_config_validation():
    with pytest.raises(ValueError, match="telemetry_interval"):
        tiny_config(telemetry_interval=-1)
    with pytest.raises(ValueError, match="anomaly_policy"):
        tiny_config(anomaly_policy="explode")
    cfg = tiny_config()
    assert cfg.telemetry_interval == 0 and cfg.anomaly_policy == "log"
    cfg = tiny_config(telemetry_groups=("embed",))
    assert cfg.telemetry_groups == ["embed"]
    # a grads-site fault plan with telemetry off would be silently inert:
    # rejected at config load instead
    with pytest.raises(ValueError, match="grads"):
        tiny_config(fault_plan="grads:nan@step3")
    tiny_config(fault_plan="grads:nan@step3", telemetry_interval=1)


def test_grad_scale_requires_telemetry(eight_devices):
    from homebrewnlp_tpu.train import Trainer
    tr = Trainer(tiny_config())
    with pytest.raises(ValueError, match="telemetry_interval"):
        tr.step_extra_args(grad_scale=1.0)
    assert tr.step_extra_args() == ()
    tr2 = Trainer(tiny_config(telemetry_interval=1))
    (gs,) = tr2.step_extra_args(grad_scale=np.nan)
    assert isinstance(gs, np.float32) and not np.isfinite(gs)


# -- utilization accounting (train/flops.py) ---------------------------------

def test_flops_reconcile_with_bench_cost_analysis(eight_devices):
    """Acceptance: the live MFU path's flops figure and bench.py's
    flops_per_step are the same HLO cost analysis — within 1% (they are in
    fact the identical call)."""
    import jax
    from homebrewnlp_tpu.train import Trainer, flops
    from homebrewnlp_tpu.utils import random_text_batch
    cfg = tiny_config(telemetry_interval=1)
    trainer = Trainer(cfg)
    batch = random_text_batch(cfg)
    state = trainer.init(batch)
    live = flops.step_flops(trainer, state, batch)
    bench_style = float(trainer.step_cost_analysis(state, batch).get(
        "flops", 0.0))
    assert live > 0
    assert abs(live - bench_style) <= 0.01 * bench_style
    # the AOT executable survives for the step loop (no second compile)
    assert trainer._compiled is not None
    state2, m = trainer.step(state, batch, jax.random.key(0))
    assert np.isfinite(float(m["loss"]))


def test_peak_flops_table():
    from homebrewnlp_tpu.train.flops import peak_flops
    assert peak_flops("TPU v5 lite") == 197e12  # the runtime's own string
    assert peak_flops("v5e") == 197e12  # canonical target_device names
    assert peak_flops("v5p") == 459e12
    assert peak_flops("cpu") is None


def test_utilization_rates():
    from homebrewnlp_tpu.train.flops import Utilization
    u = Utilization(flops_per_step=1e12, tokens_per_step=1000, n_chips=2,
                    peak_flops_per_chip=1e12)
    r = u.rates(0.5)
    assert r["tokens_per_sec"] == pytest.approx(2000.0)
    assert r["tokens_per_sec_per_chip"] == pytest.approx(1000.0)
    assert r["mfu"] == pytest.approx(1e12 / 0.5 / 2e12)
    assert u.rates(0.0) == {}
    # CPU/unknown device: throughput only, no MFU claim
    assert "mfu" not in Utilization(1e12, 1000, 1, None).rates(0.5)


def test_metrics_rows_carry_rates_and_goodput(tmp_path, eight_devices):
    cfg = tiny_config(model_path=str(tmp_path), telemetry_interval=1)
    cli.train(cfg, _args(5))
    rows = read_metric_rows(str(tmp_path))
    # row 0's step_seconds spans compile/init: no rate claim there
    assert "tokens_per_sec" not in rows[0]
    for r in rows[1:]:
        assert r["tokens_per_sec"] > 0
        assert 0.0 <= r["goodput"] <= 1.0


def test_live_metrics_and_healthz_carry_utilization(tmp_path, eight_devices):
    """With obs_port set and telemetry on, /metrics exposes the utilization
    gauges and /healthz mirrors them; Obs.close freezes the gauges (no
    dead-run callbacks leak into later scrapes)."""
    import socket
    import threading
    import urllib.request

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    cfg = tiny_config(model_path=str(tmp_path), obs_port=port,
                      telemetry_interval=1)
    done = threading.Event()
    errs = []
    seen = {}

    def run():
        try:
            cli.train(cfg, _args(80))
        except BaseException as e:
            errs.append(e)
        finally:
            done.set()

    t = threading.Thread(target=run)
    t.start()
    import time
    deadline = time.time() + 300
    while time.time() < deadline and not done.is_set():
        try:
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=5).read().decode()
            h = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=5).read())
        except OSError:
            time.sleep(0.02)
            continue
        if "hbnlp_tokens_per_sec" in body and not done.is_set() \
                and h.get("utilization"):
            seen["metrics"], seen["health"] = body, h
            break
        time.sleep(0.02)
    t.join(600)
    assert not errs, errs
    assert "metrics" in seen, "never scraped utilization while live"
    for name in ("hbnlp_tokens_per_sec", "hbnlp_goodput",
                 "hbnlp_flops_per_step", "hbnlp_mfu"):
        assert name in seen["metrics"], name
    assert "goodput" in seen["health"]["utilization"]
    # frozen after close: callback gauges report plain finals
    assert REGISTRY.get("hbnlp_flops_per_step").value() > 0


# -- supervisor goodput (tools/supervise.py satellite) ------------------------

def _load_supervise():
    import importlib.util
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "supervise_under_test", os.path.join(repo, "tools", "supervise.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_supervisor_goodput_accounting(tmp_path):
    """Two productive launch segments and one dead one: goodput =
    productive / wall, rendered to supervisor_metrics.prom after every
    exit."""
    supervise = _load_supervise()
    clock = [0.0]
    progress = [0]
    prom = tmp_path / "supervisor_metrics.prom"

    def launch():
        # each launch takes 10s; the second one makes no progress
        clock[0] += 10.0
        n = launch.calls = getattr(launch, "calls", 0) + 1
        if n == 1:
            progress[0] = 5
            return supervise.EXIT_PREEMPTED
        if n == 2:
            return 1  # crash, no progress
        progress[0] = 9
        return 0

    sleeps = []

    def sleep(s):
        sleeps.append(s)
        clock[0] += s

    sup = supervise.Supervisor(
        launch, lambda: progress[0], registry=supervise.MetricsRegistry(),
        metrics_path=str(prom), sleep=sleep, clock=lambda: clock[0],
        backoff_base_s=2.0, backoff_jitter=0.0)
    assert sup.run() == 0
    # wall 32s (3 launches + 2s backoff), productive 20s (launches 1 and 3)
    assert sup.goodput() == pytest.approx(20.0 / 32.0)
    text = prom.read_text()
    # every supervisor series carries the host's rank label (fleet-obs
    # satellite: N supervisors sharing a fleet dir must not collide)
    assert "hbnlp_supervisor_goodput" in text
    assert 'hbnlp_supervisor_productive_seconds{rank="0"} 20' in text
    assert ('hbnlp_supervisor_exits_total{outcome="preemption",rank="0"} 1'
            in text)
    assert 'hbnlp_supervisor_exits_total{outcome="crash",rank="0"} 1' in text
    assert 'hbnlp_supervisor_exits_total{outcome="clean",rank="0"} 1' in text


def test_supervisor_anomaly_halt_outcome_and_backoff(tmp_path):
    supervise = _load_supervise()
    rcs = iter([supervise.EXIT_ANOMALY_HALT, 0])
    progress = [0]

    def launch():
        progress[0] += 1  # the halt run made progress before halting
        return next(rcs)

    sleeps = []
    sup = supervise.Supervisor(
        launch, lambda: progress[0], registry=supervise.MetricsRegistry(),
        sleep=sleeps.append, backoff_base_s=3.0, backoff_jitter=0.0)
    assert sup.run() == 0
    assert sleeps == [3.0]  # halt backs off like a crash
    assert sup._exits.value(outcome="anomaly_halt", rank="0") == 1


def test_exit_code_contract_includes_anomaly_halt():
    import homebrewnlp_tpu.reliability as rel
    supervise = _load_supervise()
    assert supervise.EXIT_ANOMALY_HALT == rel.EXIT_ANOMALY_HALT == 86
