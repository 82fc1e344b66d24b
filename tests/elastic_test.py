"""Elastic multi-host suite (ISSUE 10): retried jax.distributed init with
fault injection, barrier-with-timeout, peer-loss detection -> checkpoint ->
EXIT_PEER_LOST, sharding-aware checkpoint manifests with verified
reshard-on-restore (composed 8-device mesh -> smaller mesh -> 1 device),
stale sharding metadata refused with fallback, the supervisor fleet's
lockstep relaunch protocol, backoff jitter, and the reshard-restore
progress probe — the CI ``chaos-multihost`` job runs this file on CPU."""
import argparse
import itertools
import json
import logging
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from homebrewnlp_tpu import main as cli
from homebrewnlp_tpu.config import Config
from homebrewnlp_tpu.obs.registry import MetricsRegistry
from homebrewnlp_tpu.reliability import EXIT_PEER_LOST, dist, faults
from homebrewnlp_tpu.reliability.faults import parse_plan

from .backend import tiny_config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import supervise  # noqa: E402  (tools/supervise.py)


def _args(steps):
    return argparse.Namespace(steps=steps, profile="", workers=None)


def _rows(model_path):
    from homebrewnlp_tpu.train.metrics import read_metric_rows
    return read_metric_rows(model_path)


@pytest.fixture(autouse=True)
def _clean_dist_state():
    faults.reset()
    dist._reset_for_tests()
    yield
    faults.reset()
    dist._reset_for_tests()


class _Cfg:
    """Bare attribute bag standing in for Config in dist-settings tests."""

    def __init__(self, **kw):
        self.dist_coordinator = ""
        self.dist_num_processes = 0
        self.dist_process_id = 0
        self.dist_init_timeout_s = 60.0
        self.dist_init_retries = 3
        self.dist_barrier_timeout_s = 60.0
        self.__dict__.update(kw)


# -- dist settings resolution -------------------------------------------------

def test_settings_single_host_is_none():
    assert dist.settings(None) is None
    assert dist.settings(_Cfg(dist_num_processes=1)) is None


def test_settings_env_overrides_config(monkeypatch):
    cfg = _Cfg(dist_coordinator="cfghost:1", dist_num_processes=4,
               dist_process_id=1)
    s = dist.settings(cfg)
    assert (s.coordinator, s.num_processes, s.process_id) == ("cfghost:1", 4, 1)
    monkeypatch.setenv(dist.ENV_COORDINATOR, "envhost:2")
    monkeypatch.setenv(dist.ENV_NUM_PROCESSES, "2")
    monkeypatch.setenv(dist.ENV_PROCESS_ID, "0")
    s = dist.settings(cfg)
    assert (s.coordinator, s.num_processes, s.process_id) == ("envhost:2", 2, 0)


def test_settings_single_process_with_explicit_coordinator():
    """The legacy ``--tpu addr,0,1`` single-process pod slice: an explicit
    coordinator with num_processes=1 still initializes the distributed
    runtime (regression: the env-stash refactor must not silently drop it)."""
    s = dist.settings(_Cfg(dist_coordinator="h:1", dist_num_processes=1))
    assert s is not None and s.num_processes == 1 and s.process_id == 0


def test_settings_requires_coordinator_and_valid_rank(monkeypatch):
    with pytest.raises(ValueError, match="coordinator"):
        dist.settings(_Cfg(dist_num_processes=2))
    with pytest.raises(ValueError, match="out of range"):
        dist.settings(_Cfg(dist_coordinator="h:1", dist_num_processes=2,
                           dist_process_id=2))


def test_attempt_timeout_slices_overall_deadline():
    """Each initialize attempt gets deadline/(retries+1) as its jax
    initialization_timeout — a slow coordinator consuming the whole budget
    on attempt 1 would otherwise make dist_init_retries unreachable."""
    s = dist.DistSettings("h:1", 2, 0, init_timeout_s=300.0, init_retries=3)
    assert s.attempt_timeout_s == 75
    assert dist.DistSettings("h:1", 2, 0,
                             init_timeout_s=0.0).attempt_timeout_s == 300
    assert dist.DistSettings("h:1", 2, 0,
                             init_timeout_s=5.0).attempt_timeout_s == 10


def test_config_validates_dist_knobs():
    cfg = tiny_config(dist_coordinator="h:1", dist_num_processes=2,
                      dist_process_id=1)
    assert cfg.dist_num_processes == 2
    for bad in (dict(dist_num_processes=-1),
                dict(dist_num_processes=2, dist_process_id=2),
                dict(dist_coordinator="h:1"),  # coordinator without a world
                dict(dist_init_timeout_s=-1),
                dict(dist_init_retries=-1),
                dict(dist_barrier_timeout_s=-1)):
        with pytest.raises(ValueError):
            tiny_config(**bad)


# -- retried distributed init -------------------------------------------------

def test_initialize_retries_then_succeeds():
    reg = MetricsRegistry()
    calls = []

    def flaky(s):
        calls.append(s.process_id)
        if len(calls) == 1:
            # real jax.distributed failures are jaxlib XlaRuntimeError — a
            # RuntimeError, NOT an OSError; the policy must retry it
            raise RuntimeError("DEADLINE_EXCEEDED: barrier timed out")
        if len(calls) == 2:
            raise OSError("coordinator unreachable")

    cfg = _Cfg(dist_coordinator="h:1", dist_num_processes=2)
    elapsed = dist.initialize(cfg, registry=reg, init_fn=flaky,
                              sleep=lambda d: None)
    assert elapsed is not None and len(calls) == 3
    assert reg.counter("hbnlp_dist_init_retries_total").value() == 2
    assert dist.active() and dist.init_seconds() == elapsed
    # the gauge rides the registry for the bench/MULTICHIP hook
    assert "hbnlp_dist_init_seconds" in reg.render()


def test_initialize_exhaustion_raises_coordinator_lost():
    cfg = _Cfg(dist_coordinator="h:1", dist_num_processes=2,
               dist_init_retries=1)

    def dead(s):
        raise OSError("nope")

    with pytest.raises(dist.CoordinatorLost, match="failed after 2"):
        dist.initialize(cfg, registry=MetricsRegistry(), init_fn=dead,
                        sleep=lambda d: None)
    assert not dist.active()


def test_initialize_fault_site_drills_retry_path():
    """dist_init:fail@1 injects the first attempt's failure through exactly
    the retry path a real coordinator outage takes."""
    faults.install("dist_init:fail@1")
    reg = MetricsRegistry()
    calls = []
    cfg = _Cfg(dist_coordinator="h:1", dist_num_processes=2)
    dist.initialize(cfg, registry=reg, init_fn=lambda s: calls.append(1),
                    sleep=lambda d: None)
    # attempt 1 died inside faults.hit BEFORE reaching init_fn; attempt 2
    # reached it — the retry counter shows the injected failure
    assert len(calls) == 1
    assert reg.counter("hbnlp_dist_init_retries_total").value() == 1


def test_initialize_die_fault_not_swallowed_by_retry():
    """dist_init:die@1 is documented non-retryable: it must kill the init
    like a real bug, not be absorbed by the RuntimeError retry path."""
    from homebrewnlp_tpu.reliability.faults import FaultInjectedCrash
    faults.install("dist_init:die@1")
    cfg = _Cfg(dist_coordinator="h:1", dist_num_processes=2)
    calls = []
    with pytest.raises(FaultInjectedCrash):
        dist.initialize(cfg, registry=MetricsRegistry(),
                        init_fn=lambda s: calls.append(1),
                        sleep=lambda d: None)
    assert calls == [] and not dist.active()


def test_initialize_single_host_noop():
    assert dist.initialize(_Cfg()) is None
    assert not dist.active()


# -- barrier ------------------------------------------------------------------

def test_barrier_single_process_noop():
    dist.barrier("anything", timeout_s=0.001)  # must not raise or hang


def test_barrier_timeout_raises_peer_lost(monkeypatch):
    import jax
    from jax._src import distributed as jdist

    class FakeClient:
        def wait_at_barrier(self, name, timeout_ms):
            raise RuntimeError(f"barrier {name} deadline exceeded "
                               f"({timeout_ms}ms)")

    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(jdist.global_state, "client", FakeClient(),
                        raising=False)
    with pytest.raises(dist.BarrierTimeout, match="never arrived"):
        dist.barrier("sync", timeout_s=0.05)
    assert issubclass(dist.BarrierTimeout, dist.PeerLost)


def test_barrier_passes_name_and_timeout(monkeypatch):
    import jax
    from jax._src import distributed as jdist
    seen = []

    class FakeClient:
        def wait_at_barrier(self, name, timeout_ms):
            seen.append((name, timeout_ms))

    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(jdist.global_state, "client", FakeClient(),
                        raising=False)
    dist.barrier("ckpt", timeout_s=2.5)
    assert seen == [("ckpt", 2500)]


# -- peer/coordinator fault sites (seeded regressions) ------------------------

def test_check_peers_fault_sites():
    faults.install("peer:die@step3;coordinator:drop@5")
    dist.check_peers(2)  # not due
    with pytest.raises(dist.PeerLost):
        dist.check_peers(3)
    dist.check_peers(3)  # one-shot
    with pytest.raises(dist.CoordinatorLost):
        dist.check_peers(5)


def test_new_fault_sites_parse_and_validate():
    rules = parse_plan("dist_init:fail@1;peer:die@step10;coordinator:drop@5")
    assert [(r.site, r.action, r.at) for r in rules] == [
        ("dist_init", "fail", 1), ("peer", "die", 10),
        ("coordinator", "drop", 5)]
    # config load validates the whole plan (chaos drills fail fast on typos)
    assert tiny_config(
        fault_plan="dist_init:fail@1;peer:die@step10").fault_plan
    with pytest.raises(ValueError):
        tiny_config(fault_plan="peer:explode@1")


def test_drop_action_at_hit_site_ignored_with_error(caplog):
    """Seeded regression: 'drop' is caller-implemented — reaching it through
    hit() (a site that executes actions itself) logs and does nothing."""
    faults.install("ckpt_write:drop@1")
    with caplog.at_level(logging.ERROR,
                         "homebrewnlp_tpu.reliability.faults"):
        faults.hit("ckpt_write")  # must not raise
    assert any("caller-implemented" in r.message for r in caplog.records)


def test_unknown_action_at_peer_site_logged_not_raised(caplog):
    faults.install("peer:nan@step1")
    with caplog.at_level(logging.ERROR,
                         "homebrewnlp_tpu.reliability.dist"):
        dist.check_peers(1)  # nan is not a peer action: log, don't raise
    assert any("unsupported action" in r.message for r in caplog.records)


# -- peer loss end to end: checkpoint + exit 87 + bit-identical resume --------

def test_peer_loss_checkpoints_and_exits_87(tmp_path, eight_devices):
    cli.train(tiny_config(model_path=str(tmp_path / "ref")), _args(6))
    over = dict(model_path=str(tmp_path / "pl"), use_checkpointing=True,
                steps_per_checkpoint=10, fault_plan="peer:die@step3")
    with pytest.raises(SystemExit) as e:
        cli.train(tiny_config(**over), _args(6))
    assert e.value.code == EXIT_PEER_LOST
    # this host's healthy state was checkpointed BEFORE the exit
    m = json.loads((tmp_path / "pl" / "ckpt" / "manifest_3.json").read_text())
    assert m["version"] >= 2 and m["mesh"]["axes"]
    # the relaunch inherits the SAME plan (supervisor env/config): the rule
    # behind the restore point is disarmed, the run completes
    cli.train(tiny_config(**over), _args(6))
    ref = {r["step"]: r["loss"] for r in _rows(str(tmp_path / "ref"))}
    got = {r["step"]: r["loss"] for r in _rows(str(tmp_path / "pl"))}
    assert set(got) == set(range(6))
    for s in range(6):
        assert ref[s] == got[s], f"loss diverged at step {s} after peer loss"


def test_coordinator_drop_exits_87(tmp_path, eight_devices):
    cfg = tiny_config(model_path=str(tmp_path), use_checkpointing=True,
                      steps_per_checkpoint=10,
                      fault_plan="coordinator:drop@2")
    with pytest.raises(SystemExit) as e:
        cli.train(cfg, _args(5))
    assert e.value.code == EXIT_PEER_LOST
    assert (tmp_path / "ckpt" / "manifest_2.json").exists()


# -- sharding-aware checkpoints + reshard-on-restore --------------------------

def _elastic_cfg(**over):
    """Tiny gpt on the composed parallelism knobs (DP/SP/[PP/]TP)."""
    base = dict(model_mode="gpt", use_video=False, sequence_length=16,
                heads=2, features_per_head=16, vocab_size=64, depth=2,
                train_batch_size=4, memory_reduction_strategy="none",
                tpu_size=8, sequence_parallel=2,
                intermediate_feed_forward_multiplier_multiplier=0.5,
                block_config=[{"layer": ["norm-shift-scale",
                                         "feed_forward-in:relu"]}])
    base.update(over)
    return Config(base)


def _state_on(cfg, devices, steps=0):
    import jax
    from homebrewnlp_tpu.data import synthetic_text_batch, to_global
    from homebrewnlp_tpu.parallel import make_mesh
    from homebrewnlp_tpu.train import Trainer
    mesh = make_mesh(cfg, devices)
    trainer = Trainer(cfg, mesh)
    gb = to_global(synthetic_text_batch(cfg, 0), cfg, mesh)
    state = trainer.init(gb)
    for i in range(steps):
        state, _ = trainer.step(state, gb, jax.random.key(i))
    return mesh, state


def _np_tree(tree):
    import jax
    return jax.tree_util.tree_map(lambda x: np.asarray(x), tree)


def _assert_trees_equal(a, b):
    import jax
    jax.tree_util.tree_map(np.testing.assert_array_equal, a, b)


def test_reshard_roundtrip_mesh_b_and_one_device(tmp_path, eight_devices):
    """THE reshard acceptance: a checkpoint saved on the composed 8-device
    DP/SP/TP mesh restores bit-identically (params AND optimizer slots,
    re-verified by the manifest CRCs after placement) onto a
    differently-shaped mesh and onto a single device."""
    from homebrewnlp_tpu.train import Checkpointer
    cfg = _elastic_cfg()
    meshA, state = _state_on(cfg, eight_devices, steps=2)
    assert dict(meshA.shape)["data"] > 1  # genuinely composed
    Checkpointer(str(tmp_path)).save(state, config_hash="x")
    want_p = _np_tree(dict(state.params))
    want_o = _np_tree({k: dict(v) for k, v in state.opt_state.items()})

    # mesh B: 4 devices, model axis shrunk — different shape, same values
    meshB, template = _state_on(cfg, eight_devices[:4])
    assert dict(meshB.shape) != dict(meshA.shape)
    restored, _ = Checkpointer(str(tmp_path)).restore(template, cfg)
    assert int(restored.step) == 2
    _assert_trees_equal(want_p, _np_tree(dict(restored.params)))
    _assert_trees_equal(want_o, _np_tree(
        {k: dict(v) for k, v in restored.opt_state.items()}))

    # 1 device: graceful degradation floor (sequence_parallel folds to 1 —
    # activation sharding only, the tree structure is mesh-independent)
    cfg1 = _elastic_cfg(sequence_parallel=1)
    _, template1 = _state_on(cfg1, eight_devices[:1])
    restored1, _ = Checkpointer(str(tmp_path)).restore(template1, cfg1)
    _assert_trees_equal(want_p, _np_tree(dict(restored1.params)))
    _assert_trees_equal(want_o, _np_tree(
        {k: dict(v) for k, v in restored1.opt_state.items()}))

    # every reshard was counted and persisted for the progress probe, and
    # the byte-verification honestly recorded (single-process: CRCs ran)
    marker = json.loads((tmp_path / "restore_marker.json").read_text())
    assert marker["count"] == 2 and marker["step"] == 2
    assert marker["from_mesh"] != marker["to_mesh"]
    assert marker["crc_verified"] is True


def test_reshard_roundtrip_composed_pipeline_mesh(tmp_path, eight_devices):
    """DP/SP/PP/TP composed mesh: stage-stacked pipeline leaves (leading
    PIPE_STAGE axis sharded over the pipe mesh axis) reshard onto a
    smaller mesh bit-identically.  Init-state save/restore — stepping the
    1F1B schedule needs jax.shard_map, absent from this toolchain (the
    known tier-1 gap)."""
    from homebrewnlp_tpu.train import Checkpointer
    cfg = _elastic_cfg(pipeline_parallel=2, pipeline_schedule="1f1b")
    meshP, state = _state_on(cfg, eight_devices)
    assert dict(meshP.shape)["pipeline"] == 2
    Checkpointer(str(tmp_path)).save(state, config_hash="p")
    want = _np_tree(dict(state.params))
    meshP4, template = _state_on(cfg, eight_devices[:4])
    assert dict(meshP4.shape) != dict(meshP.shape)
    restored, _ = Checkpointer(str(tmp_path)).restore(template, cfg)
    _assert_trees_equal(want, _np_tree(dict(restored.params)))


def test_resumed_training_after_reshard_stays_deterministic(
        tmp_path, eight_devices):
    """A 2-steps-on-mesh-A checkpoint restored onto mesh B trains on: the
    restored state is a valid training state, not just matching bytes."""
    import jax
    from homebrewnlp_tpu.data import synthetic_text_batch, to_global
    from homebrewnlp_tpu.train import Checkpointer, Trainer
    from homebrewnlp_tpu.parallel import make_mesh
    cfg = _elastic_cfg()
    _, state = _state_on(cfg, eight_devices, steps=2)
    Checkpointer(str(tmp_path)).save(state, config_hash="x")
    meshB = make_mesh(cfg, eight_devices[:4])
    trB = Trainer(cfg, meshB)
    gbB = to_global(synthetic_text_batch(cfg, 0), cfg, meshB)
    template = trB.init(gbB)
    restored, _ = Checkpointer(str(tmp_path)).restore(template, cfg)
    stepped, m = trB.step(restored, gbB, jax.random.key(2))
    assert int(stepped.step) == 3 and np.isfinite(float(m["loss"]))


def test_stale_sharding_metadata_refused_with_fallback(tmp_path,
                                                       eight_devices,
                                                       caplog):
    """Mismatched sharding metadata (spec naming an axis the recorded mesh
    lacks / unknown mesh axes) is refused loudly; restore falls back to the
    newest VERIFIED checkpoint."""
    import jax
    from homebrewnlp_tpu.data import synthetic_text_batch, to_global
    from homebrewnlp_tpu.parallel import make_mesh
    from homebrewnlp_tpu.train import Checkpointer, Trainer
    cfg = _elastic_cfg()
    mesh = make_mesh(cfg, eight_devices)
    trainer = Trainer(cfg, mesh)
    gb = to_global(synthetic_text_batch(cfg, 0), cfg, mesh)
    state = trainer.init(gb)
    ck = Checkpointer(str(tmp_path), max_to_keep=5)
    state, _ = trainer.step(state, gb, jax.random.key(0))
    ck.save(state, config_hash="x")  # step 1: stays clean
    good = _np_tree(dict(state.params))
    state, _ = trainer.step(state, gb, jax.random.key(1))
    ck.save(state, config_hash="x")  # step 2: metadata gets corrupted

    mpath = tmp_path / "manifest_2.json"
    doc = json.loads(mpath.read_text())
    key = next(k for k, e in doc["leaves"].items() if e.get("spec"))
    doc["leaves"][key]["spec"] = [["bogus_axis"]]
    mpath.write_text(json.dumps(doc))

    template = Trainer(cfg, mesh).init(gb)
    with caplog.at_level(logging.ERROR, "homebrewnlp_tpu.train.checkpoint"):
        restored, _ = Checkpointer(str(tmp_path), max_to_keep=5).restore(
            template, cfg)
    assert int(restored.step) == 1  # fell back past the poisoned step 2
    _assert_trees_equal(good, _np_tree(dict(restored.params)))
    assert any("sharding" in r.message and "falling back" in r.message
               for r in caplog.records)


def test_repeat_reshard_not_counted_as_new_progress(tmp_path,
                                                    eight_devices):
    """A child that reshard-restores the SAME checkpoint onto the SAME
    mesh every generation (restores, then dies before saving) must not
    reset the supervisor's crash-loop probe forever: only the first
    reshard bumps the marker count."""
    from homebrewnlp_tpu.train import Checkpointer
    cfg = _elastic_cfg()
    _, state = _state_on(cfg, eight_devices, steps=1)
    Checkpointer(str(tmp_path)).save(state, config_hash="x")
    for _ in range(3):
        _, template = _state_on(cfg, eight_devices[:4])
        Checkpointer(str(tmp_path)).restore(template, cfg)
    marker = json.loads((tmp_path / "restore_marker.json").read_text())
    assert marker["count"] == 1


def test_rejected_restore_never_counts_as_reshard_progress(
        tmp_path, eight_devices):
    """The marker is written only after the WHOLE restore (including the
    data-state sidecar validation) succeeds — a rejected restore must not
    feed the supervisor false progress."""
    from homebrewnlp_tpu.train import Checkpointer
    cfg = _elastic_cfg()
    _, state = _state_on(cfg, eight_devices, steps=1)
    Checkpointer(str(tmp_path)).save(state, data_state={"cursor": 7},
                                     config_hash="x")
    side = tmp_path / "data_state_1.json"
    side.write_text(side.read_text()[:-4] + "GAR}")  # torn cursor
    _, template = _state_on(cfg, eight_devices[:4])
    with pytest.raises(RuntimeError, match="no restorable checkpoint"):
        Checkpointer(str(tmp_path)).restore(template, cfg)
    assert not (tmp_path / "restore_marker.json").exists()


def test_unknown_mesh_axes_refused(tmp_path, eight_devices, caplog):
    from homebrewnlp_tpu.train import Checkpointer
    cfg = _elastic_cfg()
    _, state = _state_on(cfg, eight_devices)
    ck = Checkpointer(str(tmp_path), max_to_keep=5)
    ck.save(state, config_hash="x")
    mpath = tmp_path / "manifest_0.json"
    doc = json.loads(mpath.read_text())
    doc["mesh"]["axes"] = {"foreign_axis": 8}
    mpath.write_text(json.dumps(doc))
    _, template = _state_on(cfg, eight_devices)
    with pytest.raises(RuntimeError, match="no restorable checkpoint"):
        Checkpointer(str(tmp_path), max_to_keep=5).restore(template, cfg)


def test_pre_elastic_manifest_still_restores(tmp_path, eight_devices):
    """Version-1 manifests (no mesh key) keep restoring — reshard detection
    simply skips."""
    from homebrewnlp_tpu.train import Checkpointer
    cfg = _elastic_cfg()
    _, state = _state_on(cfg, eight_devices, steps=1)
    Checkpointer(str(tmp_path)).save(state, config_hash="x")
    mpath = tmp_path / "manifest_1.json"
    doc = json.loads(mpath.read_text())
    doc.pop("mesh")
    doc["version"] = 1
    for e in doc["leaves"].values():
        e.pop("spec", None)
    mpath.write_text(json.dumps(doc))
    _, template = _state_on(cfg, eight_devices[:4])
    restored, _ = Checkpointer(str(tmp_path)).restore(template, cfg)
    assert int(restored.step) == 1
    assert not (tmp_path / "restore_marker.json").exists()


# -- supervisor: reshard-restore progress + jitter ----------------------------

def test_progress_signature_reads_restore_marker(tmp_path):
    assert supervise.progress_signature(str(tmp_path)) == (-1, 0)
    (tmp_path / "metrics.jsonl").write_text(
        json.dumps({"step": 4, "loss": 1.0}) + "\n")
    ck = tmp_path / "ckpt"
    ck.mkdir()
    (ck / "restore_marker.json").write_text(json.dumps({"count": 2}))
    assert supervise.progress_signature(str(tmp_path)) == (4, 2)
    # ordering: a reshard restore at a FROZEN step still compares as newer
    assert (4, 2) > (4, 1) and (5, 0) > (4, 2)


def test_reshard_restore_counts_as_crash_loop_progress(tmp_path):
    """Satellite regression: relaunches whose only on-disk evidence is a
    successful reshard restore (step counter frozen) must NOT be
    misclassified as a crash loop."""
    (tmp_path / "metrics.jsonl").write_text(
        json.dumps({"step": 4, "loss": 1.0}) + "\n")
    ck = tmp_path / "ckpt"
    ck.mkdir()
    launches = {"n": 0}

    def launch():
        launches["n"] += 1
        # every relaunch reshard-restores (marker count grows) but crashes
        # before advancing the step counter; the 4th completes
        (ck / "restore_marker.json").write_text(
            json.dumps({"count": launches["n"]}))
        return 0 if launches["n"] >= 4 else 1

    sup = supervise.Supervisor(
        launch, lambda: supervise.progress_signature(str(tmp_path)),
        max_failures_no_progress=2, backoff_base_s=0.0, backoff_jitter=0.0,
        sleep=lambda s: None, registry=MetricsRegistry())
    # without the marker component this aborts EXIT_CRASH_LOOP after 2
    assert sup.run() == 0
    assert launches["n"] == 4


def test_backoff_jitter_spreads_fleet_relaunches():
    sleeps = []
    outcomes = iter([1, 1, 0])
    progress = itertools.count()  # always advances: backoff stays at base
    sup = supervise.Supervisor(
        lambda: next(outcomes), lambda: next(progress),
        backoff_base_s=1.0, backoff_jitter=0.5, rng=lambda: 1.0,
        sleep=sleeps.append, registry=MetricsRegistry())
    assert sup.run() == 0
    assert sleeps == [1.5, 1.5]  # base * (1 + 0.5 * (2*1.0 - 1))
    sleeps2 = []
    outcomes = iter([1, 0])
    sup = supervise.Supervisor(
        lambda: next(outcomes), lambda: next(progress),
        backoff_base_s=1.0, backoff_jitter=0.5, rng=lambda: 0.0,
        sleep=sleeps2.append, registry=MetricsRegistry())
    assert sup.run() == 0
    assert sleeps2 == [0.5]  # the jitter really is two-sided


# -- fleet coordinator --------------------------------------------------------

def test_fleet_generation_resumes_from_newest_posting(tmp_path):
    f = supervise.FleetCoordinator(str(tmp_path), 0, 2)
    assert f.generation == 0
    f.post_exit(87)
    f.advance()
    f.post_exit(0)
    # a restarted supervisor rejoins PAST every posting in the directory —
    # its own or a peer's — so stale files can never read as live failures
    assert supervise.FleetCoordinator(str(tmp_path), 0, 2).generation == 2
    assert supervise.FleetCoordinator(str(tmp_path), 1, 2).generation == 2


def test_fresh_run_over_stale_fleet_dir_never_kills_children(tmp_path):
    """Code-review regression: a new run reusing last run's --fleet-dir
    must not interpret the old run's final crash postings as a live peer
    failure, and a returning supervisor clears its own stale tombstone so
    barriers wait for it again."""
    old = supervise.FleetCoordinator(str(tmp_path), 1, 2)
    old.post_exit(1)  # last run's rank 1 crashed...
    old.post_final(supervise.EXIT_CRASH_LOOP)  # ...and aborted for good
    fresh = supervise.FleetCoordinator(str(tmp_path), 0, 2,
                                       peer_timeout_s=0.2, poll_s=0.02)
    assert fresh.generation == 1  # past the stale posting
    assert fresh.peer_down() is None  # no spurious SIGTERM
    # until rank 1's supervisor is back, its standing tombstone exempts it
    # from barriers (degraded relaunch, no stall)
    fresh.post_exit(87)
    fresh.post_ready(87)
    t0 = time.monotonic()
    assert fresh.await_peers() == {0: 87}
    assert time.monotonic() - t0 < 0.2
    # rank 1's supervisor restarts: its coordinator clears the tombstone
    # (it is alive), so later barriers hold for it again
    back = supervise.FleetCoordinator(str(tmp_path), 1, 2)
    assert back.generation == 2  # joined past every posting
    assert fresh._final_ranks() == {}


def test_fleet_peer_down_ignores_clean_exits(tmp_path):
    f0 = supervise.FleetCoordinator(str(tmp_path), 0, 2, poll_s=0.01)
    f1 = supervise.FleetCoordinator(str(tmp_path), 1, 2, poll_s=0.01)
    assert f0.peer_down() is None
    f1.post_exit(0)  # peer finished cleanly: not a failure
    assert f0.peer_down() is None
    f1.advance()
    f1.post_exit(87)
    assert f0.peer_down() == 1


def test_fleet_barrier_times_out_degraded(tmp_path):
    f0 = supervise.FleetCoordinator(str(tmp_path), 0, 2,
                                    peer_timeout_s=0.3, poll_s=0.02)
    f0.post_exit(87)
    f0.post_ready(87)
    t0 = time.monotonic()
    seen = f0.await_peers()
    assert time.monotonic() - t0 >= 0.3
    assert seen == {0: 87}  # rank 1 never posted: relaunch degraded
    # the miss is remembered: the NEXT barrier does not re-pay the timeout
    f0.advance()
    f0.post_exit(1)
    f0.post_ready(1)
    t0 = time.monotonic()
    assert f0.await_peers() == {0: 1}
    assert time.monotonic() - t0 < 0.25
    # ...until the vanished rank posts again (rejoining PAST the newest
    # posting; the min-gen scan still credits it to the current barrier)
    f1 = supervise.FleetCoordinator(str(tmp_path), 1, 2)
    assert f1.generation >= f0.generation
    f1.post_ready(0)
    assert set(f0.await_peers()) == {0, 1}


def test_fleet_barrier_skips_tombstoned_rank(tmp_path):
    """A rank that left for good (crash-loop abort, budget exhaustion,
    clean completion) tombstones itself; later generations' barriers must
    not pay the peer timeout for it on EVERY relaunch."""
    f0 = supervise.FleetCoordinator(str(tmp_path), 0, 2,
                                    peer_timeout_s=10.0, poll_s=0.02)
    f1 = supervise.FleetCoordinator(str(tmp_path), 1, 2)
    f1.post_exit(supervise.EXIT_CRASH_LOOP)
    f1.post_final(supervise.EXIT_CRASH_LOOP)  # rank 1 aborts forever
    f0.advance()
    f0.advance()  # rank 0 is generations ahead, relaunching degraded
    f0.post_exit(1)
    f0.post_ready(1)
    t0 = time.monotonic()
    seen = f0.await_peers()
    assert time.monotonic() - t0 < 2.0  # no full-timeout stall
    assert seen == {0: 1}
    # the nonzero final is still a peer-down signal for the CURRENT child
    # generation where it was posted, not for later ones
    assert f0.peer_down() is None


def test_fleet_watcher_signals_live_child_exactly_once(tmp_path):
    """The watcher retries while the launcher has no live child yet (the
    Popen race), but stops the moment one SIGTERM is delivered — repeated
    signals would trip the child GraceController's second-signal
    escalation (forced exit 84, no grace checkpoint)."""
    f0 = supervise.FleetCoordinator(str(tmp_path), 0, 2, poll_s=0.02)
    f1 = supervise.FleetCoordinator(str(tmp_path), 1, 2)
    f1.post_exit(1)  # failed peer posting for the current generation
    calls = []

    def on_down(rank):
        calls.append(rank)
        return len(calls) >= 3  # first two polls: child not started yet

    w = f0.watch_peers(on_down)
    time.sleep(0.4)
    w.stop()
    assert calls == [1, 1, 1]  # retried through the race, then stopped


def test_fleet_lockstep_relaunch_in_process(tmp_path):
    """The full protocol with two in-process supervisors: rank 0's child
    crashes with EXIT_PEER_LOST; rank 1's watcher terminates its (still
    running) child; both hold the barrier, then relaunch together and
    complete."""
    events = []
    lock = threading.Lock()

    def log(e):
        with lock:
            events.append(e)

    f0 = supervise.FleetCoordinator(str(tmp_path), 0, 2,
                                    peer_timeout_s=20, poll_s=0.02)
    f1 = supervise.FleetCoordinator(str(tmp_path), 1, 2,
                                    peer_timeout_s=20, poll_s=0.02)
    term1 = threading.Event()

    def launch0():
        if f0.generation == 0:
            time.sleep(0.1)  # rank 1's child is definitely running
            log("r0 peer-lost")
            return supervise.EXIT_PEER_LOST
        log("r0 done")
        return 0

    def launch1():
        if f1.generation == 0:
            terminated = term1.wait(15)  # runs until the watcher kills it
            log("r1 terminated" if terminated else "r1 wait-timeout")
            return supervise.EXIT_PREEMPTED if terminated else 1
        log("r1 done")
        return 0

    p0, p1 = itertools.count(), itertools.count()
    sup0 = supervise.Supervisor(
        launch0, lambda: next(p0), backoff_jitter=0.0, sleep=lambda s: None,
        registry=MetricsRegistry(), fleet=f0)
    sup1 = supervise.Supervisor(
        launch1, lambda: next(p1), backoff_jitter=0.0, sleep=lambda s: None,
        registry=MetricsRegistry(), fleet=f1, terminate=term1.set)
    rcs = {}
    t0 = threading.Thread(target=lambda: rcs.update(r0=sup0.run()))
    t1 = threading.Thread(target=lambda: rcs.update(r1=sup1.run()))
    t0.start()
    t1.start()
    t0.join(30)
    t1.join(30)
    assert rcs == {"r0": 0, "r1": 0}
    assert "r1 terminated" in events  # the watcher really SIGTERMed it
    # lockstep: both relaunched exactly once, generations in sync
    assert sup0.restarts == 1 and sup1.restarts == 1
    assert f0.generation == f1.generation == 1
    # both generation-0 exits are on disk (87 + the graceful 83)
    g0 = {json.loads((tmp_path / f"exit_r{r}_g0.json").read_text())["rc"]
          for r in (0, 1)}
    assert g0 == {supervise.EXIT_PEER_LOST, supervise.EXIT_PREEMPTED}


def test_cli_inits_distributed_and_drills_dist_init_fault(tmp_path):
    """The production CLI path end to end: `main.py --run_mode train` with
    an explicit coordinator and num_processes=1 (the legacy --tpu pod
    slice) really initializes jax.distributed, and the fault plan is armed
    BEFORE the init so dist_init:fail@1 exercises the retry path."""
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    cfg = dict(model_mode="gpt", use_video=False, sequence_length=16,
               heads=4, features_per_head=32, depth=1, vocab_size=64,
               train_batch_size=2, memory_reduction_strategy="none",
               intermediate_feed_forward_multiplier_multiplier=0.5,
               block_config=[{"layer": ["norm-shift-scale",
                                        "feed_forward-in:relu"]}],
               model_path=str(tmp_path / "run"),
               dist_coordinator=f"127.0.0.1:{port}", dist_num_processes=1,
               fault_plan="dist_init:fail@1")
    cfg_path = tmp_path / "cfg.json"
    cfg_path.write_text(json.dumps(cfg))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="false")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "main.py"), "--model",
         str(cfg_path), "--run_mode", "train", "--steps", "2"],
        capture_output=True, text=True, timeout=240, env=env, cwd=REPO)
    assert out.returncode == 0, out.stderr[-3000:]
    # the injected first-attempt failure went through the retry path —
    # which also proves initialize() really engaged (a silently-skipped
    # init would never reach the dist_init fault site), and rc 0 proves
    # the second attempt's real jax.distributed.initialize succeeded
    assert "dist_init failed (attempt 1" in out.stderr, out.stderr[-3000:]
    rows = _rows(str(tmp_path / "run"))
    assert [r["step"] for r in rows] == [0, 1]


# -- THE chaos-multihost drill: two supervised OS processes -------------------

@pytest.mark.slow  # ~60s: two supervisors x two generations of children;
# the CI chaos-multihost job runs it explicitly
def test_fleet_drill_two_supervised_processes(tmp_path, eight_devices):
    """Acceptance drill (CI ``chaos-multihost``): injected host death
    (peer:die@step4) under two real per-host supervisor processes ends in a
    lockstep fleet relaunch, and every host's resumed loss sequence is
    bit-identical to an uninterrupted run."""
    steps = 10
    ref = tiny_config(model_path=str(tmp_path / "ref"),
                      use_checkpointing=True, steps_per_checkpoint=2)
    cli.train(ref, _args(steps))
    fleet_dir = str(tmp_path / "fleet")
    child = os.path.join(REPO, "tests", "elastic_child.py")
    sup_py = os.path.join(REPO, "tools", "supervise.py")
    procs = []
    for r in range(2):
        model = str(tmp_path / f"host{r}")
        cmd = [sys.executable, sup_py, "--model-path", model,
               "--rank", str(r), "--world-size", "2",
               "--fleet-dir", fleet_dir, "--peer-timeout", "120",
               "--backoff-jitter", "0", "--backoff-base", "0.1", "--",
               sys.executable, child, "--model-path", model,
               "--steps", str(steps), "--fault-plan", "peer:die@step4"]
        procs.append(subprocess.Popen(cmd, stdout=subprocess.PIPE,
                                      stderr=subprocess.STDOUT, text=True))
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=420)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for r, p in enumerate(procs):
        assert p.returncode == 0, f"rank{r} supervisor rc={p.returncode}:\n" \
                                  f"{outs[r][-3000:]}"
    ref_rows = {r["step"]: r["loss"] for r in _rows(str(tmp_path / "ref"))}
    for r in range(2):
        got = {row["step"]: row["loss"]
               for row in _rows(str(tmp_path / f"host{r}"))}
        assert set(got) == set(range(steps)), (r, sorted(got))
        for s in range(steps):
            assert ref_rows[s] == got[s], \
                f"host{r} loss diverged at step {s} after the fleet relaunch"
    # lockstep: every rank relaunched at least once — its newest exit
    # posting (never pruned) is for a generation past 0, and both ranks
    # tombstoned a clean completion
    fleet_files = os.listdir(fleet_dir)
    for r in range(2):
        assert any(f.startswith(f"exit_r{r}_g") and not f.endswith("_g0.json")
                   for f in fleet_files), (r, fleet_files)
        assert f"final_r{r}.json" in fleet_files, fleet_files
    # at least one host actually took the peer-lost path (the injected
    # death); the race where the watcher SIGTERMs a child mid-87-exit can
    # turn ONE of them into a plain crash, never both
    proms = "".join(
        (tmp_path / f"host{r}" / "supervisor_metrics.prom").read_text()
        for r in range(2))
    assert 'outcome="peer_lost"' in proms
