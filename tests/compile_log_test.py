"""The compile log (obs/compile_log.py): what JAX's own events give per
build, what the registry and the tracer get of it, what the real loop's
set-up spans hold, and the benchmark's reduction of the log to the six
`setup_*` metrics (benchmark/setup_time.py, loaded by path) on synthetic
logs.  CPU; the seconds here are never speeds."""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time
import types

import jax
import jax.numpy as jnp
import pytest

from homebrewnlp_tpu import main as cli
from homebrewnlp_tpu import obs
from homebrewnlp_tpu.obs import compile_log, exporter
from homebrewnlp_tpu.obs.compile_log import CompileLog, Record
from homebrewnlp_tpu.obs.registry import REGISTRY, MetricsRegistry
from homebrewnlp_tpu.obs.spans import SpanTracer, set_tracer, span

from .backend import tiny_config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
TRACE, LOWER, BUILD = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration")
READERS = {"setup_trace_s": "trace_s", "setup_lower_s": "lower_s",
           "setup_compile_s": "compile_s",
           "setup_cache_load_s": "cache_load_s",
           "setup_other_s": "other_s", "setup_programs": "programs"}


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def setup_time():
    module = _load(os.path.join(BENCH, "setup_time.py"), "setup_time")
    sys.modules["setup_time"] = module  # the readers import it by name
    yield module
    sys.modules.pop("setup_time", None)


@pytest.fixture
def log():
    """The process's log, installed; tests read what they add to it."""
    return compile_log.install()


def fresh(tag, weight=40):
    """A jitted function nobody has built: a new name every call, and
    enough work that its trace passes `MIN_TRACE_S`."""
    def body(x):
        for i in range(weight):
            x = jnp.sin(x) * (i + 1) + jnp.cos(x)
        return x
    body.__name__ = f"{tag}_{time.perf_counter_ns()}"
    return jax.jit(body), body.__name__


def since(log, mark):
    return [r for r in log.events() if r.t1 > mark]


# -- the listener on JAX's own events ----------------------------------------
def test_install_twice_registers_once(log):
    from jax._src import monitoring
    assert compile_log.install() is log
    assert monitoring.get_event_duration_listeners().count(
        log.on_duration) == 1
    assert monitoring.get_event_listeners().count(log.on_event) == 1
    assert log.installed_at <= time.perf_counter()


def test_nested_jit_gives_nested_trace_records(log, setup_time):
    inner, inner_name = fresh("inner")
    outer_name = f"outer_{time.perf_counter_ns()}"

    def outer(x):
        return inner(x) + inner(x * 2)
    outer.__name__ = outer_name
    mark = time.perf_counter()
    jax.jit(outer)(jnp.ones((8, 8))).block_until_ready()
    records = since(log, mark)
    traces = [r for r in records if r.kind == "trace"]
    (top,) = [r for r in traces if r.fun == outer_name]
    inside = [r for r in traces if r.fun == inner_name]
    assert inside and all(top.t0 <= r.t0 and r.t1 <= top.t1 for r in inside)
    nested = [r for r in traces if top.t0 <= r.t0 and r.t1 <= top.t1]
    assert setup_time.union_s((r.t0, r.t1) for r in nested) == \
        pytest.approx(top.t1 - top.t0, abs=1e-9)
    assert sum(r.t1 - r.t0 for r in nested) > top.t1 - top.t0
    kinds = [r.kind for r in records if outer_name in r.fun]
    assert kinds == ["trace", "lower", "build"]
    assert all(r.tid == records[0].tid for r in records)


def test_second_call_of_a_compiled_function_adds_no_record(log):
    fn, _ = fresh("steady")
    x = jnp.ones((4, 4))
    fn(x).block_until_ready()
    n = len(log.events())
    for _ in range(3):
        fn(x).block_until_ready()
    assert len(log.events()) == n


def test_changed_shape_adds_a_build_and_counts_a_recompile(log):
    fn, name = fresh("reshaped")
    counter = REGISTRY.get("hbnlp_recompiles_total")
    square, wide = jnp.ones((4, 4)), jnp.ones((2, 8))
    fn(square).block_until_ready()
    assert counter.value(fun=f"jit({name})") == 0
    before, mark = log.recompiles, time.perf_counter()
    fn(wide).block_until_ready()
    (build,) = [r for r in since(log, mark) if r.kind == "build"]
    assert build.fun == f"jit({name})" and build.again
    assert counter.value(fun=f"jit({name})") == 1
    assert log.recompiles == before + 1
    assert log.rebuilt(mark) == [build] and log.rebuilt(build.t1) == []
    assert "build of jit(reshaped_" in compile_log.describe(build)


@pytest.fixture
def cache_dir(tmp_path):
    """JAX's persistent cache on, in a directory of this test's own, and
    everything as it was afterwards."""
    from jax.experimental.compilation_cache import compilation_cache
    during = {"jax_compilation_cache_dir": str(tmp_path / "cache"),
              "jax_enable_compilation_cache": True,
              "jax_persistent_cache_min_compile_time_secs": 0.0,
              "jax_persistent_cache_min_entry_size_bytes": 0}
    before = {k: getattr(jax.config, k) for k in during}
    for k, v in during.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()
    yield str(tmp_path / "cache")
    for k, v in before.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_cache_miss_then_hit_and_unstored(log, cache_dir):
    def built(fn, x):
        mark = time.perf_counter()
        fn(x).block_until_ready()
        (build,) = [r for r in since(log, mark) if r.kind == "build"]
        return build

    x = jnp.ones((16, 16))
    # nothing is quick enough to keep: compiled, and not stored
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1e9)
    quick, _ = fresh("quick", weight=2)
    assert built(quick, x).cache == "unstored"
    assert not any("quick" in name for name in os.listdir(cache_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    fn, name = fresh("kept")
    first = built(fn, x)
    assert (first.cache, first.load_s, first.again) == ("miss", None, False)
    fn.clear_cache()  # this process forgets it; the directory does not
    second = built(fn, x)
    assert second.cache == "hit" and second.again
    assert 0 < second.load_s <= second.t1 - second.t0
    seconds = REGISTRY.get("hbnlp_jax_build_seconds_total")
    assert seconds.value(cache="hit") >= second.t1 - second.t0 - 1e-6
    assert REGISTRY.get("hbnlp_jax_builds_total").value(cache="miss") >= 1


def test_a_cache_that_is_off_reads_unstored(log, cache_dir):
    jax.config.update("jax_enable_compilation_cache", False)
    fn, _ = fresh("uncached")
    x = jnp.ones((4, 4))
    mark = time.perf_counter()
    fn(x).block_until_ready()
    (build,) = [r for r in since(log, mark) if r.kind == "build"]
    assert build.cache == "unstored" and build.load_s is None


# -- synthetic events into a log of its own ----------------------------------
def own_log(**kw):
    registry = MetricsRegistry()
    return CompileLog(registry=registry, **kw), registry


def test_ten_thousand_events_cost_under_50_ms():
    best = float("inf")
    for _ in range(3):  # the best of three: the machine is shared
        log, _ = own_log()
        t0 = time.perf_counter()
        for i in range(10_000):
            log.on_duration(TRACE, 1e-3, fun_name="f")
        best = min(best, time.perf_counter() - t0)
    assert best < 0.05, best
    assert len(log.events()) == compile_log.MAX_RECORDS


def test_short_traces_are_counted_and_not_kept():
    log, registry = own_log()
    for _ in range(100):
        log.on_duration(TRACE, 5e-6, fun_name="cached_inner")
    log.on_duration(TRACE, 2e-3, fun_name="outer")
    log.on_duration(LOWER, 5e-6, fun_name="jit(outer)")
    assert [(r.kind, r.fun) for r in log.events()] == [
        ("trace", "outer"), ("lower", "jit(outer)")]
    assert registry.get("hbnlp_jax_trace_seconds_total").value() == \
        pytest.approx(100 * 5e-6 + 2e-3)
    assert log.last().kind == "lower"


def test_the_log_is_bounded_and_ignores_other_events():
    log, _ = own_log(max_records=8)
    assert log.last() is None and log.events() == []
    for i in range(20):
        log.on_duration(BUILD, 0.01, fun_name=f"jit(f{i})")
    log.on_duration("/jax/compilation_cache/compile_time_saved_sec", 3.0)
    log.on_event("/jax/compilation_cache/tasks_using_cache")
    assert [r.fun for r in log.events()] == [f"jit(f{i})"
                                             for i in range(12, 20)]
    assert log.events(before=log.events()[3].t1) == log.events()[:4]


def test_cache_events_belong_to_the_build_they_fire_inside():
    log, registry = own_log()
    log.on_event("/jax/compilation_cache/cache_hits")
    log.on_duration("/jax/compilation_cache/cache_retrieval_time_sec", 0.25)
    time.sleep(0.002)
    # the build that saw the hit raised; the next began after it
    log.on_duration(BUILD, 1e-4, fun_name="jit(later)")
    assert log.last().cache == "unstored" and log.last().load_s is None
    # a hit inside the interval is this build's, and is used up by it
    log.on_event("/jax/compilation_cache/cache_hits")
    log.on_duration("/jax/compilation_cache/cache_retrieval_time_sec", 0.25)
    log.on_duration(BUILD, 10.0, fun_name="jit(loaded)")
    assert (log.last().cache, log.last().load_s) == ("hit", 0.25)
    log.on_event("/jax/compilation_cache/cache_misses")
    log.on_duration(BUILD, 10.0, fun_name="jit(stored)")
    assert (log.last().cache, log.last().load_s) == ("miss", None)
    log.on_duration(BUILD, 10.0, fun_name="jit(stored)")
    assert log.last().cache == "unstored" and log.last().again
    builds = registry.get("hbnlp_jax_builds_total")
    assert [builds.value(cache=c) for c in ("hit", "miss", "unstored")] == [
        1, 1, 2]
    text = registry.render()
    assert 'hbnlp_recompiles_total{fun="jit(stored)"} 1' in text
    assert 'hbnlp_jax_build_seconds_total{cache="hit"} 10' in text


# -- the tracer and the watchdog ---------------------------------------------
def test_records_land_in_trace_json_inside_the_open_setup_span(log, tmp_path):
    tracer = SpanTracer(mirror_jax=False)
    previous = set_tracer(tracer)
    try:
        fn, name = fresh("spanned")
        with span("setup/step_build"):
            assert tracer.open_spans() == {"MainThread": ["setup/step_build"]}
            fn(jnp.ones((4, 4))).block_until_ready()
        assert tracer.open_spans() == {}
    finally:
        set_tracer(previous)
    doc = json.load(open(tracer.export(str(tmp_path / "trace.json"))))
    events = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    (outer,) = [e for e in events if e["name"] == "setup/step_build"]
    mine = [e for e in events if e["name"].startswith("jax/")
            and name in e["args"]["fun"]]
    assert [e["name"] for e in mine] == ["jax/trace", "jax/lower",
                                         "jax/build"]
    for e in mine:
        assert e["tid"] == outer["tid"]
        assert outer["ts"] <= e["ts"]
        assert e["ts"] + e["dur"] <= outer["ts"] + outer["dur"] + 1.0
    assert mine[-1]["args"]["cache"] in ("hit", "miss", "unstored")
    assert "cache" not in mine[0]["args"]


def test_open_spans_nest_by_thread_and_empty_again():
    import threading
    tracer = SpanTracer(mirror_jax=False)
    inside = threading.Event()
    leave = threading.Event()

    def worker():
        with tracer.span("feed"):
            inside.set()
            leave.wait(10)

    thread = threading.Thread(target=worker, name="feeder-under-test")
    thread.start()
    assert inside.wait(10)
    with tracer.span("setup/init_or_restore"):
        with tracer.span("restore"):
            assert tracer.open_spans() == {
                "MainThread": ["setup/init_or_restore", "restore"],
                "feeder-under-test": ["feed"]}
    leave.set()
    thread.join(10)
    assert not thread.is_alive() and tracer.open_spans() == {}
    tracer.add("phase", 1.0, 2.0)  # a retroactive span opens nothing
    assert tracer.open_spans() == {} and tracer.event_count() == 4


def test_startup_stall_report_names_the_open_span_and_the_last_build(
        log, tmp_path):
    fn, name = fresh("stalled")
    fn(jnp.ones((4, 4))).block_until_ready()
    assert "no span tracer (obs_spans off)" in exporter.startup_position()
    tracer = SpanTracer(mirror_jax=False)
    previous = set_tracer(tracer)
    try:
        health = obs.Health(startup_stall_s=0.05)
        dog = obs.Watchdog(health, str(tmp_path), factor=10.0, poll_s=0.02,
                           registry=MetricsRegistry())
        with span("setup/init_or_restore"):
            dog.start()
            deadline = time.time() + 20
            while not dog.dumps and time.time() < deadline:
                time.sleep(0.02)
            dog.stop()
    finally:
        set_tracer(previous)
    assert dog.dumps
    reason = open(dog.dumps[0]).readline()
    assert "MainThread inside setup/init_or_restore" in reason
    assert f"JAX's last: build of jit({name})" in reason
    assert "compile/restore" not in reason


def test_train_writes_setup_spans_and_the_steps_build(log, tmp_path,
                                                      eight_devices):
    cfg = tiny_config(model_path=str(tmp_path), obs_spans=True,
                      use_checkpointing=False)
    cli.train(cfg, argparse.Namespace(steps=4, profile="", workers=None))
    doc = json.load(open(tmp_path / "trace.json"))
    events = sorted((e for e in doc["traceEvents"] if e["ph"] == "X"),
                    key=lambda e: e["ts"])
    assert events[0]["name"] == "setup/mesh"
    names = {e["name"] for e in events}
    assert {"setup/mesh", "setup/probe_batch", "setup/init_or_restore",
            "setup/step_build", "setup/metric_writer",
            "setup/pipeline"} <= names
    steps = [e for e in events if e["name"] == "step"]
    assert [e.get("args", {}).get("first") for e in steps] == [
        "True", None, None, None]
    (build,) = [e for e in events if e["name"] == "jax/build"
                and e["args"]["fun"] == "jit(step_fn)"]
    first = steps[0]
    assert first["ts"] <= build["ts"]
    assert build["ts"] + build["dur"] <= first["ts"] + first["dur"] + 1.0
    assert build["tid"] == first["tid"]
    init = [e for e in events if e["name"] == "setup/init_or_restore"][0]
    assert any(e["name"] == "jax/trace" and init["ts"] <= e["ts"]
               <= init["ts"] + init["dur"] for e in events)


# -- benchmark/setup_time.py on synthetic logs -------------------------------
def rec(kind, t0, t1, fun="f", cache=None):
    return Record(kind, fun, t0, t1, 1, cache)


SYNTHETIC = [
    rec("trace", 101.0, 101.5, "inner"),            # inside the outer trace
    rec("trace", 100.5, 103.0, "step_fn"),
    rec("lower", 102.5, 104.0, "jit(step_fn)"),     # laps half a second
    rec("build", 104.0, 110.0, "jit(step_fn)", "miss"),
    rec("build", 111.0, 111.25, "jit(init)", "hit"),
    rec("build", 112.0, 112.5, "jit(tiny)", "unstored"),
    rec("trace", 119.0, 121.0, "reference"),        # ends after the open
    rec("build", 130.0, 140.0, "jit(reference)", "miss"),
]


def test_partition_closes_and_cuts_at_the_window(setup_time):
    got = setup_time.partition(SYNTHETIC, installed_at=100.0,
                               window_open=120.0)
    assert got["trace_s"] == 2.5 and got["lower_s"] == 1.5
    assert got["compile_s"] == 6.5 and got["cache_load_s"] == 0.25
    assert got["programs"] == 3 and got["overlap_s"] == 0.5
    covered = (got["trace_s"] + got["lower_s"] + got["compile_s"]
               + got["cache_load_s"] - got["overlap_s"])
    assert covered == 10.25
    assert got["other_s"] + covered == pytest.approx(20.0, abs=1e-12)
    later = setup_time.partition(SYNTHETIC, 100.0, 150.0)
    assert later["programs"] == 4 and later["compile_s"] == 16.5
    empty = setup_time.partition([], 100.0, 120.0)
    assert empty["other_s"] == 20.0 and empty["cache_load_s"] == 0.0
    assert setup_time.union_s([(0, 2), (1, 3), (5, 6), (5.5, 5.75)]) == 4.0
    cut = [r for r in SYNTHETIC if r.t1 <= 120.0]
    holes = setup_time.gaps(cut, 100.0, 120.0, n=99)
    assert sum(g[0] for g in holes) == pytest.approx(got["other_s"])
    assert holes[0] == (7.5, 12.5, "build jit(tiny)", "the window")
    assert setup_time.gaps(cut, 100.0, 120.0, n=1) == holes[:1]


def a_run(window_open=120.0):
    return {"spans": [("dispatch", window_open + 0.5, 0.01),
                      ("batch_pick", window_open, 0.001),
                      ("drain", window_open + 9.0, 0.2)]}


@pytest.fixture
def synthetic_program(setup_time, monkeypatch):
    stand_in = types.SimpleNamespace(
        installed_at=100.0,
        events=lambda before=None: [r for r in SYNTHETIC if r.t1 <= before])
    monkeypatch.setattr(setup_time, "program_log", lambda: stand_in)


@pytest.mark.parametrize("name", sorted(READERS))
def test_each_reader_reads_its_number(name, setup_time, synthetic_program,
                                      capsys):
    reader = _load(os.path.join(BENCH, "layer_metrics", name + ".py"),
                   "reader_" + name)
    run = a_run()
    want = setup_time.partition(SYNTHETIC, 100.0, 120.0)[READERS[name]]
    assert reader.read(run) == want
    assert reader.read(run) == want  # kept on the run: one reading of the log
    err = capsys.readouterr().err
    assert err.count("[bench] setup_phases") == 1 and "overlap_s=0.5" in err
    assert "build jit(step_fn) 6.000s miss" in err
    manifest = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == name]
    assert entry["unit"] == reader.UNIT and entry["moves"] == "setup_s"
    assert entry["workloads"] == [w["name"] for w in manifest["workloads"]]


def test_a_program_without_the_log_reads_none(setup_time, monkeypatch):
    monkeypatch.setitem(sys.modules, "homebrewnlp_tpu.obs.compile_log", None)
    monkeypatch.delattr(obs, "compile_log")
    assert setup_time.program_log() is None
    for key in READERS.values():
        assert setup_time.read(a_run(), key) is None


def test_a_log_never_installed_or_a_run_without_spans_reads_none(
        setup_time, monkeypatch):
    monkeypatch.setattr(compile_log, "LOG", CompileLog(MetricsRegistry()))
    assert setup_time.program_log() is None
    monkeypatch.undo()
    compile_log.install()
    assert setup_time.program_log() is compile_log.LOG
    assert setup_time.read({"spans": []}, "other_s") is None


def test_the_real_log_partitions_a_real_setup(log, setup_time):
    fn, _ = fresh("measured")
    fn(jnp.ones((4, 4))).block_until_ready()
    t_open = time.perf_counter()
    after, _ = fresh("after_the_window")
    after(jnp.ones((4, 4))).block_until_ready()
    run = {"spans": [("dispatch", t_open, 0.01)]}
    got = setup_time.phases(run)
    total = t_open - log.installed_at
    covered = setup_time.union_s(
        (max(r.t0, log.installed_at), r.t1) for r in log.events(t_open))
    assert got["other_s"] + covered == pytest.approx(total, abs=1e-3)
    assert got["programs"] == sum(r.kind == "build"
                                  for r in log.events(t_open))
    assert all("after_the_window" not in r.fun for r in log.events(t_open))
