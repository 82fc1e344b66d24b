"""The benchmark's one command.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data that this file finds by name, starting from
`BENCHMARK.json` at the root of the checkout:

    workloads[].config   -> configs[].file              the configuration as run
    workloads[].traffic  -> benchmark/traffic/<traffic>.json   parameters + `runner`
    traffic.runner       -> benchmark/runners/<runner>.py      run(), check()
    config.benchmark.reference -> benchmark/reference/<name>.py  plain reference
    workloads[].name     -> benchmark/limits/<cell>.json       limits of `correct`
    per_layer[].name     -> benchmark/layer_metrics/<name>.py  read(run) -> number | None

The last line of standard output is the result; everything else a run says
goes to standard error.  See README.md beside this file.
"""
from __future__ import annotations

import time

_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import typing  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")
TRACE_DIR = os.path.join(HERE, "_trace")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
                "/jax/compilation_cache/cache_misses": "cache_misses"}


def log(message: str) -> None:
    print(f"[bench] {message}", file=sys.stderr, flush=True)


def load_module(directory: str, name: str):
    """`benchmark/<directory>/<name>.py` as a module (names may hold dots)."""
    path = os.path.join(HERE, directory, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{directory}_{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


class Spans:
    """The runner's host spans: kept in memory on the host clock and, in a
    traced run, mirrored into the profiler's trace under `bench/<name>`."""

    def __init__(self, mirror: bool):
        self.mirror = mirror
        self.closed: typing.List[typing.Tuple[str, float, float]] = []

    @contextlib.contextmanager
    def span(self, name: str):
        note = contextlib.nullcontext()
        if self.mirror:
            import jax
            note = jax.profiler.TraceAnnotation(f"bench/{name}")
        with note:
            start = time.perf_counter()
            try:
                yield
            finally:
                self.closed.append((name, start, time.perf_counter() - start))


class Counters:
    """Compilations and persistent-cache hits and misses, by phase."""

    def __init__(self):
        from jax import monitoring
        self.in_window = False
        self.counts = {"compiles_total": 0, "compiles_in_window": 0,
                       "cache_hits": 0, "cache_misses": 0}
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_):
        if event == COMPILE_EVENT:
            self.counts["compiles_total"] += 1
            self.counts["compiles_in_window"] += self.in_window

    def _event(self, event: str, **_):
        if event in CACHE_EVENTS:
            self.counts[CACHE_EVENTS[event]] += 1


def lift_compile_cache_cap() -> None:
    """No size cap on JAX's persistent cache: the flagship's update is a
    0.6 GB entry, which a capped cache (the chip tool's machine comes with
    192 MiB) never keeps, so that every run would compile.  Set through
    JAX's own variable, so before JAX is imported.  Where the cache lies is
    the program's to say, at its one site: `enable_compilation_cache()`
    takes `JAX_COMPILATION_CACHE_DIR` where that is set and
    `<checkout>/.jax_cache` where it is not."""
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"


def find_cell(manifest: dict, name: str) -> dict:
    """Everything the manifest and the data files say about one cell."""
    try:
        entry = next(w for w in manifest["workloads"] if w["name"] == name)
    except StopIteration:
        raise SystemExit(f"BENCHMARK.json has no workload {name!r}")
    conf_entry = next(c for c in manifest["configs"]
                      if c["name"] == entry["config"])
    config = load_json(ROOT, conf_entry["file"])
    traffic = load_json(HERE, "traffic", entry["traffic"] + ".json")

    def reports(metric: dict) -> bool:
        return name in metric.get("workloads", [name])

    return {
        "name": name, "chips": entry["chips"], "config": config,
        "traffic": traffic,
        "limits": load_json(HERE, "limits", name + ".json"),
        "reference": load_module("reference", config["benchmark"]["reference"]),
        "runner": load_module("runners", traffic["runner"]),
        "end_to_end": [m for m in manifest["end_to_end"] if reports(m)],
        "per_layer": [m for m in manifest["per_layer"] if reports(m)],
    }


def find_device(cell: dict):
    """(platform, kind, count, the kind's peaks) of the chips JAX shows, or
    None where they are not what the cell asks for: anything but a TPU, the
    wrong number of chips, a kind with no recorded peak."""
    import jax
    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    log(f"platform={platform} device_kind={kind!r} devices={len(devices)}")
    if platform != "tpu":
        log("no accelerator: this benchmark measures a TPU and nothing else")
        return None
    if len(devices) != cell["chips"]:
        log(f"the cell asks for {cell['chips']} chip(s), JAX shows "
            f"{len(devices)}")
        return None
    peaks = load_json(HERE, "peaks.json")
    if kind not in peaks:
        log(f"no peak is recorded for device kind {kind!r}")
        return None
    return platform, kind, len(devices), peaks[kind]


def main(argv=None) -> int:
    """Run one cell."""
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = parser.parse_args(argv)

    for path in (ROOT, HERE):
        if path not in sys.path:
            sys.path.insert(0, path)
    lift_compile_cache_cap()
    cell = find_cell(load_json(MANIFEST), args.workload)

    import jax
    from homebrewnlp_tpu.utils import enable_compilation_cache
    import_s = time.perf_counter() - _PROCESS_START
    log(f"cache_dir={enable_compilation_cache()}")
    found = find_device(cell)
    if found is None:
        return 3
    platform, kind, count, peak = found

    tracing = bool(args.trace)
    if tracing:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    spans = Spans(mirror=tracing)
    counters = Counters()
    marks: dict = {}
    window_note = contextlib.ExitStack()

    def open_window() -> float:
        if tracing:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
            window_note.enter_context(
                jax.profiler.TraceAnnotation("bench/window"))
        counters.in_window = True
        spans.closed.clear()
        marks["open"] = time.perf_counter()
        return marks["open"]

    def close_window() -> float:
        closed = time.perf_counter()
        counters.in_window = False
        if tracing:
            window_note.close()
            jax.profiler.stop_trace()
        return closed

    result = cell["runner"].run(cell, args.seed, args.seconds, spans, log,
                                (open_window, close_window))
    window_spans = list(spans.closed)
    setup_s = marks["open"] - _PROCESS_START
    log(f"setup_s={setup_s:.3f} of which import_s={import_s:.3f}")
    log(f"steps={result['steps']} window_s={result['window_s']:.4f} "
        f"first_loss={result['first_loss']} last_loss={result['last_loss']}")
    log(" ".join(f"{k}={v}" for k, v in counters.counts.items()))
    log(f"memory_peak_bytes={result['memory_peak_bytes']} of which "
        + " ".join(f"{k}={v}" for k, v in result["memory_stats"].items()
                   if k.startswith(("peak_", "bytes_limit"))))

    t_check = time.perf_counter()
    rows = cell["runner"].check(result, cell, args.seed, log)
    import compare
    ok = compare.correct(rows) and result["steps"] > 0
    if counters.counts["compiles_in_window"]:
        log("FAILED: a program compiled inside the measured window")
        ok = False
    log(f"comparison_s={time.perf_counter() - t_check:.3f}")

    device = {"platform": platform, "kind": kind, "count": count,
              "memory_peak_bytes": result["memory_peak_bytes"]}
    line: dict = {"correct": ok, "attempted": result["steps"], "failed": 0}
    if not tracing:
        values = {"tokens_per_s": result["tokens"] / result["window_s"],
                  "setup_s": setup_s}
        line["metrics"] = {m["name"]: {"value": values[m["name"]],
                                       "unit": m["unit"]}
                           for m in cell["end_to_end"]}
    else:
        line["metrics"], extra = traced_metrics(cell, result, window_spans,
                                                peak)
        device.update(extra.pop("device"))
        line["breakdown"] = extra
    line["device"] = device
    line["checks"] = {name: {"value": value, "limit": limit}
                      for name, value, limit in rows}
    for name, value, limit in rows:
        verdict = "ok" if value <= limit else "OVER"
        log(f"check {name}: {value:.6g} limit {limit:.6g} {verdict}")
    print(json.dumps(line), flush=True)
    return 0


def traced_metrics(cell: dict, result: dict, window_spans, peak: dict):
    """Per-layer metrics of a traced run, each from its own reader, and the
    device's busy time and breakdown."""
    import trace_reduce as tr
    t0 = time.perf_counter()
    loaded = tr.load_xplane(TRACE_DIR)
    spans_in_trace = tr.host_spans(loaded)
    start, end = tr.window_of(spans_in_trace)
    per_device = {name: tr.clip(events, start, end)
                  for name, events in tr.device_ops(loaded).items()}
    if not per_device or not any(per_device.values()):
        raise RuntimeError("the trace holds no device operation")
    busy = [tr.busy_ns(events) for events in per_device.values()]
    first = next(iter(per_device.values()))
    model = {k: v for k, v in cell["config"].items() if k != "benchmark"}
    run = {
        "result": result, "model": model, "peak": peak, "chips": cell["chips"],
        "spans": window_spans, "ops": first,
        "device": {"busy_s": sum(busy) / len(busy) / 1e9,
                   "window_s": (end - start) / 1e9},
    }
    metrics = {}
    for metric in cell["per_layer"]:
        reader = load_module("layer_metrics", metric["name"])
        value = reader.read(run)
        if value is not None:
            metrics[metric["name"]] = {"value": value, "unit": metric["unit"]}
    extra = {
        "device": run["device"],
        "device_ops": [list(x) for x in tr.top_ops(first)],
        "idle_gaps": [list(x) for x in tr.idle_gaps(
            first, spans_in_trace, start, end)],
    }
    log(f"trace_read_s={time.perf_counter() - t0:.3f} "
        f"device_events={len(first)}")
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    return metrics, extra


if __name__ == "__main__":
    sys.exit(main())
