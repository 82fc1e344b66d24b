"""Chip smoke: the quickest proof that the system still starts on the TPU.

    python3 chip_smoke.py

One process, no options, the entry points a user calls, the flagship
(``configs/32big_mixer.json``) at its real width and depth, localised to one
chip exactly as the benchmark cell of that name (``utils.one_chip_config``):

1. **kernel** — forward and backward of ``ops/pallas_mixer.fused_mixer_block``
   at the ``32mixer_group`` tile shape, compiled by Mosaic (the lowered
   module must hold a TPU custom call), against ``mixer_chain_reference``.
2. **train** — ``homebrewnlp_tpu.main.train`` (what ``main.py --run_mode
   train --steps N`` calls: DeviceFeeder, async loop, metric writer) for
   ``TRAIN_UPDATES`` updates on byte-level TFRecords of a seeded toy language
   written under ``runs/chip_smoke/`` before JAX is initialised.  Every loss
   and grad norm finite, the mean of the last three losses below the first by
   ``TRAIN_LOSS_MARGIN``, one ``metrics.jsonl`` row per update, fed by the
   dataset files and not by the synthetic fallback, on a mesh that covers
   every device.
3. **serve** — what ``main.py --run_mode web_api`` builds
   (``main.start_web_api``) with ``serve_max_batch=4`` so ``BatchEngine`` and
   the KV pool are on the path, in the same process once the trainer's
   buffers are gone: concurrent HTTP completions from in-process threads, one
   of them streamed; all 200, token counts as asked, two identical greedy
   prompts give identical tokens, ``/healthz`` ok, ``hbnlp_serve_*`` series on
   ``/metrics``.  ``serve_aot_cache_dir`` stays ``""`` (the AOT round trip is
   broken on this toolchain, ROADMAP D9).

It refuses to run anywhere but on a TPU (exit 2, naming the platform found),
fails if any phase failed, and prints as the LAST line of stdout

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The compile cache is placed by ``utils.enable_compilation_cache``
(``JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``); a
second run in the same checkout reports persistent-cache hits.  The phase
functions take their sizes as arguments so ``tests/bringup_test.py`` can
rehearse the control flow at a toy width on the CPU; the script itself has no
switch.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import shutil
import statistics
import sys
import threading
import time
import typing
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
#: everything the smoke writes (``runs/`` is git-ignored)
SMOKE_DIR = os.path.join(REPO, "runs", "chip_smoke")

FLAGSHIP = "32big_mixer"
TRAIN_UPDATES = 16
#: mean(last three losses) must undercut the first loss by this much.  Set
#: from the chip (PERF.md "Findings", PR 21): the flagship's first loss is
#: 6.19 and updates 13-15 average 3.59 on the seeded toy language, a fall of
#: 2.60; the bound asks for well under half of it.
TRAIN_LOSS_MARGIN = 1.0
SERVE_LANES = 4
#: the ``32mixer_group`` tile: seq 256, key 256, heads 8; batch 8 gives the
#: same ``_block_rows`` (4) as the cell's batch 256 and two batch grid steps
KERNEL_SHAPE = dict(batch=8, seq=256, heads=8, key=256)
#: max |fused - reference| over max |reference|, per output: the two paths
#: round in different orders, ~3 bf16 ulps measured on the chip (1.2e-2)
KERNEL_REL_TOL = 4e-2

#: the smoke's dataset: N_FILES shards x RECORDS records x RECORD_TOKENS
#: bytes (8 windows of seq 512 per record: 64 flagship batches of 8)
DATA_FILES, DATA_RECORDS, DATA_RECORD_TOKENS, DATA_SEED = 4, 16, 4097, 21


class SmokeFailure(AssertionError):
    """A phase ran and its result is wrong."""


def check(ok: bool, message: str) -> None:
    if not ok:
        raise SmokeFailure(message)


def log(message: str) -> None:
    print(f"[chip_smoke] {message}", flush=True)


# -- measurement helpers ------------------------------------------------------

def compile_totals() -> dict:
    """What JAX compiled so far, from the counters of the program's compile
    log (``obs/compile_log.py``, installed by ``enable_compilation_cache``):
    seconds in the backend (an XLA compile, or the load of a persistent-
    cache entry), seconds tracing and lowering, and the persistent cache's
    hits and misses (a miss is an executable compiled and then stored)."""
    from homebrewnlp_tpu.obs.registry import REGISTRY

    def value(name, **labels):
        return REGISTRY.get(f"hbnlp_jax_{name}_total").value(**labels)

    return dict(
        backend_s=sum(value("build_seconds", cache=c)
                      for c in ("hit", "miss", "unstored")),
        trace_s=value("trace_seconds") + value("lower_seconds"),
        cache_hits=int(value("builds", cache="hit")),
        cache_misses=int(value("builds", cache="miss")))


def compiled_since(mark: dict) -> dict:
    return {k: round(v - mark[k], 2) if isinstance(v, float)
            else v - mark[k] for k, v in compile_totals().items()}


def device_memory() -> dict:
    """``bytes_in_use`` / ``peak_bytes_in_use`` of device 0 ({} where the
    backend keeps no statistics: the CPU)."""
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return {k: int(stats[k]) for k in ("bytes_in_use", "peak_bytes_in_use")
            if k in stats}


def write_dataset(directory: str) -> str:
    """The smoke's byte-level TFRecords, from a seed, in this process (no
    pool) and without touching a JAX backend; returns their glob.  Rewritten
    on every run: what feeds the trainer is what this checkout generates."""
    from homebrewnlp_tpu.data.synthetic import (learnable_tokens,
                                                write_text_tfrecords)
    shutil.rmtree(directory, ignore_errors=True)
    write_text_tfrecords(directory, DATA_FILES, DATA_RECORDS,
                         DATA_RECORD_TOKENS, seed=DATA_SEED,
                         draw=learnable_tokens)
    return os.path.join(directory, "*.tfrecord")


# -- phase 1: the pallas kernel -----------------------------------------------

def phase_kernel(batch: int, seq: int, heads: int, key: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from homebrewnlp_tpu.ops import pallas_interpret
    from homebrewnlp_tpu.ops.pallas_mixer import (fused_mixer_block,
                                                  mixer_chain_reference)
    interpret = pallas_interpret()
    dtype = jnp.bfloat16
    ks = jax.random.split(jax.random.key(0), 8)
    normal = jax.random.normal
    args = (normal(ks[0], (batch, seq, heads, key), dtype),
            (0.05 * normal(ks[1], (heads, seq, seq))).astype(dtype),
            (0.05 * normal(ks[2], (heads, seq, seq))).astype(dtype),
            (1 + 0.1 * normal(ks[3], (heads, key))).astype(dtype),
            (0.1 * normal(ks[4], (heads, key))).astype(dtype),
            (1 + 0.1 * normal(ks[5], (heads, key))).astype(dtype),
            (0.1 * normal(ks[6], (heads, key))).astype(dtype))
    dout = normal(ks[7], (batch, seq, heads, key), dtype)

    def out_and_grads(fn):
        def run(*a):
            out, vjp = jax.vjp(fn, *a)
            return (out,) + vjp(dout)
        return jax.jit(run)

    fused = out_and_grads(lambda *a: fused_mixer_block(*a, interpret))
    t0 = time.perf_counter()
    lowered = fused.lower(*args)
    n_calls = lowered.as_text().count("tpu_custom_call")
    # forward + backward kernel, compiled by Mosaic — or, on the CPU test
    # platform only, neither (the interpreter emits plain HLO)
    check(n_calls == (0 if interpret else 2),
          f"lowered module holds {n_calls} tpu_custom_call(s) with "
          f"interpret={interpret}: the kernel is not the path that ran")
    got = jax.block_until_ready(lowered.compile()(*args))
    seconds = time.perf_counter() - t0
    want = out_and_grads(mixer_chain_reference)(*args)
    names = ("out", "dx", "dbias1", "dbias2", "dscale1", "dshift1",
             "dscale2", "dshift2")
    rel = {}
    for name, g, w in zip(names, got, want):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        check(bool(np.isfinite(g).all()), f"kernel {name} is not finite")
        rel[name] = float(np.abs(g - w).max() / max(np.abs(w).max(), 1e-9))
        check(rel[name] <= KERNEL_REL_TOL,
              f"kernel {name} differs from mixer_chain_reference by "
              f"{rel[name]:.3g} of its scale (bound {KERNEL_REL_TOL})")
    return {"mosaic": not interpret, "tpu_custom_calls": n_calls,
            "seconds": round(seconds, 2),
            "max_rel_err": round(max(rel.values()), 5),
            "rel_err": {k: round(v, 5) for k, v in rel.items()}}


# -- phase 2: the trainer -----------------------------------------------------

def phase_train(cfg, n_updates: int, loss_margin: float) -> dict:
    from homebrewnlp_tpu import main as cli
    shutil.rmtree(cfg.model_path, ignore_errors=True)  # metrics.jsonl appends
    t0 = time.perf_counter()
    cli.train(cfg, argparse.Namespace(steps=n_updates, profile="",
                                      workers=None))
    wall = time.perf_counter() - t0
    return dict(check_train_run(cfg, n_updates, loss_margin),
                wall_s=round(wall, 1))


def check_train_run(cfg, n_updates: int, loss_margin: float) -> dict:
    """What the run left in ``<model_path>/metrics.jsonl``, checked."""
    from homebrewnlp_tpu.train.metrics import read_metric_rows
    with open(os.path.join(cfg.model_path, "metrics.jsonl")) as f:
        marker = json.loads(f.readline())  # the run-start marker
    rows = read_metric_rows(cfg.model_path)
    check(marker.get("data_source") == "dataset_files",
          f"the trainer was fed by {marker.get('data_source')!r}, not by "
          f"the dataset files at {cfg.dataset_configs}")
    mesh_size = math.prod(marker["mesh"].values())
    check(mesh_size == marker["n_devices"],
          f"mesh {marker['mesh']} covers {mesh_size} of "
          f"{marker['n_devices']} devices")
    check([r["step"] for r in rows] == list(range(n_updates)),
          f"metrics.jsonl holds steps {[r['step'] for r in rows]}, expected "
          f"one row for each of {n_updates} updates")
    losses = [r["loss"] for r in rows]
    grad_norms = [r["grad_norm"] for r in rows]
    check(all(math.isfinite(v) for v in losses + grad_norms),
          f"non-finite loss or grad norm: {losses} {grad_norms}")
    tail = sum(losses[-3:]) / 3
    check(tail < losses[0] - loss_margin,
          f"loss did not fall: first {losses[0]:.4f}, mean of the last "
          f"three {tail:.4f}, required margin {loss_margin}")
    return {"data_source": marker["data_source"], "mesh": marker["mesh"],
            "n_devices": marker["n_devices"], "updates": n_updates,
            "losses": [round(v, 4) for v in losses],
            "grad_norms": [round(v, 3) for v in grad_norms],
            # host clock between dispatches: the first covers the compile,
            # the median is the cadence once the in-flight window is full
            "first_step_s": round(rows[0]["step_seconds"], 2),
            "median_step_s": round(statistics.median(
                r["step_seconds"] for r in rows[1:] or rows), 4)}


# -- phase 3: the server ------------------------------------------------------

def _post(url: str, body: dict) -> typing.Tuple[int, typing.List[dict]]:
    """POST ``body``; the JSON answer, or every SSE ``data:`` event of a
    streamed one, as a list."""
    request = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(request, timeout=600) as response:
        if response.headers.get("Content-Type") != "text/event-stream":
            return response.status, [json.loads(response.read())]
        return response.status, [json.loads(line[len(b"data:"):])
                                 for line in response
                                 if line.startswith(b"data:")]


def _get(url: str) -> typing.Tuple[int, bytes]:
    with urllib.request.urlopen(url, timeout=60) as response:
        return response.status, response.read()


def phase_serve(cfg) -> dict:
    import numpy as np

    from homebrewnlp_tpu import main as cli
    from homebrewnlp_tpu.serve.engine import BatchEngine
    t0 = time.perf_counter()
    server = cli.start_web_api(cfg, argparse.Namespace(port=0, obs_port=0))
    try:
        engine = server.api.engine
        check(isinstance(engine, BatchEngine),
              f"serve_max_batch={cfg.serve_max_batch} but the server runs "
              f"{type(engine).__name__}: BatchEngine and the KV pool are not "
              f"on the path")
        start_s = time.perf_counter() - t0
        url = f"http://127.0.0.1:{server.server_address[1]}/token_completion"
        obs = f"http://127.0.0.1:{server._obs_server.server_address[1]}"
        rng = np.random.default_rng(0)
        room = cfg.sequence_length

        def prompt(n):
            return rng.integers(0, cfg.vocab_size, min(n, room // 4)).tolist()

        twin = prompt(16)
        bodies = [  # more requests than lanes: one waits in the queue
            dict(prompt=twin, temperature=0.0, response_len=24),
            dict(prompt=twin, temperature=0.0, response_len=24),
            dict(prompt=prompt(8), temperature=0.0, response_len=16,
                 stream=True),
            dict(prompt=prompt(40), response_len=8),
            dict(prompt=prompt(3), response_len=32),
        ]
        for body in bodies:
            body["response_len"] = min(body["response_len"],
                                       room - len(body["prompt"]))
        answers: typing.List[typing.Any] = [None] * len(bodies)

        def drive(i):
            try:
                answers[i] = _post(url, bodies[i])
            except Exception as e:  # noqa: BLE001 - reported by the check
                answers[i] = e

        t_req = time.perf_counter()
        threads = [threading.Thread(target=drive, args=(i,))
                   for i in range(len(bodies))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=900)
        requests_s = time.perf_counter() - t_req
        completions = []
        for body, answer in zip(bodies, answers):
            check(isinstance(answer, tuple) and answer[0] == 200,
                  f"request {body} answered {answer!r}")
            events = answer[1]
            final = events[-1]["completion"]
            n_prompt = len(body["prompt"])
            check(final[:n_prompt] == body["prompt"]
                  and len(final) - n_prompt == body["response_len"],
                  f"asked for {body['response_len']} tokens after a "
                  f"{n_prompt}-token prompt, got {len(final) - n_prompt}")
            if body.get("stream"):
                check(len(events) >= 3 and events[-1].get("done") is True,
                      f"streamed request answered {len(events)} event(s)")
                streamed = [t for e in events[:-1] for t in e["tokens"]]
                check(streamed == final[n_prompt:],
                      "streamed chunks do not add up to the final completion")
            completions.append(final)
        check(completions[0] == completions[1],
              "two identical greedy prompts gave different tokens")
        status, body = _get(obs + "/healthz")
        health = json.loads(body)
        check(status == 200 and health.get("status") == "ok",
              f"/healthz answered {status} {health.get('status')!r}")
        usage = (health.get("usage") or {}).get("totals") or {}
        check(usage.get("flops", 0) > 0,
              f"the usage meter priced no flops ({usage}): the serve "
              f"executables did not trace")
        _, metrics = _get(obs + "/metrics")
        series = {line.split(b"{")[0].split(b" ")[0].decode()
                  for line in metrics.splitlines()
                  if line.startswith(b"hbnlp_serve_")}
        for name in ("hbnlp_serve_ttft_seconds_count",
                     "hbnlp_serve_itl_seconds_count",
                     "hbnlp_serve_batch_size_count",
                     "hbnlp_serve_kv_blocks_free"):
            check(name in series, f"{name} missing from /metrics")
        memory = device_memory()
    finally:
        server.drain(10.0)
        server.server_close()
        server.api.wrapper.close()
    return {"engine": type(engine).__name__, "lanes": cfg.serve_max_batch,
            "start_s": round(start_s, 1), "requests": len(bodies),
            "requests_s": round(requests_s, 2),
            "generated_tokens": sum(b["response_len"] for b in bodies),
            "serve_series": len(series), "memory_live": memory}


# -- the script ---------------------------------------------------------------

def main() -> None:
    # the dataset first: generated before any JAX backend exists
    data_glob = write_dataset(os.path.join(SMOKE_DIR, "data"))

    import jax

    from homebrewnlp_tpu.utils import (enable_compilation_cache,
                                       one_chip_config)
    cache_dir = enable_compilation_cache()
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu":
        print(f"chip_smoke.py runs on a TPU only; JAX found platform "
              f"{device['platform']!r} ({device['kind']}, {device['count']} "
              f"device(s))", file=sys.stderr)
        raise SystemExit(2)
    cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    log(f"device {json.dumps(device)}; jax {jax.__version__}; compile cache "
        f"{cache_dir} ({cached} entries before this run)")

    def run(name, phase):
        mark, t0 = compile_totals(), time.perf_counter()
        result = phase()
        result["compile"] = compiled_since(mark)
        result["phase_s"] = round(time.perf_counter() - t0, 1)
        gc.collect()  # the phase's device buffers go with its last reference
        result["memory_after"] = device_memory()
        log(f"{name}: {json.dumps(result)}")

    run("kernel", lambda: phase_kernel(**KERNEL_SHAPE))
    dataset = [{"path": data_glob, "type": "text", "weight": 1}]
    train_cfg = one_chip_config(FLAGSHIP, dataset_configs=dataset,
                                model_path=os.path.join(SMOKE_DIR, "train"))
    run("train", lambda: phase_train(train_cfg, TRAIN_UPDATES,
                                     TRAIN_LOSS_MARGIN))
    # main.py's serving modes force batch 1 and train=False (main.main)
    serve_cfg = one_chip_config(FLAGSHIP, train=False, train_batch_size=1,
                                serve_max_batch=SERVE_LANES,
                                model_path=os.path.join(SMOKE_DIR, "serve"))
    run("serve", lambda: phase_serve(serve_cfg))
    total = compile_totals()
    log(f"persistent cache: {total['cache_hits']} hit(s), "
        f"{total['cache_misses']} miss(es) written; backend compile "
        f"{total['backend_s']:.1f}s, trace+lower {total['trace_s']:.1f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
