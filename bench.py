"""Benchmark: tokens/sec/chip on the three reference workloads (BASELINE.md).

Primary metric (the driver's ``value``): the flagship 32big_mixer
architecture (full DSL/optimizer/dtype config, batch shrunk to fit one
chip), 5 timed windows of train steps, MEDIAN window.  Round 5 adds the two
other reference workload definitions (``32mixer_group`` throughput shape,
``32ctx_mixer`` long-context shape) as driver-captured rows in the same JSON
line — previously their numbers lived only in docs/perf — plus the
real-corpus numerics guard.

Prints ONE JSON line:

    {"metric": "tokens_per_sec_per_chip", "value": N, "unit": "tok/s/chip",
     "vs_baseline": R, ..., "workloads": {"32big_mixer": {...},
     "32mixer_group": {...}, "32ctx_mixer": {...}},
     "numerics_guard": {...}}

Each workload row is self-verifying: ``flops_per_step`` comes from XLA's
cost analysis of the exact compiled step (EXECUTED flops — includes the
recompute that the ``reversible_remat_blocks`` knob adds), and
``flops_per_step_algorithmic`` cost-analyzes the same step with the remat
knob off, so the line carries BOTH ``mfu`` (hardware utilization) and
``mfu_algorithmic`` (useful-work utilization) — VERDICT r4 item 3.  A
physically-possible mfu is <= 1.0; above it ``distorted`` is set (the timed
window closed before the device finished) and the throughput must not be
trusted.

``numerics_guard`` (VERDICT r4 item 9) replays the first N (default 300)
steps of the real-corpus 32ctx ACCEPTANCE setup
(``configs/32ctx_accept_10k.json`` — LR 0.002 / warmup 512, on the corpus
tools/build_corpus.py rebuilds) through the full CLI train path and asserts
the warmup trajectory: fresh-init loss > 6.5, loss below 4.5 by step 120,
final loss < 3.6 and finite (the round-5 10k-step run of this setup went
7.71 -> 3.45@100 -> 2.76-class@300).  The guard does NOT run
``32ctx_real_1chip.json`` (the reference's LR 0.01 at batch 8): at that
operating point grad norms climb after warmup and the loss regresses to
5-8, so a guard anchored there flakes across environments.

The MTF reference publishes no numbers (see BASELINE.md), so ``vs_baseline``
is computed against the first value this repo ever recorded
(bench_baseline.json, COMMITTED — 21040.8 tok/s on v5e) — i.e.
round-over-round speedup.

The async-dispatch PR adds two host-path fields (docs/performance.md): each
workload row carries ``host_blocked_s`` (median per-window wall time the
host spends blocked in the device->host loss pull that closes a timed
window, AFTER a block_until_ready excludes the window's remaining device
compute), and the flagship row carries ``compile_cache_hit``
(``warm_compile_s``: re-lower + re-compile the exact step after dropping
the in-process jit caches, with the persistent XLA cache warm — the
restart cost a user actually pays; ``hit`` flags whether it undercut half
the cold step compile, ``cold_compile_s``).

The low-precision PR (ISSUE 6) adds: per-workload ``compile_budget`` (+
top-level ``compile_ok``) evaluating ``compile_and_warmup_s`` against the
committed per-device budget in bench_compile_baseline.json (>20% over =
fail; tools/compile_ratchet.py runs the same check in CI);
``compile_cache_hit`` on every workload row (was flagship-only); a
``step``/``drain`` split inside every row's ``phases_s``; a complete
``flops_per_step``/``mfu`` under opaque pallas kernels (unfused-twin
lower bound, flagged ``flops_lower_bound``/``mfu_lower_bound`` — no more
``mfu: null``); and the ``quant`` probe on the 32mixer_group row
(docs/performance.md "Low-precision compute"): int8 step-time/MFU delta
plus the fixed-seed loss-trajectory accept gate.

The static-analysis cost-model PR (ISSUE 7) adds: ``hbm_peak_bytes`` on
EVERY workload row (max per-device ``memory_stats()`` peak, sampled right
after the timed windows so a failing telemetry/quant probe can no longer
drop it) and a ``resources`` validation hook — the graftcost prediction
(``predicted_peak_bytes`` + per-component breakdown, analysis/
cost_model.py) next to the measured peak and XLA's ``memory_analysis()``
figures, with ``prediction_error`` riding the BENCH trajectory so the
per-topology constants table (homebrewnlp_tpu/devices.py) is calibrated by
every TPU round.

The graftprof PR (ISSUE 8) adds a per-workload ``profile`` sub-dict: each
row auto-arms a ``jax.profiler`` window over ``HBNLP_BENCH_PROFILE_STEPS``
(default 5) steady-state steps — no hand-set ``profile_start`` needed —
and attributes the captured device time (obs/profile.py,
docs/observability.md "Profile attribution"): an ``ms_per_step``
decomposition into mxu + hbm + comm + idle, top-K ops, per-scope ms, the
comm fraction, and a ``reconcile`` block comparing each measured component
against graftcost's static alpha-beta / roofline estimate
(per-component ``prediction_error`` — how the constants table in
homebrewnlp_tpu/devices.py gets calibrated for *time*, the way the
``resources`` hook calibrates it for bytes).  Attribution drift is gated
by the committed per-device-kind baseline ``bench_profile_baseline.json``
(same shape + self-record semantics as the compile ratchet): any
decomposition fraction moving more than 0.15 absolute, or scope coverage
dropping more than 0.15, fails the row's ``baseline`` and the top-level
``profile_ok``.  The probe skips cleanly when the toolchain never writes
the profiler plugin directory.

The serving-SLO PR (ISSUE 9) adds a ``serving`` workload row: the REST
server comes up in-process on live (fresh-init) params, tools/graftload.py
drives it closed-loop with a fixed-seed prompt corpus, and the row records
client-measured e2e percentiles + goodput tok/s next to the server's own
TTFT / queue-wait / engine-busy histogram percentiles, the client-vs-server
reconciliation verdict, and ``serialization_overhead_s`` (client p50 e2e −
engine-busy p50 — the number the future continuous-batching PR must
shrink).  The core latency/goodput fields are recorded BEFORE the
server-scrape/reconcile sub-sections, so a probe failure cannot drop the
baseline comparison (same ordering discipline as ``hbm_peak_bytes``).
Latency/goodput drift is gated by the committed per-device-kind
``bench_serve_baseline.json`` (self-records on first contact, like the
compile budget): p50 e2e growing past 1.5x, or goodput dropping below
2/3x, fails the row's ``baseline`` and the top-level ``serve_ok``.
The token-level observability PR (ISSUE 14) extends the row with
``itl_p50/p95`` + ``decode_step_p50/p95`` (the server's per-token
histograms), ``prefill_stall_fraction`` (decode wall stalled on admission
prefill / total loop wall — the number the prefill-off-critical-path work
must shrink), and a contained streaming probe recording ``stream_ttft_s``
(client-measured first-SSE-chunk latency) plus the client-vs-server ITL
reconciliation; all three self-record into the baseline and ratchet in
``evaluate_serve_baseline``.

Env knobs (development / partial runs): ``HBNLP_BENCH_WORKLOADS`` is a
comma list or ``all`` (default); ``HBNLP_BENCH_GUARD_STEPS`` overrides the
guard length (0 disables); ``HBNLP_BENCH_QUANT=0`` skips the quant probe,
``HBNLP_BENCH_QUANT_DTYPE``/``_STEPS``/``_TOL`` tune it;
``HBNLP_BENCH_RESOURCES=0`` skips the cost-model prediction hook;
``HBNLP_BENCH_PROFILE=0`` skips the profile probe,
``HBNLP_BENCH_PROFILE_STEPS`` sizes its window; ``HBNLP_BENCH_SERVE=0``
skips the serving row, ``HBNLP_BENCH_SERVE_CONFIG``/``_REQUESTS``/
``_CONCURRENCY``/``_RESPONSE_LEN`` shape it.
"""
from __future__ import annotations

import json
import os
import time
import typing

import jax

BASELINE_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "bench_baseline.json")
# committed per-device compile+warmup budgets (seconds per workload); the
# compile ratchet fails any row >20% above its budget — the silent
# 79 s -> 135 s slide of r04 -> r05 must not repeat (tools/compile_ratchet.py
# enforces the same file in CI over the committed BENCH_r*.json lines)
COMPILE_BASELINE_FILE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "bench_compile_baseline.json")
#: tolerated compile_and_warmup_s ratio vs the committed budget
COMPILE_BUDGET_RATIO = 1.2
# committed per-device-kind device-time attribution baseline (graftprof):
# category fractions + scope coverage per workload; drift past the
# tolerance fails the row's profile baseline and the line's profile_ok
PROFILE_BASELINE_FILE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "bench_profile_baseline.json")
#: steps in the per-workload profile capture window
PROFILE_PROBE_STEPS = int(os.environ.get("HBNLP_BENCH_PROFILE_STEPS", "5"))

# committed per-device-kind serving baseline (p50 e2e latency + goodput);
# self-records on first contact like the compile budget, then drift past
# the ratios below fails the serving row's baseline and the line's serve_ok
SERVE_BASELINE_FILE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "bench_serve_baseline.json")
#: tolerated p50 e2e growth vs the committed serving baseline
SERVE_LATENCY_RATIO = 1.5
#: tolerated goodput floor vs the committed serving baseline
SERVE_GOODPUT_RATIO = 2.0 / 3.0
#: serving-row shape (env-overridable for development/smoke runs).  An
#: overridden shape never SELF-RECORDS a baseline: a smoke run on a fresh
#: device kind would otherwise commit its shape as the baseline and leave
#: every later default-shape run skipping the ratchet as "shape differs".
SERVE_SHAPE_OVERRIDDEN = any(
    os.environ.get(k) for k in
    ("HBNLP_BENCH_SERVE_CONFIG", "HBNLP_BENCH_SERVE_REQUESTS",
     "HBNLP_BENCH_SERVE_CONCURRENCY", "HBNLP_BENCH_SERVE_RESPONSE_LEN",
     "HBNLP_BENCH_SERVE_MAX_BATCH"))
SERVE_CONFIG = os.environ.get("HBNLP_BENCH_SERVE_CONFIG", "32big_mixer")
SERVE_REQUESTS = int(os.environ.get("HBNLP_BENCH_SERVE_REQUESTS", "24"))
SERVE_CONCURRENCY = int(os.environ.get("HBNLP_BENCH_SERVE_CONCURRENCY", "4"))
SERVE_RESPONSE_LEN = int(os.environ.get("HBNLP_BENCH_SERVE_RESPONSE_LEN",
                                        "16"))
#: decode lanes for the serving row's continuous-batching engine
#: (docs/observability.md "Continuous batching"); 1 = the pre-engine
#: serialized path (what the committed baselines were measured under)
SERVE_MAX_BATCH = int(os.environ.get("HBNLP_BENCH_SERVE_MAX_BATCH", "4"))
#: chunked-prefill A/B probe: when > 0, the serving row runs two extra
#: contained closed-loop drives over a mixed-length corpus — one with
#: serve_prefill_chunk_tokens=0 (monolithic admission prefill on the
#: decode thread) and one at this chunk size — and records itl_p95 +
#: prefill_stall_fraction for both arms under row["chunked_prefill"].
#: Deliberately NOT part of SERVE_SHAPE_OVERRIDDEN: the probe never
#: touches the main drive, so its presence must not skip the ratchet.
SERVE_CHUNK_TOKENS = int(os.environ.get("HBNLP_BENCH_SERVE_CHUNK", "0"))

# Peak table + MFU arithmetic shared with the LIVE utilization accounting
# (homebrewnlp_tpu/train/flops.py): bench's offline mfu and the run's
# /metrics mfu are the same math over the same cost-analyzed executable,
# so the two figures cannot drift.
from homebrewnlp_tpu.train.flops import peak_flops as _peak_flops  # noqa: E402
from homebrewnlp_tpu.train.flops import unfused_twin_flops  # noqa: E402

# The three reference workload definitions localised to one chip: ONE
# definition, shared with chip_smoke.py and the probe tools, so the smoke
# compiles the executables the cells ask the persistent cache for
# (homebrewnlp_tpu/utils: ONE_CHIP_COMMON / ONE_CHIP_WORKLOADS).
from homebrewnlp_tpu.utils import ONE_CHIP_COMMON as _COMMON  # noqa: E402
from homebrewnlp_tpu.utils import ONE_CHIP_WORKLOADS as WORKLOADS  # noqa: E402


_CACHE_PREWARMED = None


def _cache_prewarmed() -> bool:
    """True when the persistent XLA cache dir already held entries BEFORE
    this process compiled anything — probed once, on the first call (the
    first workload's own init would otherwise populate the dir and make
    every later check read true)."""
    global _CACHE_PREWARMED
    if _CACHE_PREWARMED is None:
        cache_dir = getattr(jax.config, "jax_compilation_cache_dir", None)
        _CACHE_PREWARMED = bool(
            cache_dir and os.path.isdir(os.path.expanduser(cache_dir))
            and os.listdir(os.path.expanduser(cache_dir)))
    return _CACHE_PREWARMED


def bench_workload(name: str, probe_loss: bool = False) -> dict:
    """Median-of-5 timed windows on one workload config; returns the row.

    ``probe_loss`` pins the fixed-seed 33-step comparison loss (the
    flagship's round-over-round numerics probe; schedule-rounding-sensitive,
    see BASELINE.md — the real guard is ``numerics_guard``)."""
    from homebrewnlp_tpu.obs.spans import SpanTracer
    from homebrewnlp_tpu.train import Trainer
    from homebrewnlp_tpu.utils import load_config, random_text_batch

    # local span tracer (NOT the process-ambient one): the per-phase wall
    # breakdown rides the JSON line as ``phases_s``
    tracer = SpanTracer(mirror_jax=False)
    t0_all = time.perf_counter()
    cache_prewarmed = _cache_prewarmed()  # probe BEFORE any compile
    with tracer.span("init"):
        cfg = load_config(f"configs/{name}.json", **_COMMON,
                          **WORKLOADS[name])
        trainer = Trainer(cfg)
        batch = random_text_batch(cfg)
        state = trainer.init(batch)
    rng = jax.random.key(1)

    # compile + XLA cost analysis of the exact step being timed (EXECUTED
    # flops: remat recompute included); timed separately so the
    # compile_cache_hit comparison below has an honest cold denominator.
    # On a warm-restart run the persistent cache serves THIS compile too —
    # cache_prewarmed (probed above) keeps the hit flag from reading a
    # fast "cold" compile as a cache miss
    t_cold = time.perf_counter()
    with tracer.span("compile"):
        cost = trainer.step_cost_analysis(state, batch)
    cold_compile_s = time.perf_counter() - t_cold
    flops_exec = float(cost.get("flops", 0.0))

    # algorithmic flops: the same step with the remat knob AND the fused
    # pallas kernel off — what the model's math costs as XLA-visible ops
    # (revnet's own backward replay is part of the algorithm and stays
    # counted; pallas kernels are opaque to cost analysis, so the unfused
    # chain is the only honest flop count)
    flops_algo = flops_exec
    kernel_opaque = bool(cfg.fused_mixer_block)
    if cfg.reversible_remat_blocks or kernel_opaque or cfg.blocked_causal_map:
        from homebrewnlp_tpu.optim import Optimizer
        # blocked_causal_map also resets to 0: the algorithmic count is the
        # CONVENTIONAL masked-einsum implementation, so mfu_algorithmic
        # stays comparable round-over-round while mfu (executed) shows the
        # carved-triangle saving
        cfg_algo = load_config(f"configs/{name}.json", **_COMMON,
                               **WORKLOADS[name],
                               reversible_remat_blocks=False,
                               fused_mixer_block=False,
                               blocked_causal_map=0)
        # params/opt-state/axes are identical either way: adopt them from
        # the measured trainer instead of re-initializing on device
        tr_algo = Trainer(cfg_algo)
        tr_algo.axes = trainer.axes
        tr_algo.optimizer = Optimizer(cfg_algo, trainer.axes)
        cost_algo = tr_algo.step_cost_analysis(state, batch)
        flops_algo = float(cost_algo.get("flops", 0.0)) or flops_exec

    # complete hardware-flops figure even under opaque pallas kernels
    # (BENCH_r05 reported flops_executed_partial + mfu null for the group
    # workload): the unfused twin's executed count is an explicit LOWER
    # BOUND on the fused step's (the kernels run the same math plus
    # in-kernel backward recompute — train/flops.py::unfused_twin_flops),
    # so the row carries a usable flops_per_step and a floor mfu, flagged
    # flops_lower_bound instead of silently incomplete
    flops_lower_bound = False
    if kernel_opaque:
        if cfg.reversible_remat_blocks or cfg.blocked_causal_map:
            # the twin keeps remat/blocked-map exactly as timed; flops_algo
            # above reset them, so it is NOT the right bound here
            flops_exec = max(flops_exec,
                             unfused_twin_flops(trainer, state, batch))
        else:
            # remat and blocked-map are off: the unfused twin IS the
            # cfg_algo analysis already paid for — no third lowering
            flops_exec = max(flops_exec, flops_algo)
        flops_lower_bound = True

    # fixed seed schedule: step i always uses fold_in(rng, i), so the probe
    # loss is reproducible round over round
    step_i = 0

    def run_steps(n, state):
        nonlocal step_i
        metrics = None
        for _ in range(n):
            # per-step dispatch span: phases_s separates dispatch ("step")
            # from the host pull closing each window ("drain"), so the
            # group path's compile/feed/step split is visible per workload
            with tracer.span("step"):
                state, metrics = trainer.step(state, batch,
                                              jax.random.fold_in(rng, step_i))
            step_i += 1
        return state, metrics

    # warmup: compile + let the device path reach steady state
    with tracer.span("warmup"):
        state, metrics = run_steps(3, state)
        float(metrics["loss"])
    compile_and_warmup_s = time.perf_counter() - t0_all

    # 5 windows of 10 steps.  Each window ends with block_until_ready on
    # the state and a HOST PULL of the loss scalar: a device->host transfer
    # of the final step's output cannot complete until the whole dependency
    # chain has.  The figure of record is the MEDIAN window; best + raw
    # windows expose the spread.  The fixed-seed comparison loss stays
    # pinned to step 33 (the figure rounds 1-2 recorded).
    n_steps = 10
    window_dts = []
    host_blocked = []
    loss_after = None
    pin_step = step_i + 3 * n_steps
    for _ in range(5):
        t0 = time.perf_counter()
        with tracer.span("window"):
            state, metrics = run_steps(n_steps, state)
            # host_blocked_s: wall time the host spends BLOCKED on the
            # device->host pull that ends the window — the async train loop
            # hides exactly this class of sync behind its in-flight window
            # (docs/performance.md), so the bench line makes it visible.
            # block_until_ready first: it waits for the window's remaining
            # DEVICE compute (which belongs to the window, not to host
            # blocking), so t_sync..t_end times only the transfer/sync
            jax.block_until_ready(state)
            t_sync = time.perf_counter()
            with tracer.span("drain"):
                window_loss = float(metrics["loss"])
            t_end = time.perf_counter()
        host_blocked.append(t_end - t_sync)
        window_dts.append(t_end - t0)
        if step_i == pin_step or loss_after is None and step_i >= pin_step:
            loss_after = window_loss
    dt = sorted(window_dts)[len(window_dts) // 2]
    best_dt = min(window_dts)
    tokens = cfg.train_batch_size * cfg.sequence_length * n_steps
    n_chips = max(1, len(jax.devices()))
    peak = _peak_flops(jax.devices()[0].device_kind)

    row = {
        "value": round(tokens / dt / n_chips, 2),
        "best": round(tokens / best_dt / n_chips, 2),
        "windows_tok_s": [round(tokens / w / n_chips, 1)
                          for w in window_dts],
        "ms_per_step": round(dt / n_steps * 1e3, 3),
        "flops_per_step": flops_exec,
        "flops_per_step_algorithmic": flops_algo,
        "mfu": None, "mfu_algorithmic": None,
        "compile_and_warmup_s": round(compile_and_warmup_s, 1),
        # median per-window host-blocked time (the loss pull closing each
        # window); the rest of the window is async-dispatched device work
        "host_blocked_s": round(sorted(host_blocked)[len(host_blocked) // 2],
                                4),
        # per-phase wall breakdown from the span tracer ("window" totals all
        # 5 timed windows; "init"/"compile"/"warmup" decompose the startup
        # envelope compile_and_warmup_s summarizes)
        "phases_s": {k: round(v, 3) for k, v in
                     tracer.phase_totals().items()},
    }
    # hbm_peak_bytes rides EVERY workload row, recorded immediately after
    # the timed windows and BEFORE the telemetry/quant probes below — a
    # probe failure (they donate `state` and can die on exotic toolchains)
    # previously dropped the whole prediction-vs-measured comparison row
    # (ISSUE 7 satellite).  None on backends without memory_stats (CPU).
    row["hbm_peak_bytes"] = _hbm_peak_bytes()

    _res_cache: list = []

    def static_train_resources():
        # ONE abstract re-trace (seconds) shared by the resources and
        # profile hooks below; lazy so either hook can be env-skipped
        if not _res_cache:
            from homebrewnlp_tpu.analysis import cost_model, trace_config
            traces = trace_config(cfg, name, steps=("train",))
            _res_cache.append(cost_model.config_resources(traces)
                              .get("train"))
        return _res_cache[0]

    # static cost-model validation hook (docs/static_analysis.md "Resource
    # cost model"): the predicted per-device peak next to the measured
    # memory_stats() peak and XLA's own memory analysis, so
    # prediction_error joins the BENCH trajectory and the constants table
    # in homebrewnlp_tpu/devices.py gets calibrated every TPU round
    if os.environ.get("HBNLP_BENCH_RESOURCES", "1") != "0":
        try:
            row["resources"] = _resource_prediction(
                trainer, row["hbm_peak_bytes"], static_train_resources())
        except Exception as e:  # noqa: BLE001 - must not kill the line
            row["resources"] = {"error": f"{type(e).__name__}: {e}"[:300]}
    # graftprof device-time attribution (module docstring; ISSUE 8): a
    # short auto-armed profiler window over the live state, parsed into
    # the ms_per_step decomposition + prediction_error vs graftcost.
    # Steps through trainer.step donate-and-return `state`, so the probe
    # hands the post-window state back for the probes below
    if os.environ.get("HBNLP_BENCH_PROFILE", "1") != "0":
        try:
            row["profile"], state = _profile_probe(
                name, cfg, trainer, state, batch, static_train_resources)
        except Exception as e:  # noqa: BLE001 - must not kill the line
            row["profile"] = {"error": f"{type(e).__name__}: {e}"[:300]}
    if kernel_opaque:
        # flops_per_step is the unfused twin's LOWER BOUND (see above) —
        # the flags describe the flop count itself, peak table or not
        row["flops_executed_partial"] = True  # r05-compatible flag
        row["flops_lower_bound"] = flops_lower_bound
    if peak and flops_exec:
        # under opaque pallas kernels mfu inherits the lower bound — a
        # floor, flagged, never null
        row["mfu"] = round(flops_exec * n_steps / dt / (peak * n_chips), 4)
        if kernel_opaque:
            row["mfu_lower_bound"] = True
        row["mfu_algorithmic"] = round(
            flops_algo * n_steps / dt / (peak * n_chips), 4)
    if probe_loss:
        row["loss_after_n_steps"] = round(loss_after, 4)
        row["n_steps_total"] = step_i
    # compile_cache_hit (EVERY workload since the compile-ratchet PR; it
    # was flagship-only before): drop the in-process jit caches and
    # re-lower + re-compile the exact step.  bench.main enables the
    # persistent XLA cache, and the cold compile above just populated it,
    # so this measures the warm-restart path: tracing/lowering re-runs, the
    # XLA compile is served from disk.  A warm second bench run shows the
    # same effect in compile_and_warmup_s itself.
    t_warm = time.perf_counter()
    jax.clear_caches()
    tr_warm = Trainer(cfg)
    tr_warm.axes = trainer.axes
    tr_warm.optimizer = trainer.optimizer
    tr_warm.step_cost_analysis(state, batch)
    warm_s = time.perf_counter() - t_warm
    # hit compares against the COLD lower+compile of the same step (not
    # the whole init+warmup envelope, which would flatter a cold cache).
    # When the cache was prewarmed, cold_compile_s was ITSELF served
    # from disk (warm ~= "cold"), which is a hit, not a miss.
    row["compile_cache_hit"] = {
        "warm_compile_s": round(warm_s, 1),
        "cold_compile_s": round(cold_compile_s, 1),
        "cache_prewarmed": cache_prewarmed,
        "hit": bool(cache_prewarmed or warm_s < 0.5 * cold_compile_s),
    }
    if probe_loss:
        if os.environ.get("HBNLP_BENCH_TELEMETRY", "1") != "0":
            # device-telemetry overhead probe (docs/observability.md): the
            # same workload with in-graph numerics armed.  Acceptance:
            # tokens/s within 2% of the base row, and the telemetry graph's
            # cost-analyzed flops within 1% of flops_per_step (the norm
            # reductions are O(params), noise next to the matmuls) — both
            # ratios ride the line.  LAST probe in the row: its step calls
            # donate `state`
            try:
                row["telemetry"] = _telemetry_probe(
                    name, trainer, state, batch, flops_exec, row["value"])
            except Exception as e:  # noqa: BLE001 - must not kill the line
                row["telemetry"] = {"error": f"{type(e).__name__}: {e}"[:300]}
    if (name == "32mixer_group"
            and os.environ.get("HBNLP_BENCH_QUANT", "1") != "0"):
        # int8 accept gate for the grouped-mixer chain (ISSUE 6): the
        # quantized step's tok/s + ms_per_step delta vs this base row, and
        # a numerics_guard-style fixed-seed loss-trajectory comparison.
        # LAST probe in the row: its step calls donate `state`
        try:
            row["quant"] = _quant_probe(name, trainer, state, batch,
                                        flops_algo, row["value"],
                                        row["ms_per_step"])
        except Exception as e:  # noqa: BLE001 - must not kill the line
            row["quant"] = {"error": f"{type(e).__name__}: {e}"[:300]}
    return row


def _hbm_peak_bytes():
    """Max per-device ``memory_stats()`` peak, or None where the backend
    exposes none (CPU).  Never raises — the field must survive any probe."""
    try:
        peaks = []
        for d in jax.local_devices():
            stats = d.memory_stats() or {}
            peak = stats.get("peak_bytes_in_use", stats.get("bytes_in_use"))
            if peak is not None:
                peaks.append(int(peak))
        return max(peaks) if peaks else None
    except Exception:  # noqa: BLE001
        return None


def _resource_prediction(trainer, measured_peak, res):
    """Static cost-model prediction for the workload's exact config
    (``res`` = the shared ``static_train_resources()`` StepResources) +
    the compiled step's XLA memory analysis, with ``prediction_error``
    vs the measured device peak when available."""
    out = {}
    if res is not None:
        out["predicted_peak_bytes"] = int(res.hbm["peak"])
        out["predicted_hbm"] = {k: int(v) for k, v in res.hbm.items()}
        out["verdict"] = res.verdict
    compiled = getattr(trainer, "_compiled", None)
    if compiled is not None:
        try:
            ma = compiled.memory_analysis()
            if ma is not None:
                out["xla_temp_bytes"] = int(ma.temp_size_in_bytes)
                out["xla_argument_bytes"] = int(ma.argument_size_in_bytes)
        except Exception:  # noqa: BLE001 - optional on some backends
            pass
    if measured_peak and out.get("predicted_peak_bytes"):
        out["measured_peak_bytes"] = int(measured_peak)
        out["prediction_error"] = round(
            out["predicted_peak_bytes"] / measured_peak - 1.0, 4)
    return out


def _profile_probe(name: str, cfg, trainer, state, batch, static_res):
    """One auto-armed capture window (docs/observability.md "Profile
    attribution"): profile ``PROFILE_PROBE_STEPS`` steps of the workload's
    live state, dump the AOT executable's op->scope sidecar, attribute the
    device time, and reconcile the measured mxu/hbm/comm split against
    graftcost's static estimate (``static_res`` = the shared lazy
    ``static_train_resources`` callable).  Returns ``(profile row,
    state)`` — the steps donate state buffers, so the caller must adopt
    the new state; once the window has stepped, parse/attribution
    failures are contained in the row's ``error`` field rather than
    raised, so the donated-and-returned state is never lost to the
    caller's except handler.  Skips cleanly (``skipped`` field) when the
    toolchain writes no profiler plugin directory."""
    import shutil
    import tempfile

    from homebrewnlp_tpu.obs import profile as profile_mod

    n = PROFILE_PROBE_STEPS
    rng = jax.random.key(5)
    tmp = tempfile.mkdtemp(prefix=f"bench_prof_{name}_")
    stepped = False
    try:
        try:
            jax.profiler.start_trace(tmp)
            try:
                for i in range(n):
                    state, metrics = trainer.step(state, batch,
                                                  jax.random.fold_in(rng, i))
                jax.block_until_ready(state)
                stepped = True
            finally:
                jax.profiler.stop_trace()
            profile_mod.write_op_map_for(trainer, tmp)
            summary = profile_mod.capture_summary(tmp, n_steps=n)
        except Exception as e:  # noqa: BLE001 - see docstring
            if not stepped:
                raise
            return {"error": f"{type(e).__name__}: {e}"[:300]}, state
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    if summary is None:
        return {"skipped": "no profiler trace written "
                           "(plugin directory absent)"}, state
    steps = max(1, n)
    scopes_ms = {k: round(v * 1e3 / steps, 4)
                 for k, v in list(summary.scopes_s.items())[:8]}
    row = {
        "n_steps": n,
        "ms_per_step": summary.decomposition_ms_per_step,
        "fractions": summary.fractions,
        "comm_fraction": summary.fractions.get("comm", 0.0),
        "attributed_category_frac": summary.attributed_category_frac,
        "attributed_scope_frac": summary.attributed_scope_frac,
        "top_ops": summary.top_ops[:5],
        "scopes_ms": scopes_ms,
        "collectives_s": summary.collectives_s,
    }
    # measured vs graftcost static estimate — per-component
    # prediction_error; null on CPU/unknown kinds, where the constants
    # table makes no time claims
    try:
        from homebrewnlp_tpu.analysis import cost_model
        from homebrewnlp_tpu.analysis.graph_rules import intended_mesh
        res = static_res()
        pred = None
        kind = jax.devices()[0].device_kind
        if res is not None:
            pred = cost_model.step_static_times(
                res, dict(intended_mesh(cfg).shape), kind)
        row["reconcile"] = profile_mod.reconcile(summary, pred)
        row["prediction_device"] = kind if pred is not None else None
    except Exception as e:  # noqa: BLE001 - reconcile is best-effort
        row["reconcile"] = {"error": f"{type(e).__name__}: {e}"[:300]}
    return row, state


def _telemetry_probe(name: str, trainer, state, batch, flops_base: float,
                     base_tok_s: float) -> dict:
    """Timed windows of the telemetry-enabled step (telemetry_interval=1,
    anomaly_policy=skip_step — the most expensive configuration: sentinels,
    norms AND the in-graph update mask).  Returns tokens/s, the ratio vs
    the base row, and the flops agreement with the base cost analysis."""
    from homebrewnlp_tpu.optim import Optimizer
    from homebrewnlp_tpu.train import Trainer
    from homebrewnlp_tpu.utils import load_config

    cfg_tel = load_config(f"configs/{name}.json", **_COMMON,
                          **WORKLOADS[name], telemetry_interval=1,
                          anomaly_policy="skip_step")
    tr = Trainer(cfg_tel)
    tr.axes = trainer.axes
    tr.optimizer = Optimizer(cfg_tel, trainer.axes)
    cost = tr.step_cost_analysis(state, batch)
    flops_tel = float(cost.get("flops", 0.0))
    rng = jax.random.key(2)
    for i in range(3):  # warmup the telemetry executable
        state, metrics = tr.step(state, batch, jax.random.fold_in(rng, i))
    float(metrics["loss"])
    n_steps, dts = 10, []
    for w in range(3):
        t0 = time.perf_counter()
        for i in range(n_steps):
            state, metrics = tr.step(state, batch,
                                     jax.random.fold_in(rng, 100 + w * 16 + i))
        jax.block_until_ready(state)
        float(metrics["loss"])
        dts.append(time.perf_counter() - t0)
    dt = sorted(dts)[len(dts) // 2]
    tokens = cfg_tel.train_batch_size * cfg_tel.sequence_length * n_steps
    tok_s = tokens / dt / max(1, len(jax.devices()))
    return {
        "value": round(tok_s, 2),
        "ratio_vs_base": round(tok_s / base_tok_s, 4) if base_tok_s else None,
        "flops_per_step": flops_tel,
        "flops_ratio_vs_base": (round(flops_tel / flops_base, 4)
                                if flops_base else None),
    }


#: quant probe knobs (env-overridable for development runs)
QUANT_PROBE_BLOCKS = ("bottleneck_group_linear",)
QUANT_GATE_STEPS = int(os.environ.get("HBNLP_BENCH_QUANT_STEPS", "30"))
QUANT_GATE_REL_TOL = float(os.environ.get("HBNLP_BENCH_QUANT_TOL", "0.1"))


def evaluate_quant_gate(base_losses, quant_losses,
                        rel_tol: float = QUANT_GATE_REL_TOL) -> dict:
    """Pure accept-gate evaluation (unit-testable without a chip), in the
    numerics_guard mold: the quantized trajectory must be finite, must
    train (final < first), and must track the high-precision trajectory
    within ``rel_tol`` relative deviation at every compared step.  A False
    verdict is a measured REJECT — the knob stays default-off and the
    numbers ride the line either way (repo perf culture)."""
    if not base_losses or len(base_losses) != len(quant_losses):
        return {"pass": False, "error": "trajectory length mismatch"}
    finite = all(l == l and abs(l) != float("inf")
                 for l in base_losses + quant_losses)
    devs = [abs(q - b) / max(abs(b), 1.0)
            for b, q in zip(base_losses, quant_losses)]
    max_dev = max(devs) if devs else 0.0
    trains = quant_losses[-1] < quant_losses[0]
    return {"pass": bool(finite and trains and max_dev <= rel_tol),
            "finite": bool(finite),
            "trains": bool(trains),
            "max_rel_dev": round(max_dev, 4),
            "rel_tol": rel_tol,
            "steps": len(base_losses),
            "loss_first": round(quant_losses[0], 4),
            "loss_final": round(quant_losses[-1], 4),
            "loss_final_base": round(base_losses[-1], 4)}


def _loss_trajectory(cfg, batch, n_steps: int):
    """Fresh-init fixed-seed loss trajectory (one float per step) — the
    deterministic comparison arm of the quant accept gate.  Same init seed
    and rng schedule for both arms, so the only difference between the
    base and quant trajectories is the quantized forward itself."""
    from homebrewnlp_tpu.train import Trainer
    tr = Trainer(cfg)
    state = tr.init(batch)
    rng = jax.random.key(3)
    losses = []
    for i in range(n_steps):
        state, metrics = tr.step(state, batch, jax.random.fold_in(rng, i))
        losses.append(float(metrics["loss"]))
    return losses


def _quant_probe(name: str, trainer, state, batch, flops_algo: float,
                 base_tok_s: float, base_ms: float) -> dict:
    """The int8 (or fp8, HBNLP_BENCH_QUANT_DTYPE) grouped-mixer probe:

    1. timed windows of the quantized step against the SAME live state —
       tok/s, ms_per_step, and their delta vs the base row (the mfu delta
       follows from ms_per_step: both rows share flops_algorithmic);
    2. the accept gate: two fresh-init fixed-seed loss trajectories (quant
       off / on) compared by ``evaluate_quant_gate``.
    """
    from homebrewnlp_tpu.optim import Optimizer
    from homebrewnlp_tpu.train import Trainer
    from homebrewnlp_tpu.utils import load_config

    qdtype = os.environ.get("HBNLP_BENCH_QUANT_DTYPE", "int8")
    quant_over = dict(quant_blocks=list(QUANT_PROBE_BLOCKS),
                      quant_dtype=qdtype)
    cfg_q = load_config(f"configs/{name}.json", **_COMMON, **WORKLOADS[name],
                        **quant_over)
    tr = Trainer(cfg_q)
    tr.axes = trainer.axes
    tr.optimizer = Optimizer(cfg_q, trainer.axes)
    tr.step_cost_analysis(state, batch)  # compile (kept AOT executable)
    rng = jax.random.key(4)
    for i in range(3):  # warmup
        state, metrics = tr.step(state, batch, jax.random.fold_in(rng, i))
    float(metrics["loss"])
    n_steps, dts = 10, []
    for w in range(3):
        t0 = time.perf_counter()
        for i in range(n_steps):
            state, metrics = tr.step(state, batch,
                                     jax.random.fold_in(rng, 100 + w * 16 + i))
        jax.block_until_ready(state)
        float(metrics["loss"])
        dts.append(time.perf_counter() - t0)
    dt = sorted(dts)[len(dts) // 2]
    tokens = cfg_q.train_batch_size * cfg_q.sequence_length * n_steps
    n_chips = max(1, len(jax.devices()))
    tok_s = tokens / dt / n_chips
    peak = _peak_flops(jax.devices()[0].device_kind)
    row = {
        "quant_dtype": qdtype,
        "quant_blocks": list(QUANT_PROBE_BLOCKS),
        "value": round(tok_s, 2),
        "ms_per_step": round(dt / n_steps * 1e3, 3),
        "ratio_vs_base": round(tok_s / base_tok_s, 4) if base_tok_s else None,
        "ms_delta_vs_base": (round(dt / n_steps * 1e3 - base_ms, 3)
                             if base_ms else None),
    }
    if peak and flops_algo:
        # same algorithmic flop count as the base row by construction, so
        # the two mfu_algorithmic figures ARE the MFU delta
        row["mfu_algorithmic"] = round(
            flops_algo * n_steps / dt / (peak * n_chips), 4)
    gate_steps = QUANT_GATE_STEPS
    if gate_steps > 0:
        cfg_base = load_config(f"configs/{name}.json", **_COMMON,
                               **WORKLOADS[name])
        row["accept"] = evaluate_quant_gate(
            _loss_trajectory(cfg_base, batch, gate_steps),
            _loss_trajectory(cfg_q, batch, gate_steps))
    return row


def _stream_delta_reconcile(client: dict, pre_text: str,
                            post_text: str) -> dict:
    """Reconcile the streaming probe's CLIENT percentiles against the
    server histograms' pre/post scrape DELTA — exactly the probe's own
    requests, even when the cumulative series is dominated by the main
    (queued, non-streamed) drive.  Same per-series tolerance as graftload:
    ``bucket_width_at(p50) + max(0.05, 0.25 * p50)``."""
    import math

    import graftload

    from homebrewnlp_tpu.obs.registry import bucket_quantile, bucket_width_at
    pre = graftload.parse_prom(pre_text)
    post = graftload.parse_prom(post_text)
    arms: dict = {}
    for key, series in (("itl", "hbnlp_serve_itl_seconds"),
                        ("ttft", "hbnlp_serve_ttft_seconds")):
        cp = (client.get(f"{key}_s") or {}).get("p50")
        snap_post = graftload.histogram_snapshot(post, series)
        if cp is None or snap_post is None:
            continue
        snap_pre = graftload.histogram_snapshot(pre, series)
        counts = list(snap_post["counts"])
        if (snap_pre is not None
                and snap_pre["buckets"] == snap_post["buckets"]):
            counts = [b - a for a, b in zip(snap_pre["counts"], counts)]
        sp = bucket_quantile(snap_post["buckets"], counts, 0.5)
        if sp is None:
            continue
        width = bucket_width_at(snap_post["buckets"], sp)
        tol = (width if width != math.inf else 0.0) + max(0.05, 0.25 * sp)
        arms[key] = {"client_p50_s": round(cp, 6),
                     "server_p50_s": round(sp, 6),
                     "abs_diff_s": round(abs(cp - sp), 6),
                     "tolerance_s": round(tol, 6),
                     "within_tolerance": bool(abs(cp - sp) <= tol)}
    return arms


def bench_serving() -> dict:
    """The ``serving`` workload row (docs/observability.md "Serving SLOs"):
    bring the REST server up in-process on live fresh-init params, drive it
    with tools/graftload.py (closed loop, fixed-seed corpus), and record
    client-side latency/goodput next to the server's own SLO histograms.

    Field-ordering contract: the core fields the baseline gate consumes
    (``e2e_p50_s``, ``goodput_tok_s``) are written into the row BEFORE the
    server-scrape/reconcile sub-sections, each of which is contained — a
    scrape failure lands in ``server.error`` without dropping the gate."""
    import shutil
    import sys
    import tempfile
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    t0 = time.perf_counter()
    # the continuous-batching engine serves the row by default
    # (serve_max_batch lanes, AOT executables cached in a fresh dir so
    # one run measures BOTH the cold compile and the warm reload); 1 =
    # the pre-engine serialized path
    aot_dir = tempfile.mkdtemp(prefix="hbnlp_aot_")
    try:
        return _bench_serving_inner(aot_dir, t0)
    finally:
        shutil.rmtree(aot_dir, ignore_errors=True)


def _serve_chunk_arm(params, chunk_tokens: int) -> dict:
    """One arm of the chunked-prefill A/B probe: fresh engine + server at
    ``serve_prefill_chunk_tokens=chunk_tokens`` driven closed-loop over a
    MIXED-length corpus (graftload --long-frac/--long-len) so long-prompt
    admissions land while short requests are mid-decode — the workload the
    decode-stall exists on.  No AOT dir: both arms pay their own compile,
    keeping donation identical to production.  Returns the figures the
    ratchet compares (goodput, itl_p95, prefill_stall_fraction)."""
    import graftload

    from homebrewnlp_tpu.obs.registry import MetricsRegistry
    from homebrewnlp_tpu.serve import RestAPI, serve
    from homebrewnlp_tpu.utils import load_config

    cfg = load_config(f"configs/{SERVE_CONFIG}.json", **_COMMON,
                      train_batch_size=1, serve_max_batch=SERVE_MAX_BATCH,
                      serve_prefill_chunk_tokens=chunk_tokens)
    reg = MetricsRegistry()
    api = RestAPI(cfg, params)
    server = serve(cfg, None, port=0, background=True, registry=reg,
                   obs_port=0, api=api)
    try:
        url = f"http://127.0.0.1:{server.server_address[1]}"
        murl = f"http://127.0.0.1:{server._obs_server.server_address[1]}"
        api.wrapper.complete([1, 2, 3], 0.0, SERVE_RESPONSE_LEN)
        # long prompts fill most of the context window minus the response;
        # short ones keep decode lanes busy underneath the long admissions
        long_len = max(8, cfg.sequence_length - SERVE_RESPONSE_LEN)
        report = graftload.drive(
            url, metrics_url=murl, n_requests=SERVE_REQUESTS,
            concurrency=max(8, SERVE_CONCURRENCY), vocab=cfg.vocab_size,
            min_prompt=4,
            max_prompt=max(4, min(16, cfg.sequence_length // 4)),
            long_frac=0.25, long_len=long_len,
            response_len=SERVE_RESPONSE_LEN, seed=5)
    finally:
        server.shutdown()
        server.server_close()
        api.wrapper.close()
    c = report.get("client") or {}
    srv = report.get("server") or {}
    arm = {"goodput_tok_s": c.get("goodput_tok_s"),
           "error_rate": c.get("error_rate")}
    if isinstance(srv, dict) and "error" not in srv:
        itl = srv.get("itl_s")
        arm["itl_p95"] = itl.get("p95") if isinstance(itl, dict) else None
        arm["prefill_stall_fraction"] = srv.get("prefill_stall_fraction")
    return arm


def _bench_serving_inner(aot_dir: str, t0: float) -> dict:
    import graftload

    from homebrewnlp_tpu.models import init_params
    from homebrewnlp_tpu.obs.registry import MetricsRegistry
    from homebrewnlp_tpu.serve import RestAPI, serve
    from homebrewnlp_tpu.utils import load_config, random_text_batch

    cfg = load_config(f"configs/{SERVE_CONFIG}.json", **_COMMON,
                      train_batch_size=1, serve_max_batch=SERVE_MAX_BATCH,
                      serve_aot_cache_dir=aot_dir if SERVE_MAX_BATCH > 1
                      else "")
    params, _ = init_params(cfg, random_text_batch(cfg))
    # a dedicated registry: the serving histograms this row reconciles
    # against must contain exactly this run's requests, not the training
    # workloads' REST leftovers
    reg = MetricsRegistry()
    t_engine0 = time.perf_counter()
    api = RestAPI(cfg, params)
    server = serve(cfg, None, port=0, background=True, registry=reg,
                   obs_port=0, api=api)
    cold = {}
    try:
        url = f"http://127.0.0.1:{server.server_address[1]}"
        murl = f"http://127.0.0.1:{server._obs_server.server_address[1]}"
        # prompts must leave room to generate: TTFT/decode need tokens
        max_prompt = max(4, min(64, cfg.sequence_length - SERVE_RESPONSE_LEN))
        # warmup: pay the sampler compile OUTSIDE the HTTP/SLO path (a
        # direct engine call records nothing), so the registry this row
        # scrapes holds exactly the timed requests and the steady-state
        # percentiles are honest; timed apart as compile_and_warmup_s
        api.wrapper.complete([1, 2, 3], 0.0, SERVE_RESPONSE_LEN)
        compile_and_warmup_s = time.perf_counter() - t0
        # cold start (engine build -> first token served), split into the
        # engine's own compile vs AOT-reload accounting when available
        cold["cold_start_s"] = round(time.perf_counter() - t_engine0, 3)
        for k in ("compile_s", "aot_reload_s", "aot_cache_hit"):
            v = getattr(api.engine, k, None)
            cold[k] = round(v, 3) if isinstance(v, float) else v
        report = graftload.drive(
            url, metrics_url=murl, n_requests=SERVE_REQUESTS,
            concurrency=SERVE_CONCURRENCY, vocab=cfg.vocab_size,
            min_prompt=4, max_prompt=max_prompt,
            response_len=SERVE_RESPONSE_LEN, seed=2)
        # streaming probe (contained): a short --stream pass measuring
        # client-side TTFT-to-first-SSE-chunk and reconciling client ITL
        # against the server histogram — runs AFTER the main drive so the
        # main report's scrape holds exactly the gated load.  The probe's
        # reconcile arms use a pre/post scrape DELTA: the cumulative
        # histograms are dominated by the main drive's queued load, and
        # comparing the idle probe's client clocks against those would
        # flag two healthy clocks
        stream_probe: dict = {}
        try:
            pre_text = graftload.fetch_metrics(murl)
            sreport = graftload.drive(
                url, n_requests=4, concurrency=2,
                vocab=cfg.vocab_size, min_prompt=4, max_prompt=max_prompt,
                response_len=SERVE_RESPONSE_LEN, seed=7, stream=True)
            post_text = graftload.fetch_metrics(murl)
            sc = sreport["client"]
            if sc.get("error_rate"):
                stream_probe["stream_probe_error"] = (
                    f"error_rate={sc['error_rate']}")
            else:
                stream_probe["stream_ttft_s"] = (sc.get("ttft_s")
                                                 or {}).get("p50")
                stream_probe["stream_itl_p50"] = (sc.get("itl_s")
                                                  or {}).get("p50")
                arms = _stream_delta_reconcile(sc, pre_text, post_text)
                if arms:
                    stream_probe["stream_reconcile"] = arms
        except Exception as e:  # noqa: BLE001 - probe failure, row survives
            stream_probe["stream_probe_error"] = (
                f"{type(e).__name__}: {e}"[:200])
    finally:
        server.shutdown()
        server.server_close()
        # the wrapper's daemon workers pin wrapper -> engine -> params (the
        # full serving-config weights) through every later bench section
        # unless told to exit
        api.wrapper.close()
    if SERVE_MAX_BATCH > 1 and cold.get("compile_s") is not None:
        # second server start against the populated AOT cache: the replica
        # autoscaling number — deserialization must beat compilation
        # (contained: a probe failure lands in cold["error"], the row and
        # its core figures survive)
        try:
            from homebrewnlp_tpu.serve.engine import BatchEngine
            t1 = time.perf_counter()
            e2 = BatchEngine(cfg, params)
            e2.complete_tokens([1, 2, 3], 0.0, SERVE_RESPONSE_LEN)
            cold["warm_start_s"] = round(time.perf_counter() - t1, 3)
            cold["aot_reload_s"] = (round(e2.aot_reload_s, 3)
                                    if e2.aot_reload_s is not None else None)
            cold["aot_cache_hit"] = e2.aot_cache_hit
            e2.close()
        except Exception as e:  # noqa: BLE001
            # NOT "error": that key at row top level flips the serve_ok
            # gate, and a failed warm-start probe must not sink a row whose
            # core serving figures are healthy
            cold["warm_probe_error"] = f"{type(e).__name__}: {e}"[:200]
    chunk_probe: dict = {}
    if SERVE_CHUNK_TOKENS > 0 and SERVE_MAX_BATCH > 1:
        # chunked-prefill A/B (contained): same model, same mixed-length
        # corpus, chunking off vs on.  Off measures the real decode stall
        # (the blocking admission prefill the PR-14 ruler prices); on must
        # cut the stall fraction without regressing itl_p95 — the ratchet
        # in evaluate_serve_baseline enforces exactly that once recorded
        try:
            chunk_probe["chunked_prefill"] = {
                "chunk_tokens": SERVE_CHUNK_TOKENS,
                "off": _serve_chunk_arm(params, 0),
                "on": _serve_chunk_arm(params, SERVE_CHUNK_TOKENS)}
        except Exception as e:  # noqa: BLE001 - probe failure, row survives
            chunk_probe["chunk_probe_error"] = f"{type(e).__name__}: {e}"[:200]
    c = report["client"]
    e2e = c.get("e2e_s") or {}
    row = {
        # core fields FIRST (the baseline gate and the driver's trajectory
        # read these; everything after is contained best-effort detail)
        "config": SERVE_CONFIG,
        "value": c.get("goodput_tok_s"),  # the row's figure of record
        "goodput_tok_s": c.get("goodput_tok_s"),
        "e2e_p50_s": e2e.get("p50"),
        "e2e_p95_s": e2e.get("p95"),
        "requests_per_s": c.get("requests_per_s"),
        "truncated": c.get("truncated", False),
        "error_rate": c.get("error_rate"),
        "n_requests": c.get("n_requests"),
        "n_rejected": c.get("n_rejected"),
        "concurrency": SERVE_CONCURRENCY,
        "response_len": SERVE_RESPONSE_LEN,
        "serve_max_batch": SERVE_MAX_BATCH,
        "compile_and_warmup_s": round(compile_and_warmup_s, 1),
    }
    row.update(cold)
    row.update(stream_probe)
    row.update(chunk_probe)
    # flight-recorder steady-state overhead (observability PR): time the
    # recorder's whole per-request hot path (trail build + ring append +
    # tail-sampling quantile) on realistic finished records and price it
    # against this row's measured p50 latency — the figure the ≤1%
    # acceptance bound ratchets (contained: probe failure, row survives)
    try:
        from homebrewnlp_tpu.obs.flight import FlightRecorder
        from homebrewnlp_tpu.serve.slo import RequestRecord
        fr = FlightRecorder(registry=reg)

        def _probe_rec(i: int) -> RequestRecord:
            r = RequestRecord(i, path="/token_completion")
            r.xid = f"bench-{i:04d}"
            r.mark_parsed()
            r.mark_enqueued(queue_depth=0)
            r.mark_started()
            r.mark_first_token()
            r.mark_engine_done()
            r.tokens_generated = SERVE_RESPONSE_LEN
            r.mark_finished(200)
            return r

        probe_recs = [_probe_rec(i) for i in range(256)]
        t_fl = time.perf_counter()
        for r in probe_recs:
            fr.observe_request(r)
        per_req_s = (time.perf_counter() - t_fl) / len(probe_recs)
        row["flight_observe_us"] = round(per_req_s * 1e6, 2)
        if isinstance(e2e.get("p50"), (int, float)) and e2e["p50"] > 0:
            row["flight_overhead_frac"] = round(per_req_s / e2e["p50"], 6)
    except Exception as e:  # noqa: BLE001 - probe failure, row survives
        row["flight_probe_error"] = f"{type(e).__name__}: {e}"[:200]
    # usage-meter steady-state overhead (usage metering PR): time the
    # meter's whole per-request hot path (tenant validation + sketch admit
    # + accumulate + flops pricing + rate-window append) on realistic
    # finished records and price it against this row's measured p50 — the
    # same absolute ≤1% acceptance bound as the flight recorder
    try:
        from homebrewnlp_tpu.obs.usage import UsageMeter
        from homebrewnlp_tpu.serve.slo import RequestRecord
        meter = UsageMeter(32, pricing={"prefill_flops": 1.0e9,
                                        "decode_flops_per_token": 1.0e6})

        def _usage_rec(i: int) -> RequestRecord:
            r = RequestRecord(i, path="/token_completion")
            r.xid = f"bench-u-{i:04d}"
            r.tenant = f"t{i % 8}"
            r.mark_parsed()
            r.mark_enqueued(queue_depth=0)
            r.mark_started()
            r.mark_first_token()
            r.mark_engine_done()
            r.prompt_tokens = 16
            r.tokens_generated = SERVE_RESPONSE_LEN
            r.kv_blocks = 2
            r.kv_block_seconds = 0.25
            r.lane_seconds = 0.12
            r.mark_finished(200)
            return r

        usage_recs = [_usage_rec(i) for i in range(256)]
        t_um = time.perf_counter()
        for r in usage_recs:
            meter.finalize(r, 200)
        per_req_s = (time.perf_counter() - t_um) / len(usage_recs)
        row["usage_finalize_us"] = round(per_req_s * 1e6, 2)
        if isinstance(e2e.get("p50"), (int, float)) and e2e["p50"] > 0:
            row["usage_overhead_frac"] = round(per_req_s / e2e["p50"], 6)
    except Exception as e:  # noqa: BLE001 - probe failure, row survives
        row["usage_probe_error"] = f"{type(e).__name__}: {e}"[:200]
    srv = report.get("server") or {}
    if isinstance(srv, dict) and "error" not in srv:
        for key, out_key in (("ttft_s", "ttft"), ("queue_wait_s",
                                                  "queue_wait"),
                             ("engine_s", "engine"),
                             ("decode_tokens_per_sec", "decode_rate"),
                             ("batch_size", "batch_size"),
                             ("itl_s", "itl"),
                             ("decode_step_s", "decode_step")):
            if isinstance(srv.get(key), dict):
                row[f"{out_key}_p50"] = srv[key].get("p50")
                row[f"{out_key}_p95"] = srv[key].get("p95")
        if srv.get("prefill_stall_fraction") is not None:
            row["prefill_stall_fraction"] = srv["prefill_stall_fraction"]
    if "server" in report:
        row["server"] = srv
    if "reconcile" in report:
        row["reconcile"] = report["reconcile"]
        over = report["reconcile"].get("serialization_overhead_s")
        if over is not None:
            row["serialization_overhead_s"] = over
    return row


def evaluate_serve_baseline(row: dict, baseline: dict,
                            max_latency_ratio: float = SERVE_LATENCY_RATIO,
                            min_goodput_ratio: float = SERVE_GOODPUT_RATIO):
    """Pure serving-ratchet evaluation (unit-testable without a server):
    the row's p50 e2e latency and goodput tok/s against the committed
    per-device baseline.  Returns (gate row or None, ok).  A missing
    figure or baseline is skipped — absence is not a regression (the
    baseline self-records on first contact, bench.main)."""
    if not isinstance(row, dict) or not baseline:
        return None, True
    out: dict = {}
    ok = True
    e2e, base_e2e = row.get("e2e_p50_s"), baseline.get("e2e_p50_s")
    if isinstance(e2e, (int, float)) and base_e2e:
        ratio = e2e / base_e2e
        passed = bool(ratio <= max_latency_ratio)
        out["e2e_p50"] = {"baseline_s": base_e2e, "ratio": round(ratio, 3),
                          "pass": passed}
        ok = ok and passed
    good, base_good = row.get("goodput_tok_s"), baseline.get("goodput_tok_s")
    if isinstance(good, (int, float)) and base_good:
        ratio = good / base_good
        passed = bool(ratio >= min_goodput_ratio)
        out["goodput"] = {"baseline_tok_s": base_good,
                          "ratio": round(ratio, 3), "pass": passed}
        ok = ok and passed
    # cold-start ratchet (continuous-batching PR): once a baseline has
    # recorded cold_start_s, a later round may not regress it past the
    # latency ratio — AOT reload keeps replica cold starts in seconds
    cold, base_cold = row.get("cold_start_s"), baseline.get("cold_start_s")
    if isinstance(cold, (int, float)) and base_cold:
        ratio = cold / base_cold
        passed = bool(ratio <= max_latency_ratio)
        out["cold_start"] = {"baseline_s": base_cold,
                             "ratio": round(ratio, 3), "pass": passed}
        ok = ok and passed
    # token-level ratchets (streaming/ITL PR): per-token latency and the
    # streamed first-chunk latency gate like e2e; the prefill-stall
    # fraction gets an absolute 0.05 slack on top of the ratio — at tiny
    # stall fractions a pure ratio would flag scheduler noise
    for key, base_key in (("itl_p50", "itl_p50"),
                          ("stream_ttft_s", "stream_ttft_s")):
        v, b = row.get(key), baseline.get(base_key)
        if isinstance(v, (int, float)) and b:
            ratio = v / b
            passed = bool(ratio <= max_latency_ratio)
            out[key] = {"baseline_s": b, "ratio": round(ratio, 3),
                        "pass": passed}
            ok = ok and passed
    frac = row.get("prefill_stall_fraction")
    base_frac = baseline.get("prefill_stall_fraction")
    if isinstance(frac, (int, float)) and isinstance(base_frac, (int, float)):
        limit = base_frac * max_latency_ratio + 0.05
        passed = bool(frac <= limit)
        out["prefill_stall_fraction"] = {
            "baseline": base_frac, "value": frac,
            "limit": round(limit, 4), "pass": passed}
        ok = ok and passed
    # chunked-prefill ratchet (chunked prefill PR): once a baseline has
    # recorded the A/B probe's ON arm, a later round's ON arm may not
    # regress it — the stall fraction gets the same ratio + 0.05 absolute
    # slack as the main stall gate, and itl_p95 gates like the other
    # latencies (chunk interleave must stay off the decode critical path)
    on = (row.get("chunked_prefill") or {}).get("on") or {}
    base_on = (baseline.get("chunked_prefill") or {}).get("on") or {}
    c_frac = on.get("prefill_stall_fraction")
    b_frac = base_on.get("prefill_stall_fraction")
    if isinstance(c_frac, (int, float)) and isinstance(b_frac, (int, float)):
        limit = b_frac * max_latency_ratio + 0.05
        passed = bool(c_frac <= limit)
        out["chunked_stall_fraction"] = {
            "baseline": b_frac, "value": c_frac,
            "limit": round(limit, 4), "pass": passed}
        ok = ok and passed
    c_itl, b_itl = on.get("itl_p95"), base_on.get("itl_p95")
    if isinstance(c_itl, (int, float)) and b_itl:
        ratio = c_itl / b_itl
        passed = bool(ratio <= max_latency_ratio)
        out["chunked_itl_p95"] = {"baseline_s": b_itl,
                                  "ratio": round(ratio, 3), "pass": passed}
        ok = ok and passed
    # flight-recorder overhead (observability PR): an ABSOLUTE cap, not a
    # ratio against baseline — the ≤1%-of-p50 bound IS the acceptance
    # criterion, so a baseline recorded at 0.2% must not license 0.3%
    fo = row.get("flight_overhead_frac")
    if isinstance(fo, (int, float)):
        passed = bool(fo <= 0.01)
        out["flight_overhead_frac"] = {"value": fo, "limit": 0.01,
                                       "pass": passed}
        ok = ok and passed
    # usage-meter overhead (usage metering PR): the same absolute ≤1%
    # bound — metering must stay invisible next to a model step
    uo = row.get("usage_overhead_frac")
    if isinstance(uo, (int, float)):
        passed = bool(uo <= 0.01)
        out["usage_overhead_frac"] = {"value": uo, "limit": 0.01,
                                      "pass": passed}
        ok = ok and passed
    return (out or None), ok


def evaluate_compile_budget(workloads: dict, budgets: dict,
                            max_ratio: float = COMPILE_BUDGET_RATIO):
    """Pure compile-time ratchet evaluation (unit-testable, shared with
    tools/compile_ratchet.py): each workload's ``compile_and_warmup_s``
    against its committed per-device budget.  Returns (per-workload budget
    rows, all_pass).  Workloads without a recorded figure or budget are
    skipped — absence is not a regression (e.g. a partial
    HBNLP_BENCH_WORKLOADS run)."""
    rows: dict = {}
    ok = True
    for nm, w in sorted(workloads.items()):
        s = w.get("compile_and_warmup_s") if isinstance(w, dict) else None
        base = (budgets or {}).get(nm)
        if not isinstance(s, (int, float)) or not base:
            continue
        ratio = s / base
        passed = bool(ratio <= max_ratio)
        rows[nm] = {"baseline_s": base, "ratio": round(ratio, 3),
                    "pass": passed}
        ok = ok and passed
    return rows, ok


def ensure_real_corpus(pattern: str, builder=None):
    """None when files matching ``pattern`` exist (rebuilding them
    deterministically if needed), else a structured guard-failure dict —
    the trajectory guard REFUSES the train CLI's silent synthetic fallback
    (round-5 post-mortem, docs/perf/README.md).  ``builder`` is injectable
    for tests; the default shells out to tools/build_corpus.py."""
    import glob
    import subprocess
    import sys

    def default_builder():
        subprocess.run([sys.executable, "tools/build_corpus.py",
                        "--out-dir", "datasets"], check=True)

    if not glob.glob(pattern):
        try:
            (builder or default_builder)()
        except Exception as e:  # noqa: BLE001 - report, don't crash the line
            return {"pass": False,
                    "error": f"corpus rebuild failed: {e}"[:300]}
    if not glob.glob(pattern):
        return {"pass": False,
                "error": f"no real corpus at {pattern}; refusing the "
                         "synthetic fallback"}
    return None


def numerics_guard(n_steps: int = 300) -> dict:
    """Real-corpus trajectory check, driver-visible (VERDICT r4 item 9):
    run the first ``n_steps`` of the 10k acceptance setup
    (``configs/32ctx_accept_10k.json``, committed 84M-token corpus, fixed
    data_seed) through the full CLI train path and assert the warmup
    trajectory of the committed 10k-run record (the STABLE Run-B
    hyperparameters — see the module docstring for why not the LR-0.01
    ``32ctx_real_1chip`` point)."""
    import argparse
    import tempfile

    from homebrewnlp_tpu import main as cli
    from homebrewnlp_tpu.utils import load_config

    with tempfile.TemporaryDirectory(prefix="bench_guard_") as tmp:
        cfg = load_config("configs/32ctx_accept_10k.json",
                          model_path=tmp, use_checkpointing=False)
        err = ensure_real_corpus(cfg.dataset_configs[0]["path"])
        if err is not None:
            return err
        args = argparse.Namespace(steps=n_steps, profile="", workers=None)
        t0 = time.perf_counter()
        cli.train(cfg, args)
        wall = time.perf_counter() - t0
        from homebrewnlp_tpu.train.metrics import read_metric_rows
        rows = read_metric_rows(tmp)
    result = evaluate_guard(rows, n_steps)
    result["wall_s"] = round(wall, 1)
    result["config"] = "configs/32ctx_accept_10k.json"
    return result


def evaluate_guard(rows, n_steps: int) -> dict:
    """Pure threshold evaluation over metrics rows (separated so the logic
    is unit-testable without a chip).  Thresholds follow the committed
    round-5 10k-step run (7.71 -> 3.45@100 -> 2.76-class@300, with
    margin); shorter development runs
    (HBNLP_BENCH_GUARD_STEPS < 120/300) only assert the checkpoints they
    actually reach, plus strict decrease."""
    # tolerate raw rows: run-start boundary markers carry no loss and only
    # metric rows participate in the trajectory check (read_metric_rows
    # already filters when the rows come from it)
    rows = [r for r in rows if "loss" in r]
    if not rows:
        return {"pass": False,
                "error": "no metric rows (marker-only metrics.jsonl — the "
                         "run died before its first metric drain)"}
    by_step = {r["step"]: r["loss"] for r in rows}
    first = rows[0]["loss"]
    final = rows[-1]["loss"]
    at_120 = min((s for s in by_step if s >= min(120, n_steps - 1)),
                 default=rows[-1]["step"])
    loss_120 = by_step[at_120]
    ok = (first > 6.5 and final == final and final < first)
    if n_steps >= 120:
        ok = ok and loss_120 < 4.5
    if n_steps >= 300:
        ok = ok and final < 3.6
    return {"pass": bool(ok), "steps": rows[-1]["step"],
            "loss_first": round(first, 4),
            "loss_step120": round(loss_120, 4),
            "loss_final": round(final, 4)}


def _rows_with_error(node, path: str = "") -> typing.List[str]:
    """Paths of every (sub-)row of the record that carries an ``error``."""
    if not isinstance(node, dict):
        return []
    found = [path or "."] if node.get("error") else []
    for key, value in node.items():
        found += _rows_with_error(value, f"{path}/{key}" if path else key)
    return found


def main() -> None:
    from homebrewnlp_tpu.utils import enable_compilation_cache

    device = jax.devices()[0]
    if device.platform != "tpu":
        # a CPU timing under the name of a device metric is worse than no
        # number (and self-records as a baseline): measure on the chip only
        raise SystemExit(f"bench.py measures on a TPU; JAX found platform "
                         f"{device.platform!r} ({device.device_kind})")
    # persistent XLA cache: a warm re-run skips the step compiles
    enable_compilation_cache()

    sel = os.environ.get("HBNLP_BENCH_WORKLOADS", "all")
    names = list(WORKLOADS) if sel == "all" else [
        s for s in sel.split(",") if s in WORKLOADS]
    workloads = {}
    for name in names:
        try:
            workloads[name] = bench_workload(
                name, probe_loss=(name == "32big_mixer"))
        except Exception as e:  # noqa: BLE001 - one workload must not kill the line
            workloads[name] = {"error": f"{type(e).__name__}: {e}"[:300]}

    # serving workload row + its ratchet, evaluated HERE — before the
    # guard and the compile/profile ratchet sections below — so a failure
    # in any later probe cannot drop the serving baseline comparison
    # (the hbm_peak_bytes ordering discipline, ISSUE 9 satellite)
    serve_ok: typing.Optional[bool] = None
    if os.environ.get("HBNLP_BENCH_SERVE", "1") != "0":
        try:
            workloads["serving"] = bench_serving()
        except Exception as e:  # noqa: BLE001
            workloads["serving"] = {"error": f"{type(e).__name__}: {e}"[:300]}
        srow = workloads["serving"]
        # a row without usable core figures (every request failed, server
        # never came up cleanly, graftload abandoned a live worker) must
        # FAIL the gate, not skip it — serve_ok exists to catch exactly
        # that class of regression
        serve_ok = ("error" not in srow
                    and not srow.get("truncated")
                    and isinstance(srow.get("e2e_p50_s"), (int, float))
                    and isinstance(srow.get("goodput_tok_s"), (int, float)))
        if (isinstance(srow.get("e2e_p50_s"), (int, float))
                and not srow.get("truncated")):
            serve_baselines = {}
            if os.path.exists(SERVE_BASELINE_FILE):
                with open(SERVE_BASELINE_FILE) as f:
                    serve_baselines = json.load(f)
            kind = jax.devices()[0].device_kind
            dev_serve = serve_baselines.setdefault(kind, {})
            # latency/goodput only compare like against like: the baseline
            # remembers the workload shape it was recorded under, and an
            # env-overridden run (HBNLP_BENCH_SERVE_*, smoke/dev shapes)
            # skips the ratchet instead of failing it spuriously
            shape = {"config": SERVE_CONFIG, "n_requests": SERVE_REQUESTS,
                     "concurrency": SERVE_CONCURRENCY,
                     "response_len": SERVE_RESPONSE_LEN}
            if not dev_serve and not SERVE_SHAPE_OVERRIDDEN:
                # first contact at the DEFAULT shape: self-record (operator
                # commits); an overridden smoke shape must not become the
                # baseline every default run then skips against
                dev_serve.update({
                    "e2e_p50_s": srow["e2e_p50_s"],
                    "goodput_tok_s": srow.get("goodput_tok_s"),
                    # continuous-batching figures self-record so the NEXT
                    # round ratchets them (cold start + the serialization
                    # overhead the engine exists to collapse)
                    "queue_wait_p50_s": srow.get("queue_wait_p50"),
                    "serialization_overhead_s": srow.get(
                        "serialization_overhead_s"),
                    "cold_start_s": srow.get("cold_start_s"),
                    "compile_s": srow.get("compile_s"),
                    "aot_reload_s": srow.get("aot_reload_s"),
                    "serve_max_batch": srow.get("serve_max_batch"),
                    # token-level figures (streaming/ITL PR) self-record
                    # so the NEXT round ratchets them
                    "itl_p50": srow.get("itl_p50"),
                    "prefill_stall_fraction": srow.get(
                        "prefill_stall_fraction"),
                    "stream_ttft_s": srow.get("stream_ttft_s"),
                    # chunked-prefill A/B figures (chunked prefill PR),
                    # present only when HBNLP_BENCH_SERVE_CHUNK ran the probe
                    "chunked_prefill": srow.get("chunked_prefill"),
                    # flight-recorder per-request cost (observability PR) —
                    # recorded for trajectory visibility; the gate itself
                    # is the absolute ≤1% cap, not a ratio against this
                    "flight_overhead_frac": srow.get("flight_overhead_frac"),
                    # usage-meter per-request cost (usage metering PR) —
                    # same deal: trajectory visibility, absolute ≤1% gate
                    "usage_overhead_frac": srow.get("usage_overhead_frac"),
                    "shape": shape,
                    "recorded": time.time()})
                with open(SERVE_BASELINE_FILE, "w") as f:
                    json.dump(serve_baselines, f, indent=2, sort_keys=True)
                    f.write("\n")
            elif (dev_serve and not SERVE_SHAPE_OVERRIDDEN
                    and isinstance(srow.get("chunked_prefill"), dict)
                    and not dev_serve.get("chunked_prefill")
                    and dev_serve.get("shape", shape) == shape):
                # the A/B probe self-records into an EXISTING baseline the
                # first time HBNLP_BENCH_SERVE_CHUNK runs at the default
                # shape, so the next round ratchets the ON arm
                dev_serve["chunked_prefill"] = srow["chunked_prefill"]
                with open(SERVE_BASELINE_FILE, "w") as f:
                    json.dump(serve_baselines, f, indent=2, sort_keys=True)
                    f.write("\n")
            elif (dev_serve and not SERVE_SHAPE_OVERRIDDEN
                    and isinstance(srow.get("usage_overhead_frac"),
                                   (int, float))
                    and dev_serve.get("usage_overhead_frac") is None
                    and dev_serve.get("shape", shape) == shape):
                # the usage-meter probe self-records into an EXISTING
                # baseline on its first default-shape run (the gate stays
                # the absolute ≤1% cap; this is trajectory visibility)
                dev_serve["usage_overhead_frac"] = srow["usage_overhead_frac"]
                with open(SERVE_BASELINE_FILE, "w") as f:
                    json.dump(serve_baselines, f, indent=2, sort_keys=True)
                    f.write("\n")
            if dev_serve.get("shape", shape) == shape:
                gate, gate_ok = evaluate_serve_baseline(srow, dev_serve)
                if gate is not None:
                    srow["baseline"] = gate
                serve_ok = serve_ok and gate_ok
            else:
                srow["baseline"] = {"skipped": "workload shape differs "
                                               "from the recorded baseline"}

    guard_steps = int(os.environ.get("HBNLP_BENCH_GUARD_STEPS", "300"))
    guard = None
    if guard_steps:
        try:
            guard = numerics_guard(guard_steps)
        except Exception as e:  # noqa: BLE001
            guard = {"pass": False,
                     "error": f"{type(e).__name__}: {e}"[:300]}

    device_kind = jax.devices()[0].device_kind
    n_chips = max(1, len(jax.devices()))
    flag = workloads.get("32big_mixer", {})
    value = flag.get("value")

    # round-over-round comparison keyed by device kind; bench_baseline.json
    # is COMMITTED, so every round's vs_baseline shares one pinned
    # denominator (21040.8 tok/s on v5e, the round-1 figure) instead of
    # resetting per machine
    baselines = {}
    if os.path.exists(BASELINE_FILE):
        with open(BASELINE_FILE) as f:
            baselines = json.load(f)
    if value is not None and device_kind not in baselines:
        baselines[device_kind] = {"value": value, "recorded": time.time()}
        with open(BASELINE_FILE, "w") as f:
            json.dump(baselines, f)
    baseline = baselines.get(device_kind, {}).get("value")

    # compile-time ratchet: every workload's compile_and_warmup_s against
    # the committed per-device budget (bench_compile_baseline.json).  A
    # first run on an unknown device kind records its own budget (committed
    # by the operator like bench_baseline.json); after that, >20% over
    # budget fails the line's compile_ok and the CI ratchet
    # (tools/compile_ratchet.py).
    comp_baselines = {}
    if os.path.exists(COMPILE_BASELINE_FILE):
        with open(COMPILE_BASELINE_FILE) as f:
            comp_baselines = json.load(f)
    # self-record per WORKLOAD, not just per device kind: a workload added
    # after the device's budget was first recorded (or missing from a
    # partial first run) must gain a budget on its first successful
    # measurement, or it would pass the ratchet unguarded forever
    dev_budget = comp_baselines.setdefault(device_kind, {})
    new_rows = {n: w["compile_and_warmup_s"] for n, w in workloads.items()
                if isinstance(w, dict) and n not in dev_budget
                and isinstance(w.get("compile_and_warmup_s"), (int, float))}
    if new_rows:
        dev_budget.update(new_rows)
        with open(COMPILE_BASELINE_FILE, "w") as f:
            json.dump(comp_baselines, f, indent=2, sort_keys=True)
            f.write("\n")
    budget_rows, compile_ok = evaluate_compile_budget(
        workloads, comp_baselines.get(device_kind, {}))
    for n, b in budget_rows.items():
        workloads[n]["compile_budget"] = b

    # attribution-drift ratchet (graftprof): per-device-kind committed
    # baseline of decomposition fractions + scope coverage, self-recorded
    # on a workload's first successful capture (operator commits it, like
    # the compile budget); after that, drift past the tolerance fails the
    # row and the line's profile_ok
    from homebrewnlp_tpu.obs.profile import (baseline_entry,
                                             evaluate_profile_baseline)
    prof_baselines = {}
    if os.path.exists(PROFILE_BASELINE_FILE):
        with open(PROFILE_BASELINE_FILE) as f:
            prof_baselines = json.load(f)
    dev_prof = prof_baselines.setdefault(device_kind, {})
    new_prof = {n: baseline_entry(w["profile"]) for n, w in workloads.items()
                if isinstance(w, dict) and isinstance(w.get("profile"), dict)
                and "fractions" in w["profile"] and n not in dev_prof}
    if new_prof:
        dev_prof.update(new_prof)
        with open(PROFILE_BASELINE_FILE, "w") as f:
            json.dump(prof_baselines, f, indent=2, sort_keys=True)
            f.write("\n")
    prof_rows, profile_ok = evaluate_profile_baseline(workloads, dev_prof)
    for n, b in prof_rows.items():
        workloads[n]["profile"]["baseline"] = b

    record = {
        "metric": "tokens_per_sec_per_chip",
        # figure of record = the flagship's median-of-5 windows (continuity
        # with rounds 1-4); the two other reference workloads ride in
        # "workloads"
        "value": value,
        "unit": "tok/s/chip",
        "vs_baseline": (round(value / baseline, 4)
                        if value and baseline else None),
        "best": flag.get("best"),
        "windows_tok_s": flag.get("windows_tok_s"),
        "ms_per_step": flag.get("ms_per_step"),
        "flops_per_step": flag.get("flops_per_step"),
        "flops_per_step_algorithmic": flag.get("flops_per_step_algorithmic"),
        "mfu": flag.get("mfu"),
        "mfu_algorithmic": flag.get("mfu_algorithmic"),
        "loss_after_n_steps": flag.get("loss_after_n_steps"),
        "n_steps_total": flag.get("n_steps_total"),
        "compile_and_warmup_s": flag.get("compile_and_warmup_s"),
        "host_blocked_s": flag.get("host_blocked_s"),
        "phases_s": flag.get("phases_s"),
        "compile_cache_hit": flag.get("compile_cache_hit"),
        "device": device_kind,
        "n_chips": n_chips,
        "compile_ok": compile_ok,
        "profile_ok": profile_ok,
        # serving ratchet verdict (None = row skipped via HBNLP_BENCH_SERVE)
        "serve_ok": serve_ok,
        "workloads": workloads,
        "numerics_guard": guard,
    }
    if any(isinstance(w.get("mfu"), float) and w["mfu"] > 1.0
           for w in workloads.values()):
        # physically impossible: the timed window closed before the device
        # finished; the throughput figures must not be trusted.
        record["distorted"] = True
    print(json.dumps(record))
    failed = _rows_with_error(record)
    if failed:
        # the line above still carries every healthy row, but a run with a
        # failed workload, serving row, guard or probe is not a pass
        raise SystemExit(f"bench.py: rows carrying an error: "
                         f"{', '.join(failed)}")


if __name__ == "__main__":
    main()
