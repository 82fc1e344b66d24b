"""CLI entry: run modes over a JSON config.

Mirrors the reference CLI (/root/reference/main.py:12-30, src/main.py:36-166):
``--model cfg.json --run_mode {train,sample,query,web_api,debug}``.  TPU
bootstrap collapses from cluster-resolver/session plumbing to
``jax.distributed.initialize`` (multi-host) + mesh construction; run-config
and model-size artifacts are dumped next to checkpoints exactly like the
reference (src/main.py:66-69, src/run/utils_run.py:108-113).
"""
from __future__ import annotations

import argparse
import json
import os
import time
import typing

import numpy as np


def parse_args(argv: typing.Optional[typing.Sequence[str]] = None):
    p = argparse.ArgumentParser()
    p.add_argument("--model", type=str, required=True, help="JSON config path")
    p.add_argument("--tpu", type=str, default="", help="unused on single host;"
                   " 'host:port,rank,size' triggers jax.distributed.initialize")
    p.add_argument("--run_mode", type=str, default="train",
                   choices=["train", "sample", "query", "web_api", "debug",
                            "debug_old"])
    p.add_argument("--steps", type=int, default=0,
                   help="override train_steps (0 = config value)")
    p.add_argument("--workers", type=int, default=None,
                   help="override cfg.web_workers (reference src/main.py:60)")
    p.add_argument("--debug_grad", action="store_true")
    p.add_argument("--port", type=int, default=8000)
    p.add_argument("--obs_port", type=int, default=None,
                   help="web_api: /metrics + /healthz exporter port "
                        "(overrides cfg.obs_port; the replica router "
                        "health-gates on this endpoint)")
    p.add_argument("--grace_deadline_s", type=float, default=30.0,
                   help="web_api: SIGTERM graceful-drain bound — finish "
                        "in-flight streams for at most this long before "
                        "exiting (docs/reliability.md)")
    p.add_argument("--profile", type=str, default="",
                   help="directory for a jax.profiler trace of a few "
                        "steady-state train steps (upgrade over the "
                        "reference's phase timers, SURVEY.md §5.1)")
    return p.parse_args(argv)


def _init_distributed(tpu_arg: str) -> None:
    """Stash ``--tpu host:port,rank,size`` into the HBNLP_DIST_* env vars;
    the actual (retried) ``jax.distributed.initialize`` happens once the
    config is loaded, via ``reliability.dist.initialize`` — one init path
    for the CLI flag, the config knobs, and the supervisor's env plumbing."""
    if "," in tpu_arg:
        from .reliability import dist
        addr, rank, size = tpu_arg.split(",")
        os.environ[dist.ENV_COORDINATOR] = addr
        os.environ[dist.ENV_PROCESS_ID] = rank
        os.environ[dist.ENV_NUM_PROCESSES] = size


def _have_dataset_files(cfg) -> bool:
    from .data import fs
    return bool(cfg.dataset_configs) and any(
        fs.glob(d["path"]) for d in cfg.dataset_configs)


def _build_state(cfg, batch, mesh=None):
    from .train import Checkpointer, Trainer, color_print
    trainer = Trainer(cfg, mesh)
    state = trainer.init(batch)
    ckpt = None
    data_state = None
    if cfg.use_checkpointing:
        ckpt = Checkpointer(os.path.join(cfg.model_path, "ckpt"),
                            cfg.max_checkpoints_keep,
                            retries=cfg.ckpt_retries)
        state, data_state = ckpt.restore(state, cfg)
        color_print(f"restored step {int(state.step)} from checkpoints"
                    if int(state.step) else "fresh initialization")
    return trainer, state, ckpt, data_state


def _dump_run_artifacts(cfg, trainer, params) -> None:
    os.makedirs(cfg.model_path, exist_ok=True)
    with open(os.path.join(cfg.model_path, "run_config.json"), "w") as f:
        json.dump({k: str(v) for k, v in cfg.dict().items()}, f, indent=2)
    census = trainer.param_census(params)
    with open(os.path.join(cfg.model_path, "model_size.info"), "w") as f:
        json.dump(census, f, indent=2)


def train(cfg, args) -> None:
    """Observability + fault-tolerance lifecycle wrapper around the step
    loop: builds the per-run ``Obs`` bundle (span tracer, /metrics +
    /healthz exporter, hang watchdog — docs/observability.md; all knobs
    default-off and inert), arms the fault-injection plan, installs the
    SIGTERM/SIGINT grace handlers (docs/reliability.md), guarantees
    ``trace.json`` export + thread shutdown on ANY exit, and delegates to
    ``_train_loop``.  A signal-triggered exit drains the async loop, cuts a
    grace checkpoint inside ``cfg.grace_deadline_s``, and exits with
    ``EXIT_PREEMPTED`` so a supervisor (tools/supervise.py) can tell
    preemption from crash."""
    from .obs import Obs
    from .obs.device_telemetry import AnomalyHalt
    from .reliability import (EXIT_ANOMALY_HALT, EXIT_PEER_LOST,
                              EXIT_PREEMPTED, GraceController, dist, faults)
    from .train import color_print
    # installed (or cleared) EVERY run: a plan must never leak across runs
    faults.install(cfg.fault_plan or None)
    obs = Obs.from_config(cfg)
    grace = GraceController(cfg.grace_deadline_s)
    try:
        # start() inside the try: a partial start (e.g. obs_port already
        # bound) must still unwind through close(), or the ambient tracer
        # would leak into every later run in this process
        obs.start()
        grace.install()
        # join the fleet (no-op single-host) BEFORE any device use: a
        # coordinator still coming up after a shared outage earns the
        # retry/backoff path, not a crash (docs/reliability.md
        # "Multi-host elasticity")
        dist.initialize(cfg)
        _train_loop(cfg, args, obs, grace)
    except dist.DistributedFailure as e:
        # a peer (or the coordinator) is gone: THIS host's state is healthy
        # and the loop already cut a checkpoint of it before re-raising —
        # exit with the distinct code so every per-host supervisor
        # relaunches the fleet in lockstep instead of backing off alone
        color_print(f"DISTRIBUTED FAILURE: {e}; exiting with code "
                    f"{EXIT_PEER_LOST} for a lockstep fleet relaunch")
        raise SystemExit(EXIT_PEER_LOST) from e
    except AnomalyHalt as e:
        # device telemetry saw non-finite gradients under
        # anomaly_policy="halt": exit with the distinct code BEFORE any
        # further checkpoint could persist poisoned state; the supervisor
        # treats it as a crash (backoff + resume from the last good save)
        color_print(f"ANOMALY HALT: {e}; exiting with code "
                    f"{EXIT_ANOMALY_HALT}")
        raise SystemExit(EXIT_ANOMALY_HALT) from e
    finally:
        grace.uninstall()
        obs.close()
    if grace.triggered:
        color_print(f"{grace.signame} handled: grace checkpoint cut; "
                    f"exiting with preemption code {EXIT_PREEMPTED}")
        raise SystemExit(EXIT_PREEMPTED)


def _finalize_profile(cfg, args, trainer, obs) -> None:
    """graftprof post-processing of a just-stopped ``--profile`` capture
    (docs/observability.md "Profile attribution"): dump the HLO op->scope
    sidecar from the kept AOT step executable, parse the Chrome trace into
    a category/scope attribution summary, persist it as
    ``<model_path>/profile_summary.json`` (the watchdog stall dump inlines
    it, ``tools/graftprof.py`` renders it), and feed the live exporter
    (``hbnlp_step_time_ms`` + per-category fractions on /metrics, comm
    fraction on /healthz).  Best-effort end to end: a malformed or absent
    trace (some toolchains never write the plugin directory) degrades to a
    log line, never an exception — the training result is already in."""
    from .obs import profile as profile_mod
    from .train import color_print
    try:
        profile_mod.write_op_map_for(trainer, args.profile)
        summary = profile_mod.capture_summary(args.profile,
                                              n_steps=cfg.profile_steps)
    except Exception as e:  # noqa: BLE001 - never fail the run for this
        color_print(f"graftprof summary failed: {type(e).__name__}: {e}")
        return
    if summary is None:
        color_print(f"no profiler trace found under {args.profile} "
                    "(plugin directory absent); skipping graftprof summary")
        return
    try:
        path = summary.save(os.path.join(cfg.model_path,
                                         "profile_summary.json"))
        d = summary.decomposition_ms_per_step
        color_print(
            f"graftprof: {d.get('total', 0.0):.3f} ms/step = "
            f"mxu {d.get('mxu', 0.0):.3f} + hbm {d.get('hbm', 0.0):.3f} + "
            f"comm {d.get('comm', 0.0):.3f} + idle {d.get('idle', 0.0):.3f} "
            f"(scope coverage {summary.attributed_scope_frac:.0%}) -> {path}")
        for line in profile_mod.layer_pass_table(summary.layer_pass_s,
                                                 cfg.profile_steps):
            color_print(line)
    except Exception as e:  # noqa: BLE001
        color_print(f"graftprof summary write failed: {e}")
        return
    if obs.enabled:
        obs.record_profile(summary)


def _train_loop(cfg, args, obs, grace) -> None:
    """Async-dispatch step loop (docs/performance.md): step indices are
    computed ON HOST (``step0 + (u - u0) * m`` — no device value is read on
    the hot path; graftcheck's ``host-sync`` rule pins this), batches are
    assembled + transferred by a background ``DeviceFeeder`` thread, and
    metrics drain through a bounded ``AsyncMetricWriter`` window so up to
    ``cfg.async_inflight_steps`` updates stay dispatched-but-undrained.
    ``grace.triggered`` (SIGTERM/SIGINT) breaks the loop before the next
    dispatch; the normal tail then cuts the grace checkpoint."""
    import itertools

    run_t0 = time.time()  # TRUE run start: goodput's wall origin must
    # include mesh build, init/restore, and the step compile below

    import jax
    from .data import RunLog, dataset, to_global
    from .data.feed import DeviceFeeder
    from .data.synthetic import synthetic_text_batch
    from .obs import compile_log, device_telemetry, spans
    from .reliability import dist, faults
    from .train import AsyncMetricWriter, MetricWriter, color_print
    from .train.metrics import config_hash

    from .parallel import make_mesh
    # elastic runs suppress the "axis shrunk" fold warnings: when the fleet
    # resumes degraded (the device count no longer factors the declared
    # mesh — the model axis folded, or the batch-bound data axis dropped
    # devices), the mesh searcher's suggestion replaces them
    # (docs/reliability.md "Multi-host elasticity"; analysis/
    # mesh_search.py).  Non-elastic runs keep the plain warnings — running
    # a pod config on one bench chip is deliberate, not degraded.
    from .parallel.mesh import MODEL_AXIS
    elastic = dist.settings(cfg) is not None
    # set-up runs under ``setup/*`` spans (docs/observability.md "Set-up
    # and compiles"): the compile log's ``jax/*`` spans nest inside them
    with spans.span("setup/mesh"):
        mesh = make_mesh(cfg, quiet=elastic)
        n_avail = len(jax.devices())
        if elastic and jax.process_index() == 0 and (
                int(dict(mesh.shape).get(MODEL_AXIS, 1)) != cfg.mesh_model
                or mesh.size < n_avail):
            # process 0 only: the search re-traces the config (seconds on
            # a flagship) and every host would log the identical suggestion
            dist.log_mesh_suggestion(cfg, mesh, n_devices=n_avail)
    # processes sharing a data-axis coordinate (pipe axis spanning hosts)
    # read the SAME dataset slice (data/feed.py::data_slice_for_process);
    # data-major topologies reduce to (process_index, process_count)
    from .data.feed import data_slice_for_process
    slice_index, slice_count = data_slice_for_process(mesh)
    # macro-batching inflates the per-step host batch by M (reference
    # dataloader_placement.py:40-44)
    local_batch = cfg.train_batch_size * cfg.macro_batching // slice_count

    with spans.span("setup/probe_batch"):
        have_data = _have_dataset_files(cfg)
        if have_data:
            # probe pipeline (no prefetch thread): one template batch for
            # init, then discarded — the real pipeline is built after
            # checkpoint restore so its cursor and prefetcher start from
            # the right place
            probe = dataset(cfg, local_batch, slice_index, slice_count,
                            prefetch=False)
            first_np = next(iter(probe))
        else:
            first_np = synthetic_text_batch(cfg, 0)
        template_gb = to_global(first_np, cfg, mesh)
    # which source feeds the run is never silent: it is printed, and rides
    # the run-start marker below so a reader of metrics.jsonl (the chip
    # smoke) can refuse a run that fell back to noise
    data_source = "dataset_files" if have_data else "synthetic"
    color_print(f"data source: {data_source}"
                + (f" ({[d['path'] for d in cfg.dataset_configs]})"
                   if have_data else " (no dataset files found)"))
    with spans.span("setup/init_or_restore"):
        trainer, state, ckpt, data_state = _build_state(cfg, template_gb,
                                                        mesh)
    if int(state.step) == 0 and cfg.current_step > 0:
        # config-forced starting step with no checkpoint (the reference reads
        # it from estimator internals and skips data accordingly,
        # src/main.py:71, dataloader_placement.py:156)
        import jax.numpy as jnp
        state = state._replace(step=jnp.asarray(cfg.current_step, jnp.int32))
    step0 = int(state.step)
    if step0 > 0:
        # a resumed (or step-forced) run must not refire step-site fault
        # rules at or behind its starting position — a sigterm@stepN plan
        # inherited by every supervisor relaunch would livelock otherwise
        faults.disarm_until("step", step0)
        # same for the distributed sites: a peer:die@stepN plan inherited
        # by the relaunched fleet would re-kill every generation forever
        faults.disarm_until("peer", step0)
        faults.disarm_until("coordinator", step0)
    pipe = None
    if have_data:
        # the real (prefetched) pipeline, with the checkpointed cursor
        # restored before the first read
        with spans.span("setup/pipeline"):
            pipe = dataset(cfg, local_batch, slice_index, slice_count)
            if data_state and "pipeline" in data_state:
                pipe.load_state_dict(data_state["pipeline"])

    _dump_run_artifacts(cfg, trainer, state.params)
    # device telemetry (docs/observability.md "Device telemetry"): static
    # utilization accounting once at startup — the HLO cost analysis rides
    # the step compile the run pays anyway (the kept AOT executable then
    # serves every loop step) — plus the drain-side anomaly monitor
    telemetry_on = cfg.telemetry_interval > 0
    util = anomaly = None
    # where the update is built ahead of the loop (both AOT paths below
    # keep the executable the loop then calls); with neither, the first
    # ``step`` span holds the build
    with spans.span("setup/step_build"):
        if telemetry_on:
            from .obs.device_telemetry import AnomalyMonitor
            from .train import flops as flops_mod
            anomaly = AnomalyMonitor(cfg.anomaly_policy, registry=obs.registry
                                     if obs.enabled else None)
            # template_gb is reused from init: cost analysis only LOWERS
            # the step, so no second H2D transfer of a full global batch
            util = flops_mod.utilization_for(
                trainer, state, template_gb,
                tokens_per_step=cfg.train_batch_size
                * max(1, cfg.macro_batching) * cfg.sequence_length)
            color_print(f"device telemetry on: {util.flops_per_step:.3e} "
                        f"flops/step ({util.device_kind}), anomaly_policy="
                        f"{cfg.anomaly_policy}")
        if args.profile and trainer._compiled is None:
            # graftprof attribution (docs/observability.md "Profile
            # attribution") needs the step executable's HLO metadata to map
            # trace events back to model scopes: AOT-compile now (the loop
            # reuses the kept executable, so this is the same compile the
            # first step would have paid — not an extra one) and the op-map
            # sidecar below comes for free.  Best-effort: a failing AOT
            # path only degrades per-scope attribution, never the run.
            try:
                trainer.step_cost_analysis(state, template_gb)
            except Exception as e:
                color_print(f"profile op-map pre-compile failed ({e}); "
                            "per-scope attribution will be unavailable")
    del template_gb  # release the init batch's device buffers for the run
    # deferred metrics drain: debug_train_step keeps the reference's
    # synchronous per-step prints, so it forces the window to 0
    window = 0 if cfg.debug_train_step else cfg.async_inflight_steps
    # the TensorBoard writer's imports are seconds of a start
    with spans.span("setup/metric_writer"):
        writer = AsyncMetricWriter(
            MetricWriter(cfg.model_path), window=window,
            health=obs.health if obs.enabled else None,
            registry=obs.registry if obs.enabled else None,
            anomaly=anomaly, reporter=obs.fleet_reporter)
    if util is not None:
        writer.set_utilization(util, run_start=run_t0)
        if obs.enabled:
            obs.watch_utilization(writer, util)
    # run boundary marker: restarts append to metrics.jsonl, so bench /
    # post-mortem tooling splits runs on these records
    cfg_hash = config_hash(cfg)
    # Obs.identity is cfg-resolved (env overrides the dist_* knobs): the
    # marker must agree with the /healthz identity block
    writer.write_run_start(step0, cfg_hash, identity=obs.identity,
                           data_source=data_source,
                           mesh={k: int(v) for k, v in mesh.shape.items()},
                           n_devices=n_avail)
    run_log = RunLog(cfg.model_path)
    # train_steps (and the step counter) count macro slices, reference
    # run.py:155,249: one optimizer update advances the counter by
    # macro_batching, so the update loop runs in units of M slices.
    steps = args.steps or cfg.train_steps
    m = max(1, cfg.macro_batching)
    updates_total = -(-steps // m)
    u0 = step0 // m
    ckpt_every = max(1, cfg.steps_per_checkpoint // m)
    rng = jax.random.key(cfg.data_seed)
    t0 = time.time()
    # device prefetch: the feeder's cursor snapshots ride each batch, so
    # checkpoints record CONSUMED stream position only (DeviceFeeder doc);
    # synthetic batches stay indexed by UPDATE count, as before
    if pipe is not None:
        source, state_fn = iter(pipe), pipe.state_dict
    else:
        source = (synthetic_text_batch(cfg, i) for i in itertools.count(u0))
        state_fn = None
    with spans.span("setup/pipeline"):
        feeder = DeviceFeeder(source, cfg, trainer.mesh,
                              depth=cfg.device_prefetch_depth,
                              state_fn=state_fn,
                              registry=obs.registry if obs.enabled else None)
    # a program built once the first update is out is a fault worth a line
    # ("which step recompiled"): the compile log says which and how long
    rebuilt_seen, rebuilt_after = None, 0.0
    tracing = False
    u_done = u0  # updates actually dispatched (exhaustion can end early)
    # the try owns cleanup from the moment producer threads exist: an
    # exception anywhere below (obs wiring, window validation) must still
    # join the feeder + prefetcher, or they keep pinning device batches
    try:
        if obs.enabled:
            obs.watch_feeder(feeder)
        # steady state: cfg.profile_start >= 1 keeps the window past the
        # compile update (validated in config.py)
        profile_window = range(u0 + cfg.profile_start,
                               u0 + cfg.profile_start + cfg.profile_steps)
        if args.profile and profile_window.start >= updates_total:
            color_print(f"WARNING: --profile window starts at update "
                        f"{profile_window.start} but the run only "
                        f"dispatches updates [{u0}, {updates_total}); no "
                        f"trace will be captured — lower profile_start or "
                        f"raise --steps")
        tokens_per_update = cfg.train_batch_size * m * cfg.sequence_length
        dist_failure = None
        for u in range(u0, updates_total):
            # fault-injection site "step" keys on the GLOBAL counter so
            # e.g. sigterm@step25 survives a resume; inert without a plan
            faults.hit("step", value=step0 + (u - u0) * m)
            try:
                # distributed sites (peer:die@stepN, coordinator:drop@stepN)
                # poll on the same global counter; a detected failure stops
                # BEFORE the next dispatch so the tail below checkpoints
                # this host's healthy state, then train() exits
                # EXIT_PEER_LOST for the lockstep fleet relaunch
                dist.check_peers(step0 + (u - u0) * m)
            except dist.DistributedFailure as e:
                color_print(f"distributed failure observed at update {u} "
                            f"(step {step0 + (u - u0) * m}): {e}; cutting a "
                            "checkpoint before the fleet relaunch")
                dist_failure = e
                break
            if grace.triggered:
                # preemption: stop BEFORE dispatching another update — the
                # loop tail below cuts the grace checkpoint at the last
                # completed step and the process exits EXIT_PREEMPTED
                color_print(f"{grace.signame or 'signal'} received: "
                            f"stopping at update {u} "
                            f"(step {step0 + (u - u0) * m}) for the grace "
                            "checkpoint")
                break
            try:
                with spans.span("feed", update=u):
                    gb = next(feeder)
            except StopIteration:
                # single-epoch dataset exhausted (the reference's sequential
                # reader dies on OutOfRange here, inputs.py:540-541): stop
                # CLEANLY — final checkpoint below, clear message, no
                # traceback.  Set repeat_dataset=true for deterministic
                # epoch wrap-around.
                color_print(f"dataset exhausted after update {u} "
                            f"(step {step0 + (u - u0) * m}); stopping — set "
                            "repeat_dataset=true for multi-epoch runs")
                break
            if args.profile and u == profile_window.start:
                jax.profiler.start_trace(args.profile)
                tracing = True
            host_step = step0 + (u - u0) * m  # counter BEFORE this update
            grad_scale = None
            if telemetry_on:
                # fault site "grads": the caller-implemented "nan" action
                # feeds a NaN gradient scale into this one step so the
                # anomaly policies are drillable (grads:nan@stepN) — params
                # stay clean because skip_step masks the update in-graph
                if "nan" in faults.take("grads", value=host_step):
                    grad_scale = np.nan
            with spans.span("step", update=u,
                            **({"first": True} if u == u0 else {})):
                state, metrics = trainer.step(state, gb,
                                              jax.random.fold_in(rng, u),
                                              grad_scale=grad_scale)
            u_done = u + 1
            if rebuilt_seen != compile_log.LOG.recompiles:
                if rebuilt_seen is not None:
                    for record in compile_log.LOG.rebuilt(rebuilt_after):
                        color_print(f"update {u} (step {host_step}): JAX "
                                    f"built a program again: "
                                    f"{compile_log.describe(record)}")
                rebuilt_seen = compile_log.LOG.recompiles
                rebuilt_after = time.perf_counter()
            if telemetry_on:
                # host-side thinning: norm-class telemetry keys off the
                # telemetry_interval grid never transfer; sentinels always
                # do.  The grid keys on the GLOBAL update index so a
                # resumed run's norm rows land on the same steps as an
                # uninterrupted one's
                metrics = device_telemetry.thin(metrics, u,
                                                cfg.telemetry_interval)
            writer.write(host_step, metrics)
            if obs.enabled:
                obs.step_dispatched(tokens_per_update)
            if tracing and u + 1 >= profile_window.stop:
                # the window's last update just dispatched (exactly
                # profile_steps captured): drain the whole in-flight window
                # (blocks until every dispatched step finished) so the
                # trace captures complete steps, then stop
                writer.flush()
                jax.profiler.stop_trace()
                tracing = False
                color_print(f"profiler trace written to {args.profile}")
                _finalize_profile(cfg, args, trainer, obs)
            if cfg.debug_train_step or (u + 1) % 10 == 0:
                # debug_train_step: per-step prints (reference run.py:252-261)
                # showing the most recent COMPLETED loss — never a blocking
                # read of the in-flight one
                rate = (u + 1 - u0) / (time.time() - t0)
                loss_s = ("..." if writer.last_loss is None
                          else f"{writer.last_loss:.4f}")
                color_print(f"step {host_step + m} loss {loss_s} "
                            f"({rate:.2f} updates/s)")
            if ckpt is not None and (u + 1) % ckpt_every == 0:
                writer.flush()  # metrics.jsonl consistent with the checkpoint
                data_state = ({"pipeline": feeder.state_dict()}
                              if pipe is not None else None)
                # declared pause: a multi-second save must not read as a
                # stall on /healthz or trip the watchdog
                with spans.span("checkpoint", step=host_step + m), \
                        obs.pause("checkpoint"):
                    ckpt.save(state, data_state,
                              master_dtype=cfg.storage_dtype,
                              config_hash=cfg_hash)
                if obs.enabled:
                    # memory_stats() can sync the device, so it samples at
                    # the checkpoint cadence, never per step
                    obs.sample_device_memory()
    finally:
        # pipe first: its close() wakes a feeder producer blocked on the
        # host-prefetch queue, so the feeder join below cannot stall
        if pipe is not None and hasattr(pipe, "close"):
            pipe.close()
        feeder.close()
        try:
            # an exception exit (OOM, NaN guard, Ctrl-C) must still persist
            # the in-flight window's COMPLETED updates — those are exactly
            # the losses a post-mortem needs
            writer.flush()
        except device_telemetry.AnomalyHalt:
            # the halt sentinel drained during this exit flush (a short run
            # can end before the deferred window ever drains the anomalous
            # step): propagate — the tail below must NOT cut a checkpoint
            # of potentially-poisoned params
            raise
        except Exception:
            pass  # the failing step's own metrics may be unmaterializable
    if tracing:  # run ended inside the profile window
        writer.flush()
        jax.profiler.stop_trace()
        color_print(f"profiler trace written to {args.profile}")
        _finalize_profile(cfg, args, trainer, obs)
    if ckpt is not None:
        # on a grace exit this IS the grace checkpoint (save() waits on the
        # orbax barrier before writing sidecar + manifest, so returning
        # means durable — within GraceController's deadline timer)
        with spans.span("checkpoint", step=step0 + (u_done - u0) * m), \
                obs.pause("checkpoint"):
            ckpt.save(state,
                      {"pipeline": feeder.state_dict()} if pipe else None,
                      master_dtype=cfg.storage_dtype,
                      config_hash=cfg_hash)
        if obs.enabled:
            obs.sample_device_memory()
    # rows consumed per update = batch * macro_batching (grad_accumulation
    # only splits the delivered batch, it does not consume more data);
    # record DISPATCHED updates so exhaustion-shortened runs replay right
    run_log.append(steps=u_done - u0, batch_size=cfg.train_batch_size,
                   slice_count=slice_count, ctx=cfg.sequence_length,
                   grad_accumulation=cfg.macro_batching,
                   interleave_size=cfg.interleaved_datasets,
                   token_patch_size=cfg.token_patch_size)
    run_log.save()
    writer.close()  # drains any remaining window entries first
    if u_done > u0:
        color_print(f"trained {u_done - u0} updates; host blocked "
                    f"{writer.host_blocked_s:.2f}s in metric drains "
                    f"(window {window})")
    if dist_failure is not None:
        # the checkpoint above persisted this host's progress; now surface
        # the distributed failure so train() maps it to EXIT_PEER_LOST
        raise dist_failure


def _params_for_serving(cfg):
    if cfg.use_video:
        from .data.synthetic import synthetic_video_batch
        batch = _np_to_nt(synthetic_video_batch(cfg, 0), cfg)
    else:
        from .utils import random_text_batch
        batch = random_text_batch(cfg)
    if cfg.use_checkpointing:
        from .train import Checkpointer, Trainer
        state = Trainer(cfg).init(batch)
        state, _ = Checkpointer(os.path.join(cfg.model_path, "ckpt")).restore(state, cfg)
        params = state.params
    else:
        from .models import init_params
        params, _ = init_params(cfg, batch)
    from .models import pipeline_params_stacked, unstack_pipeline_params
    if pipeline_params_stacked(cfg, params):
        # pipeline-trained checkpoints store body params stage-stacked;
        # every serving/sampling consumer runs the plain sequential chain
        params = unstack_pipeline_params(cfg, params)
    return params


def _video_batches(cfg):
    """Real video batches when dataset files exist, else synthetic frames."""
    from .data import fs
    from .data.synthetic import synthetic_video_batch
    from .data.video import VideoPipeline
    globs = [d["path"] for d in cfg.dataset_configs if d.get("type") == "video"]
    paths = [p for g in globs for p in fs.glob(g)]
    if paths:
        return iter(VideoPipeline(cfg, cfg.train_batch_size, paths=paths))
    import itertools
    return (synthetic_video_batch(cfg, i) for i in itertools.count())


def _np_to_nt(np_batch, cfg):
    import jax.numpy as jnp
    from .data.feed import axes_for
    from .nd import NT
    return {k: NT(jnp.asarray(v), axes_for(k, v, cfg))
            for k, v in np_batch.items()}


def _sample_video(cfg, args) -> None:
    """Video sample mode: render input/output ``.avi`` files from real (or
    synthetic) frame streams (reference interface.py:101-139)."""
    import numpy as np
    from .infer.sampler import autoregressive_video, forward_logits
    from .serve.sample import render_video
    from .train import color_print
    params = _params_for_serving(cfg)
    batches = _video_batches(cfg)
    outdir = os.path.join(cfg.model_path, "samples")
    os.makedirs(outdir, exist_ok=True)
    t = cfg.time_patch_size
    for i in range(cfg.num_of_sample):
        np_batch = next(batches)
        nt = _np_to_nt(np_batch, cfg)
        if cfg.use_autoregressive_sampling:
            _, frames = autoregressive_video(cfg, params, nt)
            out = np.array(frames[0], np.float32, copy=True)[:t]
            # context positions are raw 0..255; generated ones are sigmoid
            # outputs in [0,1] (the reference blends the same way,
            # inference.py:39-40)
            pos0 = min(cfg.initial_autoregressive_position, t)
            out[:pos0] /= 255.0
        else:
            _, frame_out = forward_logits(cfg, params, nt)
            out = np.asarray(frame_out[0], np.float32)[:t]
            inp = np.asarray(np_batch["frame"][0], np.float32)[1:t + 1] / 255.0
            render_video(cfg, inp, os.path.join(outdir, f"sample_{i}_input.avi"))
        path = render_video(cfg, out,
                            os.path.join(outdir, f"sample_{i}_output.avi"))
        color_print(f"sample_idx: {i} -> {path}")


def sample(cfg, args) -> None:
    if cfg.debug_sample:
        # sample mode with debug_sample prints dataset-driven similarity
        # (reference interface.py:144-152)
        return debug_old(cfg, args)
    if cfg.model_mode == "jannet" and cfg.use_video:
        return _sample_video(cfg, args)
    from .serve import CompletionEngine, render_text_samples
    params = _params_for_serving(cfg)
    if not cfg.use_autoregressive_sampling:
        # dataset-driven single forward: print target vs one-step prediction
        # (reference interface.py:165-170); synthetic only when no dataset
        # files exist
        import jax
        import numpy as np
        from .data import dataset
        from .data.synthetic import synthetic_text_batch
        from .infer.sampler import make_single_forward
        from .serve.interface import tokenizer_for
        tok = tokenizer_for(cfg)
        fwd = make_single_forward(cfg, params)
        if _have_dataset_files(cfg):
            batches = iter(dataset(cfg, cfg.train_batch_size, prefetch=False))
        else:
            import itertools
            batches = ({"token_x": synthetic_text_batch(cfg, i)["token_x"]}
                       for i in itertools.count())
        for i in range(cfg.num_of_sample):
            nt = _np_to_nt(next(batches), cfg)["token_x"]
            out = np.asarray(fwd(nt, np.int32(0), np.float32(0.0),
                                 jax.random.key(i)))
            print("target:")
            print(tok.decode(np.asarray(nt.x)[0].reshape(-1)))
            print("\nsample:")
            print(tok.decode(out[0].reshape(-1)))
        return
    engine = CompletionEngine(cfg, params)
    for i in range(cfg.num_of_sample):
        out = engine.complete_tokens([int(cfg.concat_token)])
        render_text_samples(out[None], engine.tokenizer)


def query(cfg, args) -> None:
    from .serve import repl
    repl(cfg, _params_for_serving(cfg))


def start_web_api(cfg, args):
    """The server ``--run_mode web_api`` runs — restored or fresh-init params
    behind ``serve.serve`` on a background thread — returned live, so the
    chip smoke drives exactly what :func:`web_api` parks on."""
    from .serve import serve as rest_serve
    return rest_serve(cfg, _params_for_serving(cfg), port=args.port,
                      obs_port=getattr(args, "obs_port", None),
                      background=True)


def web_api(cfg, args) -> None:
    import signal
    import threading

    print(f"serving on :{args.port}", flush=True)
    server = start_web_api(cfg, args)
    grace = float(getattr(args, "grace_deadline_s", 30.0))
    stopped = threading.Event()

    def _drain_bg():
        server.drain(grace)
        stopped.set()

    def _on_sigterm(signum, frame):
        # drain off the signal frame: drain() blocks on in-flight streams
        # then shutdown()s, neither of which belongs in a handler
        threading.Thread(target=_drain_bg, daemon=True,
                         name="drain").start()

    try:
        signal.signal(signal.SIGTERM, _on_sigterm)
    except ValueError:  # not the main thread (embedded/test use)
        pass
    try:
        # serve_forever runs on the background thread; park here until a
        # SIGTERM drain stops the server
        while not stopped.wait(timeout=1.0):
            pass
    except KeyboardInterrupt:
        server.drain(grace)
    finally:
        server.server_close()


def debug(cfg, args) -> None:
    """Self-similarity nondeterminism check (reference interface.py:283-302)."""
    from .serve import CompletionEngine, similarity_score
    # debug sampling forces greedy autoregressive mode (reference
    # src/main.py:75-78)
    cfg.use_autoregressive_sampling = True
    cfg.sampling_temperature = 0
    params = _params_for_serving(cfg)
    n_samples = max(2, min(4, cfg.equal_debugging_items_per_check))
    if cfg.use_video:
        # video self-similarity: identical greedy rollouts must produce
        # bit-equal frames
        import jax

        from .data.synthetic import synthetic_video_batch
        from .infer.sampler import autoregressive_video
        batch = _np_to_nt(synthetic_video_batch(cfg, 0), cfg)
        fn = jax.jit(lambda p, b: autoregressive_video(cfg, p, b)[1])
        samples = [np.asarray(fn(params, batch), np.float32)
                   for _ in range(n_samples)]
        if not all(np.isfinite(s).all() for s in samples):
            raise SystemExit("non-finite frames generated — check the "
                             "checkpoint, not sampler determinism")
    else:
        engine = CompletionEngine(cfg, params, force_rebuild=True)
        prompt = list(range(min(16, cfg.vocab_size)))
        samples = [np.asarray(engine.complete_tokens(prompt, temperature=0.0))
                   for _ in range(n_samples)]
    score = similarity_score(samples)
    print(f"similarity: {score * 100:.2f}%")
    if score < 1.0:
        raise SystemExit("nondeterministic sampling detected")


def debug_old(cfg, args) -> None:
    """Dataset-driven similarity sampling (reference src/main.py:37-38,
    interface.py:144-152): one real dataset window duplicated to batch 2,
    greedy autoregressive samples, % agreement printed with both decodings."""
    import jax
    import numpy as np

    from .data import dataset
    from .infer.sampler import make_text_sampler
    from .nd import NT
    from .serve import similarity_score
    from .serve.interface import TEXT_AXES, tokenizer_for
    from .train import color_print

    params = _params_for_serving(cfg)
    if _have_dataset_files(cfg):
        np_batch = next(iter(dataset(cfg, 1)))
        token_x = np.asarray(np_batch["token_x"])[:1]
    else:
        color_print("no dataset files found; using synthetic prompt")
        from .data.synthetic import synthetic_text_batch
        token_x = synthetic_text_batch(cfg, 0)["token_x"][:1, :cfg.sequence_length
                                                          // cfg.token_patch_size]
    pos0 = max(1, min(cfg.initial_autoregressive_position,
                      cfg.sequence_length - 1)) // cfg.token_patch_size
    both = np.concatenate([token_x, token_x], axis=0)  # batch 2, same prompt
    sampler = make_text_sampler(cfg, params)
    out = np.asarray(sampler(NT(jax.numpy.asarray(both), TEXT_AXES),
                             np.int32(pos0), np.float32(0.0),
                             jax.random.key(0)))
    score = similarity_score([out[0], out[1]])
    tok = tokenizer_for(cfg)
    print(f"similarity score: {score * 100:.0f}%\n")
    color_print("Prompt:")
    print(tok.decode(out[0, :pos0].reshape(-1)))
    color_print("Output:")
    print(tok.decode(out[0, pos0:].reshape(-1)).rstrip())
    if score < 1.0:
        raise SystemExit("nondeterministic sampling detected")


RUN_MODE_FNS = {"train": train, "sample": sample, "query": query,
                "web_api": web_api, "debug": debug, "debug_old": debug_old}


def main(argv: typing.Optional[typing.Sequence[str]] = None) -> None:
    args = parse_args(argv)
    _init_distributed(args.tpu)
    from .config import Config
    with open(args.model) as f:
        raw = json.load(f)
    if args.run_mode != "train":
        # serving modes force batch size 1 (2 + greedy AR for debug_old) —
        # reference src/main.py:74-80
        raw["train"] = False
        if args.run_mode == "debug_old":
            raw["train_batch_size"] = 2
            raw["use_autoregressive_sampling"] = True
            raw["sampling_temperature"] = 0
            raw["debug_sample"] = True
        else:
            raw["train_batch_size"] = 1
    cfg = Config(raw)
    # every run mode joins the fleet (no-op single-host): serving/sampling
    # on a multi-host pod must see the global device set, exactly as the
    # pre-elastic --tpu path did; train() re-checks (idempotent) for
    # callers that enter it directly.  An init give-up maps to
    # EXIT_PEER_LOST here too — after a shared outage the coordinator may
    # simply be slow, and the supervisors must relaunch the fleet in
    # lockstep rather than classify every host as crash-looping
    from .reliability import EXIT_PEER_LOST, dist, faults
    # the plan must be armed BEFORE the init or the documented
    # dist_init:fail@N drill is silently inert on the CLI path; train()
    # re-installs the same plan (harmless — the init below short-circuits
    # on its second call, so a fired dist_init rule cannot refire)
    faults.install(cfg.fault_plan or None)
    try:
        dist.initialize(cfg)
    except dist.DistributedFailure as e:
        print(f"DISTRIBUTED INIT FAILURE: {e}; exiting with code "
              f"{EXIT_PEER_LOST} for a lockstep fleet relaunch")
        raise SystemExit(EXIT_PEER_LOST) from e
    from .utils import enable_compilation_cache
    enable_compilation_cache()
    if args.debug_grad:
        cfg.debug_gradients = True
    if args.workers is not None:  # reference src/main.py:60
        cfg.web_workers = args.workers
    RUN_MODE_FNS[args.run_mode](cfg, args)
