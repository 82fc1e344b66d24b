"""Metrics logging: colored stdout + JSONL scalars (+ optional TensorBoard).

The reference emits TensorBoard scalars from inside the TPU program via
``tpu.outside_compilation`` host calls flushed every step
(/root/reference/src/run/utils_run.py:32-58, run.py:123-153) and prints
timestamped ANSI-colored phase logs (src/utils_core.py:43-48).  In JAX the
metrics come back as ordinary step outputs, so logging is plain host code; a
TensorBoard event writer is used when the `tensorboardX`/`tf` stack exists,
else JSONL only (works everywhere, greppable, and what bench.py parses).
"""
from __future__ import annotations

import collections
import datetime
import hashlib
import json
import os
import time
import typing

import numpy as np

from ..obs import fleet, spans
from ..reliability import FLUSH_POLICY, retry_call

# log2-|grad| histogram bucket edges shared between the train step (which
# bins on-device, train/state.py) and the TensorBoard rendering below
GRAD_HIST_EDGES = np.arange(-30.0, 7.0, 1.0)
GRAD_HIST_PREFIX = "grad_hist/"


def color_print(*args, color: str = "\x1b[32;1m") -> None:
    now = datetime.datetime.now().strftime("%H:%M:%S.%f")[:-3]
    print(f"{color}[{now}]\x1b[0m", *args, flush=True)


def read_metric_rows(path: str) -> typing.List[dict]:
    """Rows of a ``metrics.jsonl`` that carry step metrics — run-start
    boundary markers (``write_run_start``) and any future marker records
    are skipped.  ``path`` is the file or its containing model dir.  THE
    reader every metrics.jsonl consumer should use (bench.py's guard and
    the test helpers do) so no consumer crashes on a marker row."""
    if os.path.isdir(path):
        path = os.path.join(path, "metrics.jsonl")
    with open(path) as f:  # graftcheck: disable=bare-io
        return [r for r in (json.loads(line) for line in f) if "loss" in r]


def config_hash(cfg) -> str:
    """Stable short hash of the full (derived) config — the run-start
    marker's identity, so post-mortem tooling can tell a resume from a
    hyperparameter change."""
    doc = json.dumps({k: str(v) for k, v in cfg.dict().items()},
                     sort_keys=True)
    return hashlib.sha256(doc.encode()).hexdigest()[:12]


class MetricWriter:
    def __init__(self, model_path: str, flush_every: int = 1):
        self.path = model_path
        os.makedirs(model_path, exist_ok=True)
        self._f = retry_call(
            lambda: open(os.path.join(model_path, "metrics.jsonl"), "a"),  # graftcheck: disable=bare-io
            site="metrics_open")
        self.flush_every = flush_every
        self._n = 0
        self._t0 = time.time()
        self._last_step_time = self._t0
        # utilization accounting (train/flops.py, set via set_utilization):
        # per-row mfu/tokens_per_sec derived from step_seconds, plus run
        # goodput = productive step seconds / wall seconds since run start
        self._util = None
        self._rows_in_run = 0
        self._productive_s = 0.0
        self.last_rates: typing.Dict[str, float] = {}
        self._tb = None
        try:  # optional TensorBoard backend
            from torch.utils.tensorboard import SummaryWriter  # noqa
            self._tb = SummaryWriter(os.path.join(model_path, "tb"))
        except Exception:
            pass

    def set_utilization(self, util, run_start: typing.Optional[float] = None
                        ) -> None:
        """Arm the live MFU/goodput accounting (a ``train.flops.Utilization``):
        every subsequent metric row carries ``mfu`` / ``tokens_per_sec`` /
        ``goodput`` derived from its own ``step_seconds``.

        ``run_start``: wall origin of the goodput denominator.  The caller
        passes the loop's TRUE entry time — this writer is constructed
        AFTER init/restore/compile, and a goodput that excluded exactly the
        overhead it exists to expose would read ~1.0 on a compile-dominated
        run."""
        self._util = util
        if run_start is not None and self._n == 0:
            self._t0 = float(run_start)
            self._last_step_time = self._t0

    def goodput(self) -> float:
        """Useful-step seconds / wall seconds since this writer (run)
        started.  The first row of each run is excluded from the productive
        numerator — its ``step_seconds`` spans compile + init, exactly the
        overhead goodput exists to expose."""
        wall = time.time() - self._t0
        return self._productive_s / wall if wall > 0 else 0.0

    def write_run_start(self, resume_step: int, cfg_hash: str,
                        identity: typing.Optional[dict] = None,
                        **run_facts) -> None:
        """Run boundary marker: ``metrics.jsonl`` appends across restarts, so
        every run begins with ``{"run_start": true, resume_step,
        config_hash, wall_time}`` plus the fleet identity (rank /
        world_size / coordinator / generation — obs/fleet.py) so the file
        itself says which host of which fleet generation wrote it, plus
        the caller's ``run_facts`` (main.py: ``data_source``, ``mesh``,
        ``n_devices``).
        ``identity``: the caller's cfg-resolved identity (main.py passes
        ``Obs.identity``) so config-driven multi-host runs — env vars
        unset, dist_* knobs set — record the same rank /healthz reports;
        the env-only fallback covers direct writer users.  Consumers that
        read metric rows must skip records without a ``"loss"``/``"step"``
        key (bench.py's guard and the test helpers do)."""
        doc = {"run_start": True, "resume_step": int(resume_step),
               "config_hash": cfg_hash, "wall_time": time.time(),
               **run_facts}
        ident = identity if identity is not None else fleet.identity()
        doc["rank"] = ident["rank"]
        doc["world_size"] = ident["world_size"]
        if ident["coordinator"]:
            doc["coordinator"] = ident["coordinator"]
        if "generation" in ident:
            doc["generation"] = ident["generation"]
        self._f.write(json.dumps(doc) + "\n")
        self._rows_in_run = 0
        self.flush()

    def write(self, step: int, metrics: typing.Dict[str, typing.Any],
              wall_time: typing.Optional[float] = None) -> None:
        """``wall_time``: when the step was DISPATCHED (the deferred drain
        below writes entries later; step_seconds must reflect the training
        cadence, not the drain cadence)."""
        now = time.time() if wall_time is None else wall_time
        scalars = {}
        hists = {}
        for k, v in metrics.items():
            try:
                arr = np.asarray(v)
            except Exception:
                continue
            if arr.size == 1:
                scalars[k] = float(arr)
            elif k.startswith(GRAD_HIST_PREFIX) and arr.ndim == 1:
                # histogram counts over GRAD_HIST_EDGES buckets emitted by
                # debug_gradients (train/state.py); other non-scalar metrics
                # are skipped
                hists[k] = arr.astype(np.float64)  # host-side TB writer, never traced — graftcheck: disable=dtype-promotion
        scalars["step"] = int(step)
        scalars["wall_time"] = now
        scalars["step_seconds"] = now - self._last_step_time
        self._last_step_time = now
        if self._util is not None:
            self._rows_in_run += 1
            if self._rows_in_run > 1:
                # the run's first step_seconds spans compile/init/restore —
                # not a training cadence; it stays out of both the rates and
                # the productive-time numerator
                self._productive_s += max(0.0, scalars["step_seconds"])
                self.last_rates = self._util.rates(scalars["step_seconds"])
                scalars.update(self.last_rates)
            scalars["goodput"] = round(self.goodput(), 6)
        self._f.write(json.dumps(scalars) + "\n")
        self._n += 1
        if self._n % self.flush_every == 0:
            self.flush()
        if self._tb is not None:
            for k, v in scalars.items():
                if k not in ("step", "wall_time"):
                    self._tb.add_scalar(k, v, step)
            for k, counts in hists.items():
                # counts over GRAD_HIST_EDGES buckets: reconstruct the
                # raw-stat form add_histogram_raw expects
                limits = GRAD_HIST_EDGES[1:][:len(counts)]
                n = float(counts.sum())
                if n <= 0:
                    continue
                centers = limits - 0.5
                self._tb.add_histogram_raw(
                    k, min=float(limits[0] - 1), max=float(limits[-1]),
                    num=n, sum=float((centers * counts).sum()),
                    sum_squares=float((centers ** 2 * counts).sum()),
                    bucket_limits=limits.tolist(),
                    bucket_counts=counts.tolist(), global_step=step)

    def flush(self) -> None:
        # bounded retry (FLUSH_POLICY): a transient EIO/ENOSPC blip must not
        # kill the run, but a wedged disk must not stall the step loop either
        retry_call(self._f.flush, site="metrics_flush", policy=FLUSH_POLICY)

    def close(self) -> None:
        self._f.close()
        if self._tb is not None:
            self._tb.close()


class AsyncMetricWriter:
    """Deferred metrics drain for the async-dispatch step loop (main.py,
    docs/performance.md).

    ``write`` only enqueues the step's still-on-device metrics; entries are
    materialized (the blocking device->host transfer) when they fall out of
    the bounded ``window`` — so the loop never synchronizes on the step it
    just dispatched, and up to ``window`` updates stay in flight.
    ``window=0`` drains every step immediately (the synchronous parity
    path).

    - ``last_loss``: loss of the most recent COMPLETED (drained) step — what
      progress prints show, never blocking on in-flight work.
    - ``host_blocked_s``: accumulated wall time inside the blocking
      device->host conversions (main.py prints it in the end-of-run
      summary; bench.py reports its own per-window figure).
    - ``flush()``: drain everything — called at checkpoints, before
      ``jax.profiler.stop_trace`` (so traces capture whole steps), and on
      exit.  Because draining the newest entry blocks until its metrics are
      ready, a returned ``flush()`` implies every dispatched step finished.
    """

    def __init__(self, writer: MetricWriter, window: int = 2,
                 health=None, registry=None, anomaly=None, reporter=None):
        """``health``/``registry`` (optional, docs/observability.md): each
        drained step reports to ``Health.step_completed`` (the /healthz +
        watchdog notion of progress — a step counts once its metrics
        materialized) and a drain-latency histogram.  ``anomaly`` (an
        ``obs.device_telemetry.AnomalyMonitor``) consumes each drained
        step's telemetry sentinels — counting skip_step skips, raising
        ``AnomalyHalt`` under the halt policy — AFTER the row is written,
        so the anomalous step itself is always in metrics.jsonl for the
        post-mortem.  ``reporter`` (an ``obs.fleet.FleetReporter``) posts
        each drained step's DISPATCH timestamp to the shared fleet dir for
        cross-rank skew attribution — drain-side like everything else
        here, so the dispatch hot path stays sync-free."""
        self.writer = writer
        self.window = max(0, int(window))
        self._anomaly = anomaly
        self._reporter = reporter
        self._pending: typing.Deque[typing.Tuple[int, float, dict]] = \
            collections.deque()
        self.last_loss: typing.Optional[float] = None
        self.host_blocked_s = 0.0
        self._health = health
        self._drain_hist = None if registry is None else registry.histogram(
            "hbnlp_metric_drain_seconds",
            "wall seconds blocked in the device->host metric pull per step")

    def write_run_start(self, resume_step: int, cfg_hash: str,
                        identity: typing.Optional[dict] = None,
                        **run_facts) -> None:
        self.writer.write_run_start(resume_step, cfg_hash,
                                    identity=identity, **run_facts)

    def set_utilization(self, util,
                        run_start: typing.Optional[float] = None) -> None:
        self.writer.set_utilization(util, run_start=run_start)

    def goodput(self) -> float:
        return self.writer.goodput()

    @property
    def last_rates(self) -> typing.Dict[str, float]:
        return self.writer.last_rates

    def write(self, step: int, metrics: typing.Dict[str, typing.Any]) -> None:
        self._pending.append((step, time.time(), metrics))
        while len(self._pending) > self.window:
            self._drain_one()

    def _drain_one(self) -> None:
        step, wall, metrics = self._pending.popleft()
        t0 = time.perf_counter()
        host = {}
        with spans.span("drain", step=step):
            for k, v in metrics.items():
                try:
                    host[k] = np.asarray(v)  # blocks until step completed
                except Exception:
                    host[k] = v
        blocked = time.perf_counter() - t0
        self.host_blocked_s += blocked
        if self._drain_hist is not None:
            self._drain_hist.observe(blocked)
        if self._health is not None:
            # dispatch wall, not drain wall: a flush() draining the whole
            # window back-to-back must not collapse the health EMA
            self._health.step_completed(step, dispatch_wall=wall)
        if self._reporter is not None:
            # same dispatch wall: fleet skew measures training cadence
            self._reporter.step_completed(step, dispatch_wall=wall)
        loss = host.get("loss")
        if loss is not None and getattr(loss, "size", 0) == 1:
            self.last_loss = float(loss)
        self.writer.write(step, host, wall_time=wall)
        if self._anomaly is not None:
            # after the write: a halt must not lose the anomalous row
            self._anomaly.observe(step, host)

    def flush(self) -> None:
        while self._pending:
            self._drain_one()
        self.writer.flush()

    def close(self) -> None:
        while self._pending:
            self._drain_one()
        self.writer.close()
