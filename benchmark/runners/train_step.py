"""Runner `train_step`: a closed loop on `Trainer.step`.

The system under test is the program's `train/state.Trainer`: the one
jitted, donated update that `main._train_loop` calls.  This file drives it
with that loop's own discipline (at most `inflight_steps` updates in flight,
the loss of update i-2 pulled to the host each iteration) over a ring of
seeded batches that already sit on the device.  The input pipeline is
bypassed.

One object serves set-up and the window: `Program.drive` is the only place
that calls `Trainer.step`.  Set-up drives the first updates through it,
reads what `correct` compares from them (`followed_steps`), warms up, and
hands the same trainer and state to the window.

Weights come from the benchmark (the configuration's reference module makes
them from the seed in one device program); the program's own initialiser is
not run, only traced for names, shapes and axes.
"""
from __future__ import annotations

import collections
import gc
import time
import typing

import numpy as np


class Program:
    """The compiled update with its state, built once for a run."""

    def __init__(self, config: dict, traffic: dict, seed: int, reference,
                 spans, log):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec
        from homebrewnlp_tpu.config import Config
        from homebrewnlp_tpu.data.feed import to_global
        from homebrewnlp_tpu.models import build
        from homebrewnlp_tpu.models.ctx import Ctx
        from homebrewnlp_tpu.optim import Optimizer
        from homebrewnlp_tpu.parallel import param_shardings, spec_for
        from homebrewnlp_tpu.train import Trainer
        from homebrewnlp_tpu.train.state import TrainState
        import generate

        self.jax = jax
        self.seed = seed
        self.spans = spans
        self.traffic = traffic
        self.followed = int(config["benchmark"]["followed_steps"])
        self.reference = reference
        raw = {k: v for k, v in config.items() if k != "benchmark"}
        self.cfg = cfg = Config(raw)
        self.sizes = reference.Sizes.from_config(raw)
        self.tokens_per_step = cfg.train_batch_size * cfg.sequence_length
        self.host_batches = generate.token_batches(
            traffic, seed, cfg.train_batch_size, cfg.sequence_length,
            cfg.vocab_size)

        t0 = time.perf_counter()
        self.trainer = trainer = Trainer(cfg)
        mesh = trainer.mesh
        self.ring = [to_global({"token_x": x[..., None], "token_y": y[..., None]},
                               cfg, mesh) for x, y in self.host_batches]
        # names, shapes and axes of the parameters from an abstract trace of
        # the program's own initialiser: nothing compiles, nothing runs
        axes: dict = {}

        def collect():
            ctx = Ctx(cfg, params=None, seed=0, train=False)
            build(ctx, self.ring[0])
            axes.update(ctx.axis_names)
            return ctx.collected

        abstract = jax.eval_shape(collect)
        want = reference.shapes(self.sizes)
        have = {k: tuple(v.shape) for k, v in abstract.items()}
        if want != have:
            odd = sorted(set(want.items()) ^ set(have.items()))[:6]
            raise RuntimeError(f"program and reference disagree on the "
                               f"parameters: {odd}")
        trainer.axes = axes
        trainer.optimizer = Optimizer(cfg, axes)
        params = reference.init_weights(self.sizes, seed)
        shard = param_shardings(axes, mesh)
        params = {k: jax.device_put(v, shard[k]) for k, v in params.items()}
        slot_axes = trainer.optimizer.slot_axis_names()
        opt_state = {
            name: {k: jax.device_put(
                v, NamedSharding(mesh, spec_for(slot_axes[name][k], mesh)))
                for k, v in slots.items()}
            for name, slots in trainer.optimizer.init(params).items()}
        step = jax.device_put(jnp.zeros((), jnp.int32),
                              NamedSharding(mesh, PartitionSpec()))
        self.state = TrainState(params, opt_state, step)
        self.rng = jax.random.key(cfg.data_seed)
        self.index = 0
        jax.block_until_ready(self.state)
        log(f"init_s={time.perf_counter() - t0:.3f} "
            f"params={sum(int(np.prod(s)) for s in have.values())}")

    # -- the one call site of Trainer.step ----------------------------------
    def drive(self, keep_going: typing.Callable[[int], bool], hook=None
              ) -> typing.List[float]:
        """Dispatch updates while `keep_going(n_dispatched)`; finish the ones
        in flight; return every loss, in order.  The caller closes on
        `block_until_ready(self.state)`."""
        jax, spans = self.jax, self.spans
        inflight = int(self.traffic["inflight_steps"])
        pending: collections.deque = collections.deque()
        losses: typing.List[float] = []
        n = 0
        while keep_going(n):
            with spans.span("batch_pick"):
                gb = self.ring[self.index % len(self.ring)]
                key = jax.random.fold_in(self.rng, self.index)
            with spans.span("dispatch"):
                self.state, metrics = self.trainer.step(self.state, gb, key)
            pending.append(metrics["loss"])
            if hook is not None:
                hook(n, metrics)
            self.index += 1
            n += 1
            if len(pending) > inflight:
                with spans.span("loss_pull"):
                    losses.append(float(pending.popleft()))
        with spans.span("drain"):
            losses.extend(float(p) for p in pending)
            jax.block_until_ready(self.state)
        return losses

    # -- set-up: the followed updates, then warm-up --------------------------
    def follow_and_warm_up(self) -> dict:
        """Drive the first updates through `drive` and read from them what
        `correct` compares: each loss, the first gradient norm, the SM3 row
        mass after update 1 and the per-leaf norm of the parameters' change
        after the last followed update."""
        jax, ref = self.jax, self.reference
        followed = self.followed
        out: dict = {}

        def hook(n, metrics):
            if n == 0:
                out["grad_norm"] = [metrics["grad_norm"]]
                rows = {name: [slots[k] for k in sorted(
                    (k for k in slots if "/sm3/dim" in k),
                    key=lambda k: int(k.rsplit("dim", 1)[1]))]
                    for name, slots in self.state.opt_state.items()}
                out["sm3_leaf"] = ref.sm3_mass(rows)
            if n == followed - 1:
                # the parameters' change is read here, before update 4
                # donates them, against the seed's weights drawn again
                # inside the same device program: a second copy of the
                # weights as live buffers would stand in the run's peak
                out["change_leaf"] = np.asarray(ref.change_since_seed(
                    self.state.params, self.sizes, self.seed))

        losses = self.drive(lambda n: n < followed, hook)
        out["loss"] = losses
        out["grad_norm"] = [float(g) for g in out["grad_norm"]]
        out["sm3_leaf"] = np.asarray(out["sm3_leaf"])
        warm = int(self.traffic["warmup_steps"]) - followed
        if warm > 0:
            self.drive(lambda n: n < warm)
        return out

    def release(self) -> None:
        """Drop the state and the trainer so the reference has the chip."""
        self.state = self.trainer = self.ring = None
        gc.collect()


def run(cell: dict, seed: int, seconds: float, spans, log, window_hooks
        ) -> dict:
    """Set up, warm up, measure one window, release.  `window_hooks` is the
    harness's pair (open, close) called with the device drained just before
    the window opens and just after it closes (tracing, compile counting,
    the clock)."""
    t0 = time.perf_counter()
    program = Program(cell["config"], cell["traffic"], seed,
                      cell["reference"], spans, log)
    t1 = time.perf_counter()
    readings = program.follow_and_warm_up()
    t2 = time.perf_counter()
    log(f"build_s={t1 - t0:.3f} first_steps_and_warmup_s={t2 - t1:.3f}")
    import jax
    device = jax.local_devices()[0]
    at_open = device.memory_stats() or {}

    opened = window_hooks[0]()
    deadline = opened + seconds
    losses = program.drive(lambda n: time.perf_counter() < deadline)
    closed = window_hooks[1]()

    stats = device.memory_stats() or {}
    log("peak_bytes_in_use at the window's open "
        f"{at_open.get('peak_bytes_in_use')} at its close "
        f"{stats.get('peak_bytes_in_use')}")
    result = {
        "program": readings,
        "steps": len(losses),
        "tokens": len(losses) * program.tokens_per_step,
        "window_s": closed - opened,
        "first_loss": losses[0] if losses else None,
        "last_loss": losses[-1] if losses else None,
        # live buffers and loaded code are `in use`, the executables'
        # scratch arena is `reserved`: two high-water marks, so an upper
        # bound on the peak.  Set-up holds nothing the window does not.
        "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0)
                                 + stats.get("peak_bytes_reserved", 0)),
        "memory_stats": stats,
        "sizes": program.sizes,
        "host_batches": program.host_batches,
    }
    program.release()
    return result


def check(result: dict, cell: dict, seed: int, log=lambda m: None
          ) -> typing.List[typing.Tuple[str, float, float]]:
    """Follow the same updates with the plain reference, from the same seed
    and batches, and return (name, reading, limit) for every number
    compared.  Called once the window has closed, the peak has been read and
    the program's state is gone."""
    import compare
    conf = cell["config"]["benchmark"]
    ref = cell["reference"].follow(
        result["sizes"], seed, result["host_batches"],
        int(conf["followed_steps"]), int(conf["reference_rows"]))
    log("reference_step_s=" + ",".join(f"{t:.2f}" for t in ref["seconds"]))
    for key, leaf in compare.worst_leaves(result["program"], ref).items():
        log(f"worst {key}: {leaf}")
    got = compare.readings(result["program"], ref)
    log("readings " + " ".join(f"{k}={v:.4g}" for k, v in got.items()))
    return compare.against(got, cell["limits"])
