"""Train state + the pjit-compiled train step.

Replaces the reference's run layer graph assembly (/root/reference/src/run/
run.py:36-198) and macro-batching wrapper (src/run/train.py:19-77): what MTF
did with per-micro-batch graph rebuilds, cached variables and fused assign
ops is here one jitted function — gradient accumulation is a ``lax.scan``
over micro-batches, the optimizer update is traced inline, and GSPMD shards
everything according to parallel/sharding.py rules.
"""
from __future__ import annotations

import typing

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..config import Config
from ..obs import device_telemetry
from ..models import build, init_params
from ..models.ctx import Ctx
from ..nd import NT
from ..optim import Optimizer
from ..parallel import make_mesh, param_shardings, spec_for
from ..parallel.sharding import constraint


class TrainState(typing.NamedTuple):
    params: typing.Dict[str, jnp.ndarray]
    opt_state: typing.Dict[str, typing.Dict[str, jnp.ndarray]]
    step: jnp.ndarray  # int32 global update counter


class Trainer:
    """Owns mesh, optimizer, and the compiled train step."""

    def __init__(self, cfg: Config, mesh: typing.Optional[Mesh] = None):
        self.cfg = cfg
        self.mesh = mesh if mesh is not None else make_mesh(cfg)
        self.axes: typing.Dict[str, typing.Tuple[str, ...]] = {}
        self.optimizer: typing.Optional[Optimizer] = None
        self._step_fn = None
        self._compiled = None  # AOT executable (see step_cost_analysis)

    # -- initialization ------------------------------------------------------
    def init(self, batch: typing.Dict[str, NT], seed: int = 0) -> TrainState:
        """Initialize params on the mesh (sharded per axis rules) and zeroed
        optimizer state."""
        micro = self._micro_batch(batch)
        params, axes = init_params(self.cfg, micro, seed=seed)
        if self.cfg.pipeline_parallel > 1:
            # stage-stack the body params from init: leaves gain a leading
            # [P] axis mapped to the pipeline mesh axis, so params AND
            # optimizer slots live 1/P per device (ops/pipeline.py)
            from ..models import stack_pipeline_params
            params, axes = stack_pipeline_params(self.cfg, params, axes)
        self.axes = axes
        self.optimizer = Optimizer(self.cfg, axes)
        shardings = param_shardings(axes, self.mesh)
        params = {k: jax.device_put(v, shardings[k]) for k, v in params.items()}
        opt_state = self.optimizer.init(params)
        slot_axes = self.optimizer.slot_axis_names()
        opt_state = {
            name: {k: jax.device_put(
                v, NamedSharding(self.mesh, spec_for(slot_axes[name][k], self.mesh)))
                for k, v in slots.items()}
            for name, slots in opt_state.items()}
        step = jax.device_put(
            jnp.zeros((), jnp.int32),
            NamedSharding(self.mesh, PartitionSpec()))
        return TrainState(params, opt_state, step)

    @property
    def n_micro(self) -> int:
        """Micro-batches per train step.

        ``macro_batching`` inflates the host batch by M (the pipeline delivers
        ``train_batch_size * M`` rows, reference dataloader_placement.py:40-44)
        and ``grad_accumulation`` additionally splits each configured batch
        into G slices; the step scans all M*G micro-batches and applies ONE
        optimizer update from the averaged gradients (the reference applies
        ``fn="update"`` only on the last macro slice, src/run/train.py:50-56).
        """
        return self.cfg.grad_accumulation * self.cfg.macro_batching

    def _micro_batch(self, batch: typing.Dict[str, NT]) -> typing.Dict[str, NT]:
        """First micro-batch view of a (possibly accumulated) batch."""
        accum = self.n_micro
        if accum <= 1:
            return batch
        out = {}
        for k, t in batch.items():
            assert t.x.shape[0] % accum == 0, (
                f"batch axis {t.x.shape[0]} of {k!r} not divisible by "
                f"micro-batch count {accum}")
            out[k] = NT(t.x[:t.x.shape[0] // accum], t.names)
        return out

    # -- loss / gradients ----------------------------------------------------
    def _losses(self, params, batch, rng):
        ctx = Ctx(self.cfg, params=params, train=True, rng=rng, mesh=self.mesh)
        out = build(ctx, batch)
        return out

    def _grads(self, params, batch, rng):
        cfg = self.cfg
        if cfg.pipeline_parallel > 1 and cfg.pipeline_schedule == "1f1b":
            # loss and grads come from ONE interleaved pipeline schedule —
            # no outer jax.grad (models.pipelined_loss_and_grads)
            from ..models import pipelined_loss_and_grads
            # seed=0 is the same default Ctx seed _losses builds with, so
            # the 1F1B walk and the eval walk see identical apply-time
            # seed-dependent behavior
            return pipelined_loss_and_grads(cfg, params, batch, rng,
                                            self.mesh, seed=0)
        if cfg.multi_loss_strategy == "linear":
            def total(p):
                o = self._losses(p, batch, rng)
                return o.loss, o
            (loss, out), grads = jax.value_and_grad(total, has_aux=True)(params)
            return grads, out
        # per-loss gradients for pcgrad/mgda (reference gradients.py:65-66):
        # one forward (vjp) + one backward per loss via one-hot cotangents
        def losses_only(p):
            o = self._losses(p, batch, rng)
            return o.loss_list, o
        loss_list, vjp_fn, out = jax.vjp(losses_only, params, has_aux=True)
        n = len(loss_list)
        grads_per_loss = [
            vjp_fn(tuple(jnp.float32(1.0) if j == i else jnp.zeros_like(l)
                         for j, l in enumerate(loss_list)))[0]
            for i in range(n)]
        return self.optimizer.combine_losses(grads_per_loss), out

    # -- the step ------------------------------------------------------------
    def _make_step(self):
        cfg = self.cfg
        mesh = self.mesh
        accum = self.n_micro
        opt = self.optimizer
        # global_step counts macro slices, not updates, when macro-batching
        # (reference run.py:155-156: assign_add(global_step, macro_batching))
        step_increment = max(1, cfg.macro_batching)

        def aux_metrics(o):
            """Per-micro auxiliary losses as a flat dict (missing ones are
            simply absent — the model emits a consistent set per config)."""
            m = {}
            if o.token_loss is not None:
                m["token_loss"] = o.token_loss
            if o.video_loss is not None:
                m["video_loss"] = o.video_loss
            if o.accuracy is not None:
                m["accuracy"] = o.accuracy
            if o.expert_load is not None:
                # routed experts held here: the selected pairs that fell on
                # them, and the largest and mean load of one, this update;
                # the fullest layer's pairs, over the grouped product's
                # chunk (hybrid.expert_chunk), are the trips of its loop, and
                # over the rows those trips multiply (the rows that align a
                # run to the kernels' tile and the last chunk's tail among
                # them) the share of its products' rows that hold a pair
                pairs = jnp.sum(o.expert_load, -1)
                m["expert_pairs_held"] = jnp.sum(pairs)
                m["expert_pairs_layer_max"] = jnp.max(pairs)
                m["expert_rows_filled"] = jnp.max(pairs) / jnp.maximum(
                    o.expert_rows[jnp.argmax(pairs)], 1)
                m["expert_load_max"] = jnp.max(o.expert_load)
                m["expert_load_mean"] = jnp.mean(
                    o.expert_load.astype(jnp.float32))
            if o.dsa_kept is not None:
                # learned sparse attention, a counter a layer: the kept
                # pairs' share of the causal ones, and the indexer's loss
                for i in range(o.dsa_kept.shape[0]):
                    m[f"dsa_kept_pairs/{i}"] = o.dsa_kept[i]
                    m[f"dsa_indexer_kl/{i}"] = o.dsa_kl[i]
            return m

        # device telemetry (obs/device_telemetry.py): in-graph numerics and
        # the skip_step update mask.  With the knob off the step compiles
        # WITHOUT the grad_scale input or any telemetry op — the pre-existing
        # graph, bit-identical (the sync-parity goldens pin this).
        telemetry = cfg.telemetry_interval > 0
        skip_on_nonfinite = telemetry and cfg.anomaly_policy == "skip_step"

        def step_fn(state: TrainState, batch: typing.Dict[str, NT],
                    rng: jax.Array, grad_scale: jax.Array = None):
            batch = {k: constraint(t, mesh) for k, t in batch.items()}
            metrics = {}
            if accum <= 1:
                grads, out = self._grads(state.params, batch, rng)
                loss = out.loss
                metrics.update(aux_metrics(out))
            else:
                # scan over micro-batches, averaging gradients — the JAX form
                # of the reference's graph-stitched macro-batching
                # (src/run/train.py:19-77).
                def micro(i, t):
                    assert t.x.shape[0] % accum == 0, (
                        f"batch axis {t.x.shape[0]} not divisible by "
                        f"micro-batch count {accum}")
                    bsz = t.x.shape[0] // accum
                    return NT(jax.lax.dynamic_slice_in_dim(t.x, i * bsz, bsz, 0),
                              t.names)

                def body(carry, i):
                    mb = {k: micro(i, t) for k, t in batch.items()}
                    g, o = self._grads(state.params,
                                       mb, jax.random.fold_in(rng, i))
                    acc = jax.tree_util.tree_map(jnp.add, carry, g)
                    return acc, dict(loss=o.loss, **aux_metrics(o))

                zeros = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
                grads, per_micro = jax.lax.scan(body, zeros, jnp.arange(accum))
                grads = jax.tree_util.tree_map(lambda g: g / accum, grads)
                losses = per_micro.pop("loss")
                # reference reports first/last/mean of the macro batch
                # (src/run/train.py:48-52, run.py:123-132); the smoothing knob
                # picks which figure is THE loss
                metrics["first_loss"] = losses[0]
                metrics["last_loss"] = losses[-1]
                loss = (jnp.mean(losses) if cfg.macro_batch_loss_smoothing
                        else losses[-1])
                metrics.update({k: jnp.mean(v) for k, v in per_micro.items()})
            if cfg.pipeline_parallel > 1:
                # stage-replicated 'shared' tensors: stage-sum + re-broadcast
                # keeps the replicas bit-synced (models.stack_pipeline_params)
                from ..models import sync_shared_pipeline_grads
                grads = sync_shared_pipeline_grads(cfg, grads, self.axes)

            def norm_sq(name, g):
                """Stage-replicated shared tensors hold the SAME summed grad
                in every slice after the sync — count it once, so grad_norm
                matches the sequential model's."""
                s = jnp.sum(jnp.square(g.astype(jnp.float32)))
                from ..config import PIPE_STAGE
                ax = self.axes.get(name, ())
                if ("/shared_" in name and tuple(ax)[:1] == (PIPE_STAGE,)):
                    s = s / g.shape[0]
                return s

            if telemetry:
                # grad_scale rides the fully-formed gradients (post
                # accumulation/sync, pre optimizer): 1.0 in steady state
                # (exact in IEEE — values unchanged), NaN under the
                # "grads:nan@stepN" fault site so the anomaly path is
                # drillable without wrecking params
                grads = jax.tree_util.tree_map(
                    lambda g: g * grad_scale.astype(g.dtype), grads)
                grads_ok, nonfinite = device_telemetry.grads_finite(grads)
                skip = (~grads_ok) if skip_on_nonfinite else None
                # named scope: optimizer ops attribute to their own row in
                # graftprof's per-scope table instead of "(toplevel)"
                with jax.named_scope("optimizer"):
                    new_params, new_opt, lr, upd_sq = opt.update(
                        state.params, grads, state.opt_state, state.step,
                        skip=skip, collect_update_sq=True)
                metrics.update(device_telemetry.collect(
                    state.params, grads, upd_sq, grad_scale, nonfinite,
                    applied=(grads_ok if skip_on_nonfinite else None),
                    norm_sq_fn=norm_sq, groups=cfg.telemetry_groups))
            else:
                with jax.named_scope("optimizer"):
                    new_params, new_opt, lr = opt.update(
                        state.params, grads, state.opt_state, state.step)

            gnorm = jnp.sqrt(sum(norm_sq(k, g) for k, g in grads.items()))
            # no "step" entry: the loop computes step indices on host
            # (main.py async dispatch) — shipping the device counter back
            # every update is a needless D2H scalar the metric writer would
            # overwrite anyway
            metrics.update({
                "loss": loss,
                "learning_rate": lr,
                "grad_norm": gnorm,
            })
            if cfg.debug_gradients:
                # per-variable gradient norms + log2-magnitude histograms
                # (the reference's --debug_grad histogram stream,
                # src/run/run.py:147-153); the metric writer renders the
                # grad_hist/ vectors as TensorBoard histograms
                from .metrics import GRAD_HIST_EDGES
                edges = jnp.asarray(GRAD_HIST_EDGES)
                for name, g in grads.items():
                    gf = g.astype(jnp.float32)
                    metrics[f"grad_norm/{name}"] = jnp.sqrt(norm_sq(name, g))
                    mag = jnp.log2(jnp.abs(gf).reshape(-1) + 1e-38)
                    hist, _ = jnp.histogram(mag, bins=edges)
                    metrics[f"grad_hist/{name}"] = hist
            new_state = TrainState(new_params, new_opt,
                                   state.step + step_increment)
            return new_state, metrics

        return jax.jit(step_fn, donate_argnums=(0,))

    def step_extra_args(self, grad_scale: typing.Optional[float] = None
                        ) -> typing.Tuple:
        """Trailing step-function arguments beyond (state, batch, rng): the
        telemetry gradient scale when device telemetry is enabled, else
        nothing — so every caller (loop / bench / cost analysis / abstract
        trace) stays signature-compatible with both compiles.  A host
        ``np.float32`` (not a Python float): jit must treat it as a TRACED
        input, or the one NaN-injection step would trigger a recompile."""
        if self.cfg.telemetry_interval <= 0:
            if grad_scale is not None:
                raise ValueError("grad_scale requires telemetry_interval > 0")
            return ()
        return (np.float32(1.0 if grad_scale is None else grad_scale),)

    def step(self, state: TrainState, batch: typing.Dict[str, NT],
             rng: jax.Array, grad_scale: typing.Optional[float] = None):
        if self._step_fn is None:
            self._step_fn = self._make_step()
        args = (state, batch, rng) + self.step_extra_args(grad_scale)
        if self._compiled is not None:
            # AOT executable from step_cost_analysis (jit's dispatch cache is
            # separate, so calling the jit fn would compile a second time).
            # Arguments that no longer match it raise: falling back to jit
            # here would hide a second compile inside a timed window
            return self._compiled(*args)
        with self.mesh:
            return self._step_fn(*args)

    def step_cost_analysis(self, state: TrainState,
                           batch: typing.Dict[str, NT]
                           ) -> typing.Dict[str, float]:
        """XLA cost analysis (flops, bytes accessed) of the compiled train
        step.  The compiled executable is kept and reused by ``step`` so the
        analysis does not cost a second compilation (bench.py, and the live
        MFU accounting in train/flops.py)."""
        if self._step_fn is None:
            self._step_fn = self._make_step()
        with self.mesh:
            self._compiled = self._step_fn.lower(
                state, batch, jax.random.key(0),
                *self.step_extra_args()).compile()
        return dict(self._compiled.cost_analysis() or {})

    # -- reporting -----------------------------------------------------------
    def param_census(self, params: typing.Dict[str, jnp.ndarray]
                     ) -> typing.Dict[str, typing.Any]:
        """Parameter-count report (the reference's ``analyze_model``,
        src/run/utils_run.py:65-113) — sorted largest-first with a total."""
        rows = sorted(((k, int(v.size)) for k, v in params.items()),
                      key=lambda kv: -kv[1])
        return {"total": sum(s for _, s in rows), "by_variable": dict(rows)}
