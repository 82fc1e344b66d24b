"""Abstract tracing harness: config -> jaxprs of its train/eval/decode steps.

Everything here runs on CPU with ShapeDtypeStruct parameters — ``jax.jit(
step).trace(...)`` / ``jax.make_jaxpr`` stage the computation out without
allocating parameter memory, running FLOPs, or invoking XLA, so auditing the
flagship configs (billions of abstract parameter elements) takes seconds on a
laptop.  The resulting :class:`StepTrace` bundles expose:

- ``jaxpr``      — the ClosedJaxpr rule passes walk (:func:`iter_eqns`)
- ``args_info``  — donation metadata (train step only): the pytree of
  ``jax.stages.ArgInfo`` for the step's arguments
- ``mesh``       — the concrete mesh the step was traced under

Census counts exclude the vma-typing bookkeeping primitives
(``pvary``/``pbroadcast``): they move no data.
"""
from __future__ import annotations

import dataclasses
import typing

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..data.feed import axes_for
from ..data.synthetic import synthetic_text_batch, synthetic_video_batch
from ..models import build, pipeline_params_stacked, stack_pipeline_params
from ..models.ctx import Ctx
from ..nd import NT
from ..optim import Optimizer
from ..parallel import make_mesh
from ..train.state import Trainer, TrainState

#: data-moving collective primitives the census counts, with cross-version
#: name normalization.  vma bookkeeping (pvary/pbroadcast) is deliberately
#: absent: it moves no bytes and differs between typed/untyped toolchains.
COLLECTIVE_PRIMS: typing.Dict[str, str] = {
    "psum": "psum",
    "psum2": "psum",
    "psum_invariant": "psum",
    "all_gather": "all_gather",
    "all_gather_invariant": "all_gather",
    "all_to_all": "all_to_all",
    "ppermute": "ppermute",
    "reduce_scatter": "reduce_scatter",
    "psum_scatter": "reduce_scatter",
    "pgather": "pgather",
    "sharding_constraint": "sharding_constraint",
}


@dataclasses.dataclass
class StepTrace:
    name: str  # "train" | "eval" | "decode" | "prefill"
    jaxpr: typing.Any  # jax.core.ClosedJaxpr
    mesh: typing.Any
    args_info: typing.Any = None  # pytree of jax.stages.ArgInfo (train only)
    state_info: typing.Any = None  # the TrainState subtree of args_info
    #: logical axis names per flattened jaxpr input (the SPMD propagation
    #: seeds, analysis/spmd.py): one entry per invar — a tuple of axis
    #: names (possibly empty = replicated) or None (sharding unknown; the
    #: propagation follows instead of charging).  None entirely when the
    #: trace path could not build the seed list.
    in_axes: typing.Optional[typing.List[
        typing.Optional[typing.Tuple[str, ...]]]] = None


@dataclasses.dataclass
class ConfigTraces:
    config_name: str
    cfg: Config
    mesh: typing.Any
    steps: typing.Dict[str, StepTrace]
    param_axes: typing.Dict[str, typing.Tuple[str, ...]]
    param_shapes: typing.Dict[str, typing.Any]  # name -> ShapeDtypeStruct
    errors: typing.Dict[str, str]  # step -> repr of trace failure
    # abstract optimizer-slot shapes + their sharding axis names (for the
    # cost model's exact param+slot byte accounting); {} when params failed
    opt_state_shapes: typing.Dict[str, typing.Dict[str, typing.Any]] = (
        dataclasses.field(default_factory=dict))
    slot_axes: typing.Dict[str, typing.Dict[str, typing.Tuple[str, ...]]] = (
        dataclasses.field(default_factory=dict))


def iter_eqns(jaxpr) -> typing.Iterator:
    """Yield every equation of ``jaxpr`` (ClosedJaxpr or Jaxpr) and of every
    sub-jaxpr reachable through equation params (pjit/scan/while/cond/
    custom_vjp/shard_map/checkpoint), one yield per static call site."""
    inner = jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr
    for eqn in inner.eqns:
        yield eqn
        for v in eqn.params.values():
            vals = v if isinstance(v, (list, tuple)) else [v]
            for item in vals:
                if hasattr(item, "eqns") or (
                        hasattr(item, "jaxpr")
                        and hasattr(item.jaxpr, "eqns")):
                    yield from iter_eqns(item)


def iter_closed_jaxprs(jaxpr, _seen=None) -> typing.Iterator:
    """Yield ``jaxpr`` and every nested ClosedJaxpr once (for const walks)."""
    if _seen is None:
        _seen = set()
    if id(jaxpr) in _seen:
        return
    _seen.add(id(jaxpr))
    yield jaxpr
    inner = jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr
    for eqn in inner.eqns:
        for v in eqn.params.values():
            vals = v if isinstance(v, (list, tuple)) else [v]
            for item in vals:
                if hasattr(item, "eqns") or (
                        hasattr(item, "jaxpr")
                        and hasattr(item.jaxpr, "eqns")):
                    yield from iter_closed_jaxprs(item, _seen)


def eqn_location(eqn) -> str:
    """Best-effort ``file:line (fn)`` of an equation's user frame."""
    try:
        from jax._src import source_info_util
        return source_info_util.summarize(eqn.source_info)
    except Exception:
        return "<unknown>"


def abstract_batch(cfg: Config) -> typing.Dict[str, NT]:
    """Model-input batch with the exact shapes the data pipeline delivers
    (synthetic generators are the format reference), as tiny concrete arrays
    — token ids and masks only, never activations."""
    raw = (synthetic_video_batch(cfg) if cfg.use_video
           else synthetic_text_batch(cfg))
    return {k: NT(jnp.asarray(v), axes_for(k, v, cfg)) for k, v in raw.items()}


def abstract_params(cfg: Config, batch: typing.Dict[str, NT]
                    ) -> typing.Tuple[typing.Dict[str, typing.Any],
                                      typing.Dict[str, typing.Tuple[str, ...]]]:
    """(ShapeDtypeStruct params, axis-name metadata) via eval_shape — the
    abstract twin of ``models.init_params`` (no QR inits, no memory)."""
    meta: typing.Dict[str, typing.Tuple[str, ...]] = {}

    def _collect():
        ctx = Ctx(cfg, params=None, seed=0, train=False)
        build(ctx, batch)
        meta.update(ctx.axis_names)
        return ctx.collected

    params = jax.eval_shape(_collect)
    params, meta = dict(params), dict(meta)
    if cfg.pipeline_parallel > 1:
        # stage-stacked layout, abstractly: shapes via eval_shape, axis
        # metadata via a dummy value tree (the axis transform only needs keys)
        dummy = {k: np.zeros((1,), np.int8) for k in params}
        _, meta = stack_pipeline_params(cfg, dummy, meta)
        params = jax.eval_shape(lambda p: stack_pipeline_params(cfg, p),
                                params)
        assert pipeline_params_stacked(cfg, params)
    return params, meta


def _dict_axes(d: typing.Dict[str, typing.Any],
               fn: typing.Callable[[str], typing.Any]) -> typing.List:
    """Per-leaf seed entries of a flat dict in jax's flatten order (sorted
    keys) — the building block of a StepTrace's ``in_axes``."""
    return [fn(k) for k in sorted(d)]


def _param_in_axes(params: typing.Dict[str, typing.Any],
                   axes: typing.Dict[str, typing.Tuple[str, ...]]
                   ) -> typing.List:
    """Seed entries for a params dict: known axis metadata, else unknown
    (e.g. pipeline-unstacked decode params whose names left the metadata)."""
    return _dict_axes(params, lambda k: tuple(axes[k]) if k in axes else None)


def _check_in_axes(jaxpr, entries: typing.List
                   ) -> typing.Optional[typing.List]:
    """The seed list is only usable when it aligns 1:1 with the flattened
    invars; a mismatch (an arg subtree flattened differently than the seed
    construction assumed) degrades to None — the propagation then skips the
    step with a finding instead of mis-seeding silently."""
    inner = jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr
    return entries if len(entries) == len(inner.invars) else None


def _micro_sds(batch: typing.Dict[str, NT], n_micro: int
               ) -> typing.Dict[str, NT]:
    if n_micro <= 1:
        return batch
    return {k: NT(jnp.zeros((t.x.shape[0] // n_micro,) + t.x.shape[1:],
                            t.x.dtype), t.names)
            for k, t in batch.items()}


def trace_train(cfg: Config, mesh=None
                ) -> typing.Tuple[StepTrace, dict, dict, dict, dict]:
    """Trace the full jitted train step (grads + optimizer update) against
    abstract state.  Returns (StepTrace, param shapes, param axes,
    optimizer-slot shapes, slot sharding axes)."""
    mesh = make_mesh(cfg) if mesh is None else mesh
    batch = abstract_batch(cfg)
    trainer = Trainer(cfg, mesh)
    micro = _micro_sds(batch, trainer.n_micro)
    params, axes = abstract_params(cfg, micro)
    trainer.axes = axes
    trainer.optimizer = Optimizer(cfg, axes)
    opt_state = jax.eval_shape(trainer.optimizer.init, params)
    state = TrainState(params, opt_state,
                       jax.ShapeDtypeStruct((), jnp.int32))
    step = trainer._make_step()
    with mesh:
        # step_extra_args: telemetry-enabled configs take a grad_scale input
        traced = step.trace(state, batch, jax.random.key(0),
                            *trainer.step_extra_args())
    args_info = traced.args_info
    # args_info mirrors the call tree: ((state, batch, rng), {}) — the
    # TrainState subtree carries the donation bits the audit needs
    state_info = args_info[0][0]
    slot_axes = trainer.optimizer.slot_axis_names()
    # SPMD seeds, in TrainState's NamedTuple flatten order (params dict,
    # opt-slot dict-of-dicts, step scalar), then batch NTs, rng, extras
    in_axes: typing.List = _param_in_axes(params, axes)
    for name in sorted(opt_state):
        in_axes += _dict_axes(
            dict(opt_state[name]),
            lambda k, n=name: tuple(slot_axes.get(n, {}).get(k, ())))
    in_axes += [()]  # step counter
    in_axes += _dict_axes(batch, lambda k: tuple(batch[k].names))
    in_axes += [None]  # rng key
    in_axes += [() for _ in trainer.step_extra_args()]
    return (StepTrace("train", traced.jaxpr, mesh, args_info, state_info,
                      in_axes=_check_in_axes(traced.jaxpr, in_axes)),
            params, axes, dict(opt_state), slot_axes)


def trace_eval(cfg: Config, params, mesh=None, axes=None) -> StepTrace:
    """Trace the forward/eval walk (build -> total loss)."""
    mesh = make_mesh(cfg) if mesh is None else mesh
    batch = abstract_batch(cfg)

    def eval_fn(p, b):
        ctx = Ctx(cfg, params=p, train=False, rng=None, mesh=mesh)
        return build(ctx, b).loss

    with mesh:
        jaxpr = jax.make_jaxpr(eval_fn)(params, batch)
    in_axes = (_param_in_axes(params, axes or {})
               + _dict_axes(batch, lambda k: tuple(batch[k].names)))
    return StepTrace("eval", jaxpr, mesh,
                     in_axes=_check_in_axes(jaxpr, in_axes))


def decode_traceable(cfg: Config) -> bool:
    from ..infer.kv_cache import cache_eligible
    return bool(cfg.use_language) and not cfg.use_video and cache_eligible(cfg)


def trace_prefill(cfg: Config, params, mesh=None, axes=None) -> StepTrace:
    """Trace the decode PREFILL: one full-length forward that writes every
    prompt position's K/V at once (the serving cold path — its activation
    peak, not the per-token step's, is what bounds prompt length)."""
    from ..infer.kv_cache import _decode_logits
    mesh = make_mesh(cfg) if mesh is None else mesh
    names = ("batch", "sequence", "language_token_patch")
    seq = cfg.sequence_length // cfg.token_patch_size
    toks = jax.ShapeDtypeStruct((1, seq, cfg.token_patch_size), jnp.int32)
    if cfg.pipeline_parallel > 1 and pipeline_params_stacked(cfg, params):
        from ..models import unstack_pipeline_params
        params = jax.eval_shape(
            lambda p: unstack_pipeline_params(cfg, p), params)

    def prefill(p, t):
        return _decode_logits(cfg, p, t, jnp.int32(0), {}, seq, names)

    jaxpr = jax.make_jaxpr(prefill)(
        params, jnp.zeros(toks.shape, toks.dtype))
    in_axes = _param_in_axes(params, axes or {}) + [tuple(names)]
    return StepTrace("prefill", jaxpr, mesh,
                     in_axes=_check_in_axes(jaxpr, in_axes))


def trace_prefill_chunk(cfg: Config, params, mesh=None,
                        axes=None) -> StepTrace:
    """Trace ONE chunk-granular prefill forward: ``serve_prefill_chunk_rows``
    rows at a scalar running position against a populated cache — the
    executable the chunked admission path dispatches between decode steps
    (serve/engine.py::prefill_chunk_body).  Priced as its own step so the
    resource-budget audit stays honest when ``serve_prefill_chunk_tokens``
    is on: chunk activation peak scales with the chunk, not the prompt."""
    from ..infer.kv_cache import _decode_logits
    from ..serve.engine import prefill_chunk_rows
    mesh = make_mesh(cfg) if mesh is None else mesh
    names = ("batch", "sequence", "language_token_patch")
    seq = cfg.sequence_length // cfg.token_patch_size
    n_rows = prefill_chunk_rows(cfg)
    if n_rows <= 0:
        raise ValueError("trace_prefill_chunk needs "
                         "serve_prefill_chunk_tokens > 0")
    chunk = jax.ShapeDtypeStruct((1, n_rows, cfg.token_patch_size), jnp.int32)
    if cfg.pipeline_parallel > 1 and pipeline_params_stacked(cfg, params):
        from ..models import unstack_pipeline_params
        params = jax.eval_shape(
            lambda p: unstack_pipeline_params(cfg, p), params)

    def probe(p):
        return _decode_logits(
            cfg, p, jnp.zeros((1, 1, cfg.token_patch_size), jnp.int32),
            jnp.int32(0), {}, seq, names)[1]

    caches = jax.eval_shape(probe, params)

    def chunk_step(p, t, c):
        return _decode_logits(cfg, p, t, jnp.int32(0), c, seq, names)

    jaxpr = jax.make_jaxpr(chunk_step)(
        params, jnp.zeros(chunk.shape, chunk.dtype), caches)
    in_axes = (_param_in_axes(params, axes or {}) + [tuple(names)]
               + [None] * len(jax.tree_util.tree_leaves(caches)))
    return StepTrace("prefill_chunk", jaxpr, mesh,
                     in_axes=_check_in_axes(jaxpr, in_axes))


def trace_decode(cfg: Config, params, mesh=None, axes=None) -> StepTrace:
    """Trace ONE incremental KV-cached decode step (the serving hot path)."""
    from ..infer.kv_cache import _decode_logits
    mesh = make_mesh(cfg) if mesh is None else mesh
    names = ("batch", "sequence", "language_token_patch")
    seq = cfg.sequence_length // cfg.token_patch_size
    row = jax.ShapeDtypeStruct((1, 1, cfg.token_patch_size), jnp.int32)
    # decode runs the flat per-depth layout (serve/interface.py unstacks)
    if cfg.pipeline_parallel > 1 and pipeline_params_stacked(cfg, params):
        from ..models import unstack_pipeline_params
        params = jax.eval_shape(
            lambda p: unstack_pipeline_params(cfg, p), params)

    def probe(p):
        return _decode_logits(cfg, p, jnp.zeros(row.shape, row.dtype),
                              jnp.int32(0), {}, seq, names)[1]

    caches = jax.eval_shape(probe, params)

    def decode_step(p, r, c):
        return _decode_logits(cfg, p, r, jnp.int32(1), c, seq, names)

    jaxpr = jax.make_jaxpr(decode_step)(params, row, caches)
    in_axes = (_param_in_axes(params, axes or {}) + [tuple(names)]
               + [None] * len(jax.tree_util.tree_leaves(caches)))
    return StepTrace("decode", jaxpr, mesh,
                     in_axes=_check_in_axes(jaxpr, in_axes))


def trace_config(cfg: Config, config_name: str,
                 steps: typing.Sequence[str] = ("train", "decode"),
                 quiet: bool = False) -> ConfigTraces:
    """Trace the requested steps of one config, collecting per-step failures
    instead of aborting the whole audit.  ``quiet`` suppresses the local
    mesh's axis-fold warnings (the mesh searcher's internal traces would
    otherwise re-print the very warning its suggestion replaces)."""
    mesh = make_mesh(cfg, quiet=quiet)
    out: typing.Dict[str, StepTrace] = {}
    errors: typing.Dict[str, str] = {}
    params: typing.Dict[str, typing.Any] = {}
    axes: typing.Dict[str, typing.Tuple[str, ...]] = {}
    opt_shapes: typing.Dict[str, typing.Any] = {}
    slot_axes: typing.Dict[str, typing.Any] = {}
    if "train" in steps:
        try:
            out["train"], params, axes, opt_shapes, slot_axes = \
                trace_train(cfg, mesh)
        except Exception as e:  # surfaces as a trace-failure finding
            errors["train"] = f"{type(e).__name__}: {e}"
    if not params:
        try:
            trainer = Trainer(cfg, mesh)
            micro = _micro_sds(abstract_batch(cfg), trainer.n_micro)
            params, axes = abstract_params(cfg, micro)
        except Exception as e:
            errors.setdefault("params", f"{type(e).__name__}: {e}")
    if "eval" in steps and params:
        try:
            out["eval"] = trace_eval(cfg, params, mesh, axes=axes)
        except Exception as e:
            errors["eval"] = f"{type(e).__name__}: {e}"
    if "decode" in steps and params and decode_traceable(cfg):
        try:
            out["decode"] = trace_decode(cfg, params, mesh, axes=axes)
        except Exception as e:
            errors["decode"] = f"{type(e).__name__}: {e}"
    if "prefill" in steps and params and decode_traceable(cfg):
        try:
            out["prefill"] = trace_prefill(cfg, params, mesh, axes=axes)
        except Exception as e:
            errors["prefill"] = f"{type(e).__name__}: {e}"
    # the chunk executable rides along with "prefill" whenever the config
    # would actually compile it (serve_prefill_chunk_tokens > 0), and can
    # be requested explicitly; knob=0 configs trace exactly as before
    chunked = int(getattr(cfg, "serve_prefill_chunk_tokens", 0) or 0) > 0
    if (("prefill_chunk" in steps or ("prefill" in steps and chunked))
            and chunked and params and decode_traceable(cfg)):
        try:
            out["prefill_chunk"] = trace_prefill_chunk(cfg, params, mesh,
                                                       axes=axes)
        except Exception as e:
            errors["prefill_chunk"] = f"{type(e).__name__}: {e}"
    if params and not opt_shapes:
        # no successful train trace to reuse the slot shapes from
        try:
            opt = Optimizer(cfg, axes)
            opt_shapes = dict(jax.eval_shape(opt.init, params))
            slot_axes = opt.slot_axis_names()
        except Exception as e:
            errors.setdefault("opt_state", f"{type(e).__name__}: {e}")
    return ConfigTraces(config_name, cfg, mesh, out, axes, params, errors,
                        opt_state_shapes=dict(opt_shapes),
                        slot_axes=dict(slot_axes))
