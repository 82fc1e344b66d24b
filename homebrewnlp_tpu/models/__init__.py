"""Top-level model assembly: input -> body -> output -> loss.

Mirrors the reference's build pipeline (/root/reference/src/model/__init__.py:
_input :32-91, _body :94-130, _output :133-156, _loss :159-200, build :231-259)
re-designed for JAX: the "graph build" is tracing, memory-reduction strategies
map to jax.checkpoint / custom_vjp reversible chains, and all parallelism is
deferred to sharding constraints applied by the caller (parallel/apply.py).
"""
from __future__ import annotations

import logging
import typing

import jax
import jax.numpy as jnp

from .. import nd
from ..config import (BATCH, COLOR_CHANNELS, Config, HEADS, HEIGHT, INTERMEDIATE,
                      KEY, SEQUENCE, TOKEN_PATCH, VOCAB, WIDTH)
from ..nd import NT
from ..ops import pallas_mla, sparse_attention
from ..ops.losses import accuracy as _accuracy_fn
from ..ops.losses import softmax_cross_entropy_with_logits, video_l1_loss
from ..ops.reversible import make_reversible_chain
from .ctx import Args, Ctx, DEPTH_TOKEN
from .embedding import embed, gather, gather_embed, positional_embed
from .layers import fused_mixer_eligible
from .linear import linear, linear_from_features, linear_to_features
from .registry import block_part_fn


class ModelOutput(typing.NamedTuple):
    loss: jnp.ndarray
    loss_list: typing.Tuple[jnp.ndarray, ...]
    video_loss: typing.Optional[jnp.ndarray]
    accuracy: typing.Optional[jnp.ndarray]
    token_loss: typing.Optional[jnp.ndarray]
    frame_out: typing.Optional[NT]
    token_out: typing.Optional[NT]
    # [routed layers, experts held]: selected pairs that fell on each
    expert_load: typing.Optional[jnp.ndarray] = None
    # [routed layers]: rows each layer's grouped products multiplied
    expert_rows: typing.Optional[jnp.ndarray] = None
    # [sparse attention layers]: kept pairs over causal ones; indexer loss
    dsa_kept: typing.Optional[jnp.ndarray] = None
    dsa_kl: typing.Optional[jnp.ndarray] = None


# -- input ------------------------------------------------------------------

def _input(ctx: Ctx, batch: typing.Dict[str, NT], spatial_ctx: str
           ) -> typing.Tuple[NT, typing.Optional[NT]]:
    cfg = ctx.cfg
    tgt = None
    src = None
    if cfg.use_video:
        vid = batch["frame"].astype(cfg.calculation_dtype)
        base_args = Args(ctx, vid, [""])
        vid = ctx.dropout(vid, cfg.input_dropout)
        if cfg.use_bit_fold_input_pipeline:
            # unpack fold_count low-bit color values per packed int
            # (reference src/model/__init__.py:45-56); uint32 keeps all 32
            # packed bits without requiring jax x64
            folded = vid.x.astype(jnp.uint32)
            parts = []
            for unfold_idx in range(cfg.fold_count):
                part = (folded // (2 ** cfg.bit_fold_value) ** unfold_idx
                        ) % (2 ** cfg.bit_fold_value)
                parts.append(part.astype(jnp.uint8))
            vid = NT(jnp.concatenate(parts, vid.names.index(COLOR_CHANNELS)),
                     vid.names)
        vid = vid.astype(cfg.calculation_dtype) / 255
        ctx_dim = vid.names[1]  # "_sequence", length seq+1
        n = vid.dim_size(ctx_dim)
        tgt = nd.nt_slice(vid, ctx_dim, 1, n).rename(ctx_dim, SEQUENCE)
        src = nd.nt_slice(vid, ctx_dim, 0, n - 1).rename(ctx_dim, SEQUENCE)

        if cfg.empty_frame_embedding is not None:
            embed_args = base_args(list(cfg.empty_frame_embedding))
            frame_dims = [(name, src.dim_size(name)) for name in src.names[2:]]
            empty = embed(embed_args, frame_dims)
            for msk_name in ("vid_msk_src", "cat_mask_x"):
                msk = batch.get(msk_name)
                if msk is not None:
                    m = msk.astype(cfg.calculation_dtype)
                    src = src * m + empty * (1 - m)

        src = linear_to_features(base_args(src),
                                 [(COLOR_CHANNELS, src.dim_size(COLOR_CHANNELS))])
        for config_idx, config in enumerate(cfg.input_block_config):
            src = block_part_fn(ctx, config, src, f"vid_inp{config_idx}")

    if cfg.use_language:
        txt_src = batch["token_x"]
        base_args = Args(ctx, txt_src, [""])
        if cfg.factorized_embedding:
            small = int(cfg.intermediate_size * cfg.vocab_weight_factorization)
            txt, table = gather_embed(
                base_args(list(cfg.token_embedding)),
                [(VOCAB, cfg.vocab_size), (INTERMEDIATE, small)])
            ctx.text_input_embedding = table
            txt = ctx.dropout(txt, cfg.input_dropout)
            txt = linear_to_features(
                base_args(txt),
                [(TOKEN_PATCH, cfg.token_patch_size), (INTERMEDIATE, small)])
        else:
            # one stream-wide row a token (the patch holds one token)
            txt, table = gather_embed(
                base_args(list(cfg.token_embedding)),
                [(VOCAB, cfg.vocab_size)]
                + [(n, cfg.dims[n]) for n in cfg.feature_dims])
            ctx.text_input_embedding = table
            txt = ctx.dropout(nd.reduce_sum(txt, reduced=[TOKEN_PATCH]),
                              cfg.input_dropout)
        for config_idx, config in enumerate(cfg.input_block_config):
            txt = block_part_fn(ctx, config, txt, f"lang_inp{config_idx}")
        if not cfg.use_video:
            return txt, tgt
        return nd.concat([src, txt], spatial_ctx), tgt
    return src, tgt


# -- body -------------------------------------------------------------------

def _attn_layers(conf) -> int:
    return sum(l.split("-")[0] == "attention" for l in conf.layer)


def _block_scope(i: int, c: int) -> str:
    return f"{DEPTH_TOKEN}{i}_{c}"


def _block_param_keys(all_keys, root: str, i: int, c: int,
                      include_shared: bool = True) -> typing.List[str]:
    """Param keys of the (depth i, config c) block group.  ``include_shared``
    adds the cross-depth shared_{c} tensors (reference backend.py:43-94) —
    the stack/unstack transforms exclude them (they are replicated per stage
    instead, see stack_pipeline_params), while the pipelined body's slot
    dicts include them."""
    p1 = f"{root}/{_block_scope(i, c)}/"
    p2 = f"{root}/shared_{c}/"
    return sorted(k for k in all_keys
                  if k.startswith(p1) or (include_shared and k.startswith(p2)))


def _body(ctx: Ctx, src: NT) -> NT:
    cfg = ctx.cfg
    with ctx.scope("body"):
        if cfg.use_initial_position_embedding:
            base_args = Args(ctx, src, [""])
            for dim in [n for n in src.names if n not in cfg.feature_dims][1:]:
                fdims = [(n, cfg.dims[n]) for n in cfg.feature_dims]
                src = src + positional_embed(
                    base_args(list(cfg.position_embedding)), dim,
                    src.dim_size(dim), fdims)

        strategy = cfg.memory_reduction_strategy
        seq = [(i, c) for i in range(cfg.depth) for c in cfg.block_schedule[i]]
        attn_starts = []
        acc = ctx.attention_idx
        for i, c in seq:
            attn_starts.append(acc)
            acc += _attn_layers(cfg.block_config[c])

        if ctx.params is None or ctx.decode is not None:
            # init / collect mode: run the plain chain so parameters
            # materialize.  KV-cache decode takes the same path: there is no
            # backward pass so the memory-reduction machinery (which rebuilds
            # per-block sub-Ctxs that would drop the decode state) is skipped,
            # while the scope walk — and therefore every parameter path —
            # stays identical.
            if strategy in ("revnet", "momentum"):
                x1, x2 = (src, src) if strategy == "revnet" else (src, nd.zeros_like(src))
                for k, (i, c) in enumerate(seq):
                    ctx.attention_idx = attn_starts[k]
                    with ctx.scope(_block_scope(i, c)):
                        fx = block_part_fn(
                            ctx, cfg.block_config[c],
                            x2 if strategy == "revnet" else x1)
                    if strategy == "revnet":
                        x1, x2 = x2, x1 + fx
                    else:
                        x2 = x2 * cfg.momentumnet_alpha + fx * (1 - cfg.momentumnet_alpha)
                        x1 = x1 + x2
                ctx.attention_idx = acc
                return x1 + x2
            out = src
            for k, (i, c) in enumerate(seq):
                ctx.attention_idx = attn_starts[k]
                with ctx.scope(_block_scope(i, c)):
                    out = block_part_fn(ctx, cfg.block_config[c], out)
            ctx.attention_idx = acc
            return out

        if cfg.pipeline_parallel > 1 and ctx.mesh is not None:
            return _pipelined_body(ctx, src, seq, attn_starts, acc)

        # apply mode: each block runs in its own Ctx over a param subdict so
        # the reversible chain can take explicit per-block parameters.
        mode_scope = ctx._scope[0]
        root = f"{mode_scope}/body"
        all_keys = list(ctx.params.keys())

        def keys_for(i: int, c: int) -> typing.List[str]:
            return _block_param_keys(all_keys, root, i, c)

        def make_f(k: int, i: int, c: int, with_aux: bool = False):
            conf = cfg.block_config[c]
            a_start = attn_starts[k]
            rng = None if ctx.rng is None else jax.random.fold_in(ctx.rng, 1000 + k)

            def f(subparams: dict, x: NT):
                bctx = Ctx(cfg, params=subparams, train=ctx.train, seed=ctx.seed,
                           rng=rng, mesh=ctx.mesh)
                bctx.attention_idx = a_start
                with bctx.preset_scope(mode_scope, "body"), \
                        bctx.scope(_block_scope(i, c)):
                    out = block_part_fn(bctx, conf, x)
                if with_aux:
                    # aux losses (routed-MoE balance term, the sparse
                    # attention's indexer loss), the experts' load and the
                    # sparse attention's counters returned as real outputs
                    # so they cross jax.checkpoint (the losses with
                    # gradients intact); the per-block count is static (set
                    # by the block's layer specs), so the pytree structure
                    # is stable
                    return out, (tuple(bctx.aux_losses),
                                 tuple(bctx.expert_load),
                                 tuple(bctx.expert_rows),
                                 tuple(bctx.dsa_kept),
                                 tuple(bctx.dsa_kl))
                return out

            return f

        ctx.attention_idx = acc
        subparams = tuple({k: ctx.params[k] for k in keys_for(i, c)} for i, c in seq)

        if strategy in ("revnet", "momentum"):
            # aux losses cannot cross the reversible custom_vjp boundary;
            # config validation rejects routed_moe here when
            # moe_balance_weight > 0 (config.py)
            fs = [make_f(k, i, c) for k, (i, c) in enumerate(seq)]
            # remat skips fused-kernel blocks: their custom_vjp already
            # stores only inputs, so jax.checkpoint there would re-run the
            # forward kernel for nothing (measured +30 ms on 32mixer_group)
            rb = [cfg.reversible_remat_blocks
                  and not fused_mixer_eligible(ctx, cfg.block_config[c], src)
                  for _, c in seq]
            chain = make_reversible_chain(fs, mode=strategy,
                                          alpha=cfg.momentumnet_alpha,
                                          remat_blocks=rb)
            if strategy == "revnet":
                y1, y2 = chain(subparams, src, src)
            else:
                y1, y2 = chain(subparams, src, nd.zeros_like(src))
            return y1 + y2
        fs = [make_f(k, i, c, with_aux=True) for k, (i, c) in enumerate(seq)]
        # a part recomputes its forward in the backward but for the values
        # its layers name to keep (only a sparse attention names any, its
        # forward kernels' outputs, ops/sparse_attention.py; and attention
        # that walks its keys in cells, the forward's output and row
        # statistic, ops/pallas_mla.py)
        keep = jax.checkpoint_policies.save_only_these_names(
            *sparse_attention.SAVED, pallas_mla.KEPT)
        out = src
        for f, p in zip(fs, subparams):
            if strategy == "checkpoint":
                out, aux = jax.checkpoint(f, policy=keep)(p, out)
            else:
                out, aux = f(p, out)
            ctx.aux_losses.extend(aux[0])
            ctx.expert_load.extend(aux[1])
            ctx.expert_rows.extend(aux[2])
            ctx.dsa_kept.extend(aux[3])
            ctx.dsa_kl.extend(aux[4])
        return out


def _pipelined_body(ctx: Ctx, src: NT, seq, attn_starts, acc) -> NT:
    """GPipe pipeline-parallel body (ops/pipeline.py): the depth loop is cut
    into ``cfg.pipeline_parallel`` contiguous stages living on the pipeline
    mesh axis; microbatches stream through with activations hopping stages
    via ppermute.  Config validation guarantees P divides depth, so one
    stage function — scoped with stage 0's parameter names — serves every
    stage with its own stacked weights; cross-depth 'shared' tensors ride
    as stage-replicated leaves (stack_pipeline_params) kept bit-synced by
    the stage-summed grad broadcast (sync_shared_pipeline_grads).

    Parameters arrive STAGE-STACKED (``stack_pipeline_params``): the flat
    dict holds one ``[P, ...]`` leaf per stage-0 group key, sharded over the
    pipeline mesh axis, so each device holds only its own stage's weights —
    and optimizer state — with no per-step gather."""
    from ..ops.pipeline import gpipe
    from ..parallel.mesh import PIPE_AXIS
    cfg = ctx.cfg
    # aux-carrying layers (routed-MoE balance): thread the aux-loss stream
    # through the forward so eval/build() reports the same total loss the
    # 1F1B training path optimizes
    needs_aux = cfg.moe_balance_weight > 0 and any(
        spec.split("-")[0] == "routed_moe"
        for blk in cfg.block_config
        for spec in (blk["layer"] if isinstance(blk, dict) else blk.layer))
    stage_fn, stacked, n_stages = _pipeline_machinery(
        cfg, ctx.params, src.names, ctx.rng, ctx.train, ctx.seed,
        seq, attn_starts, mode_scope=ctx._scope[0], with_aux=needs_aux,
        mesh=ctx.mesh)
    # match the training schedule's micro partition: for 1F1B configs the
    # balance loss and capacity-dropped tokens of routed-MoE layers depend on
    # M, so eval/build() must pick the same M the 1F1B training path picks
    # (largest divisor with >= 8 rows) rather than gpipe's smallest
    n_micro = _pipeline_n_micro(src.x.shape[0], n_stages,
                                cfg.pipeline_schedule)
    if needs_aux:
        y, aux_total = gpipe(stage_fn, stacked, src.x, n_stages, n_micro,
                             ctx.mesh, PIPE_AXIS, with_aux=True)
        ctx.aux_losses.append(aux_total)
    else:
        y = gpipe(stage_fn, stacked, src.x, n_stages, n_micro, ctx.mesh,
                  PIPE_AXIS)
    ctx.attention_idx = acc
    return NT(y, names=src.names)


def _pipeline_machinery(cfg: Config, params, names, rng, train, seed,
                        seq, attn_starts, mode_scope, with_aux=False,
                        mesh=None):
    """(stage_fn, stacked slot list, n_stages) shared by the GPipe forward
    body and the 1F1B loss-and-grad path.  ``stage_fn(slot_params, idx, x)``
    runs one stage's block groups on one microbatch; ``stacked`` is the
    per-group list of stage-stacked param dicts (shared leaves replicated,
    see stack_pipeline_params).

    ``with_aux`` (the 1F1B contract): stage_fn returns ``(y, aux_loss)``
    where aux_loss is the f32 sum of the stage's layer-collected auxiliary
    loss terms (routed-MoE balance) — threaded through jax.checkpoint as a
    real output, exactly like the sequential body does."""
    n_stages = cfg.pipeline_parallel
    n_groups = len(seq)
    assert n_groups % n_stages == 0
    g = n_groups // n_stages
    root = f"{mode_scope}/body"
    all_keys = list(params.keys())
    if not pipeline_params_stacked(cfg, params):
        raise ValueError(
            "pipelined body expects stage-stacked parameters "
            "(models.stack_pipeline_params) but found per-depth keys for "
            f"stage-1 group {_block_scope(*seq[g])!r}")
    stacked = []
    for j in range(g):
        i0, c0 = seq[j]
        # include_shared: the stage-replicated shared_{c} leaves ride into
        # every group slot of their config (same stacked leaf; autodiff sums
        # the per-use cotangents, sync_shared_pipeline_grads sums stages)
        keys = _block_param_keys(all_keys, root, i0, c0, include_shared=True)
        stacked.append({k: params[k] for k in keys})

    def make_block_f(j: int):
        i0, c0 = seq[j]
        conf = cfg.block_config[c0]

        def f(subparams: dict, x_nt: NT, stage_idx):
            key = None
            if rng is not None:
                key = jax.random.fold_in(
                    jax.random.fold_in(rng, 2000 + j), stage_idx)
            # mesh=None: constraint() cannot fire inside the manual pipe
            # region; outer_mesh carries the real axis sizes for the
            # eligibility checks and the nested ring-attention path
            bctx = Ctx(cfg, params=subparams, train=train, seed=seed,
                       rng=key, mesh=None, outer_mesh=mesh)
            bctx.attention_idx = attn_starts[j]
            with bctx.preset_scope(mode_scope, "body"), \
                    bctx.scope(_block_scope(i0, c0)):
                out = block_part_fn(bctx, conf, x_nt)
            if not with_aux:
                return out
            aux = jnp.float32(0.0)
            for a in bctx.aux_losses:
                aux = aux + a.astype(jnp.float32)
            return out, aux

        return f

    block_fs = [make_block_f(j) for j in range(g)]
    remat = cfg.memory_reduction_strategy == "checkpoint"

    def stage_fn(slot_params, stage_idx, x):
        out = NT(x, names)
        aux_total = jnp.float32(0.0)
        for j, f in enumerate(block_fs):
            run = jax.checkpoint(f, static_argnums=()) if remat else f
            if with_aux:
                out, aux = run(slot_params[j], out, stage_idx)
                aux_total = aux_total + aux
            else:
                out = run(slot_params[j], out, stage_idx)
        return (out.x, aux_total) if with_aux else out.x

    return stage_fn, stacked, n_stages


def _pipeline_n_micro(batch: int, n_stages: int,
                      schedule: str = "gpipe") -> int:
    """Ideal M >= P microbatches keeps every stage busy; fall back to the
    largest batch divisor below P (with partial bubble) rather than silently
    serializing the whole pipe.

    GPipe picks the SMALLEST such M (its autodiff residuals hold every
    microbatch's internals, so M only shrinks the bubble at no memory gain
    for a fixed batch).  1F1B picks the LARGEST M keeping >= 8 rows per
    microbatch: its stash holds 2P stage inputs TOTAL (so memory shrinks as
    2P/M of the batch) and the bubble fraction 2(P-1)/(M+2P-2) falls with
    M; the row floor keeps per-tick matmuls tile-friendly."""
    divisors = [d for d in range(1, batch + 1) if batch % d == 0]
    at_least_p = [d for d in divisors if d >= n_stages]
    if schedule == "1f1b":
        big = [d for d in at_least_p if batch // d >= 8]
        if big:
            return max(big)
    n_micro = min(at_least_p) if at_least_p else max(divisors)
    if n_micro < n_stages:
        logging.getLogger(__name__).warning(
            "batch %d yields only %d pipeline microbatches for %d stages "
            "— pipe utilization %d/%d", batch, n_micro, n_stages, n_micro,
            n_stages)
    return n_micro


def pipelined_loss_and_grads(cfg: Config, params, batch, rng, mesh,
                             seed: int = 0):
    """1F1B training path (``pipeline_schedule='1f1b'``): loss AND grads
    from one interleaved pipeline schedule (ops/pipeline.py::pipeline_1f1b).

    The model is cut at the body pipeline: the input layer (+ optional body
    position embedding) runs upstream under ordinary autodiff, the body's
    stage stack runs inside the schedule, and the output/loss tail runs ON
    THE LAST STAGE per microbatch — its vjp seeds each microbatch's
    backward, which is what makes the M-independent activation memory of
    1F1B possible at all (an outer ``jax.grad`` over a forward-only
    pipeline cannot interleave).  Scope walks replicate ``build()`` exactly
    (same parameter names); config validation restricts the tail to the
    plain language loss (no accuracy/contrastive) in v1.

    Returns ``(grads, ModelOutput)`` like ``Trainer._grads``."""
    from ..ops.pipeline import pipeline_1f1b
    from ..parallel.mesh import PIPE_AXIS

    seq, g = _pipeline_seq(cfg)
    attn_starts = []
    acc = 0
    for i, c in seq:
        attn_starts.append(acc)
        acc += _attn_layers(cfg.block_config[c])
    root = f"{cfg.model_mode}/body"
    all_keys = list(params.keys())
    stage_keys = set()
    for j in range(g):
        i0, c0 = seq[j]
        stage_keys.update(_block_param_keys(all_keys, root, i0, c0,
                                            include_shared=True))
    other = {k: v for k, v in params.items() if k not in stage_keys}
    spatial_ctx = batch["token_y"].names[-2]

    def upstream(other_params):
        ctx = Ctx(cfg, params=other_params, train=True, rng=rng, mesh=mesh,
                  seed=seed)
        with ctx.scope(cfg.model_mode):
            src, _ = ctx.scoped("input", _input, ctx, batch, spatial_ctx)
            with ctx.scope("body"):
                if cfg.use_initial_position_embedding:
                    base_args = Args(ctx, src, [""])
                    for dim in [n for n in src.names
                                if n not in cfg.feature_dims][1:]:
                        fdims = [(n, cfg.dims[n]) for n in cfg.feature_dims]
                        src = src + positional_embed(
                            base_args(list(cfg.position_embedding)), dim,
                            src.dim_size(dim), fdims)
        return src

    src_nt, up_vjp = jax.vjp(upstream, other)
    names = src_nt.names

    # thread the caller's Ctx seed (the same value build()/_losses uses, so
    # any seed-dependent apply-time behavior matches the eval walk)
    stage_fn, stacked, n_stages = _pipeline_machinery(
        cfg, params, names, rng, True, seed, seq, attn_starts,
        mode_scope=cfg.model_mode, with_aux=True, mesh=mesh)
    n_micro = _pipeline_n_micro(src_nt.x.shape[0], n_stages, "1f1b")

    batch_keys = sorted(batch.keys())
    batch_names = {k: batch[k].names for k in batch_keys}
    tail_arrays = tuple(batch[k].x for k in batch_keys)

    def tail_fn(other_params, y, *tail_micro):
        micro_batch = {k: NT(a, batch_names[k])
                       for k, a in zip(batch_keys, tail_micro)}
        ctx = Ctx(cfg, params=other_params, train=True, seed=seed,
                  rng=None if rng is None else jax.random.fold_in(rng, 3001))
        with ctx.scope(cfg.model_mode):
            frame_out, token_out = ctx.scoped(
                "output", _output, ctx, NT(y, names), spatial_ctx)
            loss_list, token_loss, acc, _ = ctx.scoped(
                "loss", _loss, ctx, frame_out, token_out, micro_batch, None)
        total = loss_list[0]
        for l in loss_list[1:]:
            total = total + l
        # per-microbatch metrics ride the schedule's aux stream (averaged
        # over microbatches by the op, like the loss)
        aux = {"token_loss": token_loss.x if hasattr(token_loss, "x")
               else token_loss}
        if acc is not None:
            aux["accuracy"] = acc.x if hasattr(acc, "x") else acc
        return total, aux

    loss, aux, dstacked, dtail, dsrc = pipeline_1f1b(
        stage_fn, tail_fn, stacked, other, src_nt.x, tail_arrays,
        n_stages, n_micro, mesh, PIPE_AXIS)
    (dother_up,) = up_vjp(NT(dsrc.astype(src_nt.dtype), names))

    grads = {}
    for slot in dstacked:
        for k, v in slot.items():
            # shared leaves appear in every group slot of their config;
            # their per-slot contributions sum (matching autodiff)
            grads[k] = v if k not in grads else grads[k] + v
    for k in other:
        # both dicts always carry every key (vjp and the schedule's grad
        # carry produce full pytrees with zero leaves for unused params)
        grads[k] = dother_up[k].astype(jnp.float32) + dtail[k]
    out = ModelOutput(loss, (loss,), None, aux.get("accuracy"),
                      aux.get("token_loss"), None, None)
    return grads, out


# -- output -----------------------------------------------------------------

def _output(ctx: Ctx, out: NT, spatial_ctx: str
            ) -> typing.Tuple[typing.Optional[NT], typing.Optional[NT]]:
    cfg = ctx.cfg
    base_args = Args(ctx, out, [""])
    token_out = frame_out = None
    contrastive = cfg.contrastive_across_samples or cfg.contrastive_across_token_embeddings

    if cfg.use_language:
        token_out = out
        if cfg.use_video:
            token_out = nd.nt_slice(out, spatial_ctx, 0, cfg.language_token_patch)
        for config_idx, config in enumerate(cfg.output_block_config):
            token_out = block_part_fn(ctx, config, token_out, f"lang_out{config_idx}")
        if not contrastive:
            old = [(n, cfg.dims[n]) for n in cfg.feature_dims]
            new = [(TOKEN_PATCH, cfg.token_patch_size), (VOCAB, cfg.vocab_size)]
            table = embed(base_args(list(cfg.output_embedding)), old + new)
            out_names = tuple(n for n in token_out.names if n not in cfg.feature_dims
                              ) + (TOKEN_PATCH, VOCAB)
            token_out = nd.einsum([token_out, table], out_names)

    if cfg.use_video:
        start = cfg.language_token_patch * cfg.use_language
        frame_out = nd.nt_slice(out, spatial_ctx, start, out.dim_size(spatial_ctx))
        for config_idx, config in enumerate(cfg.output_block_config):
            frame_out = block_part_fn(ctx, config, frame_out, f"vid_out{config_idx}")
        frame_out = linear_from_features(
            Args(ctx, frame_out, [""]),
            [(COLOR_CHANNELS, cfg.channel_color_size)])
        frame_out = NT(jax.nn.sigmoid(frame_out.x), frame_out.names)

    return frame_out, token_out


# -- loss -------------------------------------------------------------------

def _loss(ctx: Ctx, frame_out, token_out, batch, vid_tgt):
    cfg = ctx.cfg
    loss_list: typing.List[jnp.ndarray] = []
    token_loss = acc = video_loss = None
    if cfg.use_language:
        txt_tgt = batch["token_y"]
        if cfg.contrastive_across_samples or cfg.contrastive_across_token_embeddings:
            sq = nd.reduce_sum(token_out * token_out, reduced=list(cfg.feature_dims))
            token_out = token_out / NT(jnp.sqrt(sq.x), sq.names)
        if cfg.contrastive_across_samples:
            sum_samples = nd.reduce_sum(token_out, reduced=[SEQUENCE])
            sum_batch = nd.reduce_sum(token_out, reduced=[BATCH])
            t1 = nd.einsum([sum_batch, sum_batch], []).x / cfg.train_batch_size
            t2 = nd.einsum([sum_samples, sum_samples], []).x / cfg.sequence_length
            token_loss = (t1 - t2) / (cfg.train_batch_size * cfg.sequence_length)
            token_loss = token_loss.astype(jnp.float32)
        elif cfg.contrastive_across_token_embeddings:
            table = ctx.text_input_embedding
            token_loss = nd.einsum([token_out, table], []).x.astype(jnp.float32)
            gathered = gather(Args(ctx, txt_tgt, [""]), table, [HEADS])
            token_loss = token_loss - 2 * nd.einsum(
                [token_out, gathered], []).x.astype(jnp.float32)
            token_loss = token_loss / (token_out.size * cfg.vocab_size)
        else:
            token_loss = softmax_cross_entropy_with_logits(token_out, txt_tgt, cfg.z_loss)
            if cfg.calc_accuracy:
                acc = _accuracy_fn(token_out, txt_tgt)
        loss_list.append(token_loss)

    if cfg.use_video:
        vid_msk = batch.get("vid_msk_tgt")
        cat_msk = batch.get("cat_mask_y")
        vmsk = vid_msk.astype(jnp.float32) if vid_msk is not None else None
        cmsk = cat_msk.astype(jnp.float32) if cat_msk is not None else None
        train_vl, video_loss = video_l1_loss(frame_out, vid_tgt, vmsk, cmsk)
        loss_list.append(train_vl)

    return loss_list, token_loss, acc, video_loss


# -- top level --------------------------------------------------------------

def build(ctx: Ctx, batch: typing.Dict[str, NT]) -> ModelOutput:
    """Assemble the full model and return losses/outputs.

    ``batch`` maps input names (token_x/token_y/frame/...masks) to NTs,
    mirroring the reference input pipeline shapes (dataclass.py:310-337)."""
    cfg = ctx.cfg
    with ctx.scope(cfg.model_mode):
        if cfg.use_language:
            spatial_ctx = batch["token_y"].names[-2]
        else:
            spatial_ctx = batch["frame"].names[2]
        src, vid_tgt = ctx.scoped("input", _input, ctx, batch, spatial_ctx)
        out = _body(ctx, src)  # pushes its own "body" scope
        frame_out, token_out = ctx.scoped("output", _output, ctx, out, spatial_ctx)
        loss_list, token_loss, acc, video_loss = ctx.scoped(
            "loss", _loss, ctx, frame_out, token_out, batch, vid_tgt)
        if ctx.aux_losses:
            # layer-collected auxiliary terms (routed-MoE load balance)
            aux = ctx.aux_losses[0]
            for a in ctx.aux_losses[1:]:
                aux = aux + a
            loss_list = [loss_list[0] + aux] + list(loss_list[1:])
    total = loss_list[0]
    for l in loss_list[1:]:
        total = total + l
    load, rows, kept, kl = (jnp.stack(x) if x else None
                            for x in (ctx.expert_load, ctx.expert_rows,
                                      ctx.dsa_kept, ctx.dsa_kl))
    return ModelOutput(total, tuple(loss_list), video_loss, acc, token_loss,
                       frame_out, token_out, load, rows, kept, kl)


def _pipeline_seq(cfg: Config):
    """(depth, block-config) group order + stage slot count for the
    pipelined body's stage-stacked parameter layout."""
    seq = [(i, c) for i in range(cfg.depth) for c in range(len(cfg.block_config))]
    assert len(seq) % cfg.pipeline_parallel == 0
    return seq, len(seq) // cfg.pipeline_parallel


def pipeline_params_stacked(cfg: Config, params) -> bool:
    """True when ``params`` carry the stage-stacked pipeline layout (no
    per-depth keys for stage-1's first block group)."""
    if cfg.pipeline_parallel <= 1:
        return False
    seq, g = _pipeline_seq(cfg)
    probe = f"{cfg.model_mode}/body/{_block_scope(*seq[g])}/"
    return not any(k.startswith(probe) for k in params)


def stack_pipeline_params(cfg: Config, params, axes=None):
    """Flat per-depth params -> the stage-stacked pipeline layout.

    Body block groups are cut into ``cfg.pipeline_parallel`` contiguous
    stages; each stage-0 group key keeps its name but its leaf becomes
    ``[P, ...]`` (stage s's slice = the corresponding group of stage s), and
    the other stages' per-depth keys disappear.  With ``axes`` metadata the
    new leaves gain a leading ``PIPE_STAGE`` axis name, which the sharding
    rules map to the pipeline mesh axis — params AND optimizer slots then
    live 1/P-sharded per device with no per-step gather (the residency the
    reference's model parallelism never had; our PP extension, SURVEY.md
    §2.12).  Returns ``params`` or ``(params, axes)`` matching the input.

    Values may be arrays OR pytrees of arrays (e.g. per-param optimizer slot
    dicts, whose structure is identical across depths) — each leaf is stacked
    stage-wise, which is what the flat->stacked checkpoint migration needs."""
    from ..config import PIPE_STAGE
    seq, g = _pipeline_seq(cfg)
    P = cfg.pipeline_parallel
    root = f"{cfg.model_mode}/body"
    all_keys = list(params.keys())
    out = dict(params)
    new_axes = None if axes is None else dict(axes)
    for j in range(g):
        i0, c0 = seq[j]
        for k in _block_param_keys(all_keys, root, i0, c0, include_shared=False):
            parts = []
            for s in range(P):
                i, c = seq[s * g + j]
                src = k.replace(f"/{_block_scope(i0, c0)}/",
                                f"/{_block_scope(i, c)}/")
                parts.append(params[src])
                if s > 0:
                    del out[src]
                    if new_axes is not None:
                        del new_axes[src]
            out[k] = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *parts)
            if new_axes is not None:
                new_axes[k] = (PIPE_STAGE,) + tuple(new_axes[k])
    # cross-depth 'shared' tensors: REPLICATED per stage (identical slices
    # under the stage axis).  Their grads are stage-summed and re-broadcast
    # (sync_shared_pipeline_grads), so the per-stage optimizer updates stay
    # bit-identical and the copies never diverge — exact cross-depth sharing
    # semantics with stage residency.
    for k in all_keys:
        if k.startswith(f"{root}/shared_"):
            out[k] = jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x[None], (P,) + x.shape), out[k])
            if new_axes is not None:
                new_axes[k] = (PIPE_STAGE,) + tuple(new_axes[k])
    return out if axes is None else (out, new_axes)


def unstack_pipeline_params(cfg: Config, params, axes=None):
    """Inverse of :func:`stack_pipeline_params`: recover the flat per-depth
    layout (used by inference/decode, which runs the plain chain)."""
    seq, g = _pipeline_seq(cfg)
    P = cfg.pipeline_parallel
    root = f"{cfg.model_mode}/body"
    all_keys = list(params.keys())
    out = dict(params)
    new_axes = None if axes is None else dict(axes)
    for j in range(g):
        i0, c0 = seq[j]
        for k in _block_param_keys(all_keys, root, i0, c0, include_shared=False):
            v = out.pop(k)
            assert v.shape[0] == P, (k, v.shape, P)
            base = None if new_axes is None else tuple(new_axes.pop(k))[1:]
            for s in range(P):
                i, c = seq[s * g + j]
                dst = k.replace(f"/{_block_scope(i0, c0)}/",
                                f"/{_block_scope(i, c)}/")
                out[dst] = v[s]
                if new_axes is not None:
                    new_axes[dst] = base
    # shared tensors: replicated slices (kept bit-identical by the grad
    # sync) — slice 0 recovers the single cross-depth tensor
    for k in all_keys:
        if k.startswith(f"{root}/shared_") and k in out:
            out[k] = jax.tree_util.tree_map(lambda x: x[0], out[k])
            if new_axes is not None:
                new_axes[k] = tuple(new_axes[k])[1:]
    return out if axes is None else (out, new_axes)


def sync_shared_pipeline_grads(cfg: Config, grads, axes):
    """Sum each stage-replicated 'shared' tensor's gradient over the stage
    axis and re-broadcast it.

    Exact cross-depth sharing semantics: the sequential model's shared-weight
    gradient is the sum over ALL depth uses; with per-stage copies each slice
    only accumulates its own stage's uses, so the stage-sum restores the
    total and the broadcast hands every stage the same gradient — identical
    per-stage optimizer updates keep the replicas bit-synced."""
    from ..config import PIPE_STAGE
    root = f"{cfg.model_mode}/body/shared_"
    out = dict(grads)
    for k, g in grads.items():
        if k.startswith(root) and tuple(axes.get(k, ()))[:1] == (PIPE_STAGE,):
            out[k] = jnp.broadcast_to(jnp.sum(g, axis=0, keepdims=True),
                                      g.shape)
    return out


def init_params(cfg: Config, batch: typing.Dict[str, NT], seed: int = 0
                ) -> typing.Tuple[typing.Dict[str, jnp.ndarray],
                                  typing.Dict[str, typing.Tuple[str, ...]]]:
    """Run the model in collect mode; returns (params, name->axis-names).

    The collect pass is jitted: parameter names/axes are Python-level side
    effects gathered at trace time, values come back as one fused XLA
    computation (all the QR inits compile together)."""
    meta: typing.Dict[str, typing.Tuple[str, ...]] = {}

    def _collect():
        ctx = Ctx(cfg, params=None, seed=seed, train=False)
        build(ctx, batch)
        meta.update(ctx.axis_names)
        return ctx.collected

    params = jax.jit(_collect)()
    return dict(params), dict(meta)
