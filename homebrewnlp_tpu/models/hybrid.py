"""Block parts of sparse-expert decoders: linear, latent and grouped-query
attention, routed experts.

Six layers of the block DSL, each declared once (ROADMAP D8, R2, R3, R5):

- ``rms_norm[-scale]``: a norm without centering, over the stream's features;
- ``gated_feed_forward[-in:<act>]``: ``(act(x W_gate) * (x W_up)) W_down``;
- ``kda``: the gated delta-rule mixer with a decay for every channel, short
  causal convolutions on q, k and v, and a gated norm on its output
  (``ops/delta_rule.py``);
- ``mla[-rope]``: causal softmax attention whose keys and values are
  expanded from one low-rank latent a token, with no positions, or with
  rotary positions on the decoupled query and key parts (``-rope``;
  ``ops/rotary.py``, ``ops/block_attention.py``);
- ``gqa-<layer type>[-gated][-qknorm][-sparse]``: causal softmax attention
  whose query heads share fewer K/V heads, with rotary positions from the
  layer type's table and, on a ``sliding_attention`` layer, a window
  (``ops/rotary.py``, ``ops/block_attention.py``); ``gqa-nope[-gated]`` is
  the layer without positions (``use_rope`` false), ``gated`` the one whose
  result a sigmoid gate of the layer's input multiplies (``use_gqa_gate``),
  ``qknorm`` the one that norms q and k per head, ``sparse`` the one whose
  rows see only the keys a learned indexer picks (``sa_config``,
  ``ops/sparse_attention.py``);
- ``routed_moe[-topk<k>][-sigmoid][-bias][-gated][-shared<n>][-in:<act>]``:
  the one routed expert layer, with nothing dropped (``ops/grouped_ffn.py``).

The mixers' heads and head widths are their own (``linear_attn_config``,
``qk_nope_head_dim``, ``head_dim`` ...): the stream's ``(heads,
features_per_head)`` pair says how wide the residual is, not how wide a
layer is inside.  Serving these layers is out of scope here:
``infer/kv_cache.py::cache_eligible`` says what is missing.
"""
from __future__ import annotations

import typing

import jax
import jax.numpy as jnp

from .. import nd
from ..config import (CONV_TAP, EXPERT_INTERMEDIATE, INDEX_HEADS, INDEX_KEY,
                      INTERMEDIATE, KV_HEADS, LATENT, LOW_RANK, MIXER_HEADS,
                      MIXER_KEY, ROUTED_EXPERTS, SEQUENCE)
from ..nd import NT
from ..ops import grouped_ffn as gf
from ..ops import rotary
from ..ops.activations import PLAIN, activate
from ..ops.block_attention import causal_attention
from ..ops.delta_rule import chunked_kda
from ..ops.pallas_gmm import grouped_dot, row_tile
from .ctx import Args
from .linear import Dim, linear, normal_var, orthogonal_var


#: pairs of a chunk of the grouped product, in balanced loads of this share
EXPERT_CHUNK_LOADS = 4


def expert_chunk(tokens: int, topk: int, held: int, experts: int) -> int:
    """Pairs of a chunk of the grouped product (``ops/grouped_ffn.py``), from
    the share of the experts held here: ``EXPERT_CHUNK_LOADS`` times what a
    balanced router sends this share (``topk * held / experts`` pairs a
    token), and no more than all pairs.  A chunk is multiplied by
    ``ops/pallas_gmm.py``'s Mosaic kernels where its shape lets them (all
    three hybrid cells' does), as one row tile an expert more rows than
    pairs, else by ``jax.lax.ragged_dot``.  8 of 256 experts under top-8 get
    ``tokens`` pairs; 8 of 320 get 6,556 for 8,192 tokens (8,704 rows on
    tiles); 16 of 64 get all ``tokens * topk`` pairs, so their loop takes
    one trip whatever the routing and the step's time cannot follow it
    (random weights on the toy language send up to 110,000 of a step's
    131,072 pairs to 16 held experts, and 33,000 on another seed: under a
    chunk of twice the balanced load the trips, and 8% of ``tokens_per_s``,
    followed the seed; PERF.md, PR 31)."""
    balanced = -(-tokens * topk * held // experts)
    return min(EXPERT_CHUNK_LOADS * balanced, tokens * topk)


def _fdims(args: Args) -> typing.List[Dim]:
    return [(n, args.cfg.dims[n]) for n in args.cfg.feature_dims]


def _rms(x, weight, eps: float):
    """``x * rsqrt(mean(x^2) + eps) * weight`` over the last axis, float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps
                             ) * weight.astype(jnp.float32)


def _activation(args: Args, default: str) -> str:
    named = [a[len("in:"):] for a in args if a.startswith("in:")]
    return named[0] if named else default


def _matrix(args: Args, name: str, old: typing.Sequence[Dim],
            new: typing.Sequence[Dim]) -> NT:
    return orthogonal_var(args, list(old) + list(new), old, name=name)


def _project(args: Args, name: str, x: NT, old: typing.Sequence[Dim],
             new: typing.Sequence[Dim]) -> NT:
    """``x`` times a named matrix, contracting ``old``."""
    out = [n for n in x.names if n not in {o for o, _ in old}] + [
        n for n, _ in new]
    return nd.einsum([x, _matrix(args, name, old, new)], out)


# -- norm and feed-forward ----------------------------------------------------

def rms_norm(args: Args) -> NT:
    """``x * rsqrt(mean(x^2) + eps)`` over the stream's features: no
    centering, no shift; ``scale`` multiplies by a learned weight."""
    t = args.tensor
    fdims = _fdims(args)
    xf = NT(t.x.astype(jnp.float32), t.names)
    mean_sq = nd.reduce_mean(xf * xf, reduced=[n for n, _ in fdims])
    out = xf * NT(jax.lax.rsqrt(mean_sq.x + args.cfg.rms_norm_eps),
                  mean_sq.names)
    if "scale" in args:
        p = normal_var(args, fdims, mean=1.0, name="scale")
        out = out * NT(p.x.astype(jnp.float32), p.names)
    return NT(out.x.astype(t.x.dtype), out.names).transpose_to(t.names)


def _feed_forward(args: Args, width: Dim, act: str, gated: bool = True
                  ) -> NT:
    """``(act(x W_gate) * (x W_up)) W_down``, or ``act(x W_in) W_out``."""
    fdims = _fdims(args)
    hidden = activate(args([act])(linear(args, fdims, [width])))
    if gated:
        hidden = hidden * linear(args, fdims, [width])
    return linear(args(hidden), [width], fdims)


def gated_feed_forward(args: Args) -> NT:
    return _feed_forward(args, (INTERMEDIATE, args.cfg.intermediate_size),
                         _activation(args, "silu"))


# -- kda ----------------------------------------------------------------------

def _short_conv(x: NT, taps: NT) -> NT:
    """Causal depthwise convolution over the sequence, one filter a channel:
    ``y_t = sum_j w_j x_{t - (taps - 1) + j}``."""
    axis = x.names.index(SEQUENCE)
    n = taps.dim_size(CONV_TAP)
    length = x.x.shape[axis]
    padded = jnp.pad(x.x, [(n - 1, 0) if a == axis else (0, 0)
                           for a in range(x.x.ndim)])
    w = taps.x.astype(x.x.dtype)
    out = sum(jax.lax.slice_in_dim(padded, j, j + length, axis=axis) * w[j]
              for j in range(n))
    return NT(out, x.names)


def kda(args: Args) -> NT:
    """Kimi delta attention: see ``ops/delta_rule.py`` for the recurrence.

        q, k, v = silu(conv(u W_q)), silu(conv(u W_k)), silu(conv(u W_v))
        q = q / |q| * d^-1/2,  k = k / |k|                   (per head)
        g = -exp(a_log) * softplus((u W_fa) W_fb + dt_bias)  (per channel)
        beta = sigmoid(u W_beta)                             (per head)
        y = (rms_head(o) * sigmoid((u W_ga) W_gb)) W_o

    Under ``kda_allow_neg_eigval`` ``beta = 2 sigmoid(u W_beta)``: ``I - beta
    k k^T`` then has its eigenvalue along ``k`` in (-1, 1), not in (0, 1).
    """
    cfg, ctx, u = args.cfg, args.ctx, args.tensor
    conf = cfg.linear_attn_config
    heads, width = (MIXER_HEADS, conf["num_heads"]), (MIXER_KEY,
                                                      conf["head_dim"])
    taps = (CONV_TAP, conf["short_conv_kernel_size"])
    rank = (LOW_RANK, conf["head_dim"])
    fdims = _fdims(args)
    f32 = jnp.float32

    with ctx.scope("conv"):
        qkv = []
        for n in "qkv":
            mixed = _short_conv(
                _project(args, f"{n}_proj", u, fdims, [heads, width]),
                normal_var(args, [taps, heads, width], taps[1] ** -0.5,
                           name=f"{n}_conv"))
            qkv.append(NT(jax.nn.silu(mixed.x), mixed.names))
        q, k, v = qkv
    with ctx.scope("gates"):
        low = _project(args, "decay_down", u, fdims, [rank])
        decay = _project(args, "decay_up", low, [rank], [heads, width])
        dt_bias = normal_var(args, [heads, width], 1.0, -2.0, name="dt_bias")
        a_log = normal_var(args, [heads], 0.5, name="a_log")
        g = -jnp.exp(a_log.x.astype(f32))[:, None] * jax.nn.softplus(
            decay.x.astype(f32) + dt_bias.x.astype(f32))
        beta = jax.nn.sigmoid(_project(args, "beta", u, fdims, [heads]
                                       ).x.astype(f32))
        if cfg.kda_allow_neg_eigval:
            beta = 2 * beta
        low = _project(args, "out_down", u, fdims, [rank])
        gate = jax.nn.sigmoid(_project(args, "out_up", low, [rank],
                                       [heads, width]).x.astype(f32))
    with ctx.scope("chunk_scan"):
        def unit(x):
            x = x.astype(f32)
            return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1,
                                             keepdims=True) + 1e-6)
        kind = u.x.dtype
        o = chunked_kda((unit(q.x) * width[1] ** -0.5).astype(kind),
                        unit(k.x).astype(kind), v.x, g, beta,
                        wide_beta=cfg.kda_allow_neg_eigval)
    with ctx.scope("out"):
        scale = normal_var(args, [width], mean=1.0, name="norm_scale")
        o = NT((_rms(o, scale.x, cfg.rms_norm_eps) * gate).astype(kind),
               q.names)
        return _project(args, "proj", o, [heads, width], fdims
                        ).transpose_to(u.names)


# -- mla ----------------------------------------------------------------------

def mla(args: Args) -> NT:
    """Latent K/V attention, for training (K and V are expanded from the
    latent; the absorbed form is a serving matter):

        q = u W_q -> [heads, nope + rope]:  q_nope, q_pe
        c = u W_kva;  c_kv = rms(c[:rank]),  k_pe = c[rank:]   (one a token)
        mla-rope: q_pe, k_pe = rot(q_pe, pos), rot(k_pe, pos)  (once a token)
        [k_nope, v] = c_kv W_kvb;  k_h = [k_nope_h, k_pe]
        y = concat_h softmax(q_h k_h^T / sqrt(nope + rope) + causal) v_h W_o

    ``mla`` has no positions (``mla_use_nope`` true); ``mla-rope`` turns the
    decoupled parts by the default rotary table at ``rope_theta``, in
    ``(x_2i, x_2i+1)`` pairs under ``rope_interleave`` (``ops/rotary.py``,
    float32; the scale goes into ``q`` before its one rounding), the shared
    ``k_pe`` once, before it is broadcast over the heads.  The part's
    spelling and ``mla_use_nope`` say the same thing twice on purpose, as
    ``gqa``'s and ``use_rope`` do.  Sub-scopes of the trace (not of the
    parameters' names): ``proj``, ``rotary``, ``attention`` (the softmax
    alone, ``ops/block_attention.py``), ``out``.
    """
    cfg, u = args.cfg, args.tensor
    spelt = "-".join(["mla"] + args.name_extras)
    if args.name_extras not in ([], ["rope"]) or (
            not args.name_extras) != cfg.mla_use_nope:
        raise ValueError(
            f"{spelt} beside mla_use_nope={cfg.mla_use_nope}: latent "
            f"attention without positions is spelt mla and has mla_use_nope "
            f"true, with rotated decoupled keys mla-rope and mla_use_nope "
            f"false")
    rotated = not cfg.mla_use_nope
    if rotated and (getattr(cfg, "rope_theta", None) is None
                    or cfg.rope_parameters is not None):
        raise ValueError(f"{spelt} turns its decoupled keys by the default "
                         f"table at rope_theta: it needs rope_theta and "
                         f"takes no rope_scaling or rope_parameters")
    nope, rope, v_dim, rank = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                               cfg.v_head_dim, cfg.kv_lora_rank)
    heads = (MIXER_HEADS, cfg.num_attention_heads or cfg.heads)
    fdims = _fdims(args)
    kind = u.x.dtype
    scale = (nope + rope) ** -0.5
    with jax.named_scope("proj"):
        q = _project(args, "q_proj", u, fdims,
                     [heads, (MIXER_KEY, nope + rope)])
        c = _project(args, "kv_down", u, fdims, [(LATENT, rank + rope)])
        norm = normal_var(args, [(LATENT, rank)], mean=1.0,
                          name="latent_norm")
        c_kv = NT(_rms(c.x[..., :rank], norm.x, cfg.rms_norm_eps
                       ).astype(kind), c.names)
        kv = _project(args, "kv_up", c_kv, [(LATENT, rank)],
                      [heads, (MIXER_KEY, nope + v_dim)])
        k_pe = c.x[..., None, rank:]
        q_in, k_nope_v = q.x, kv.x
        if not rotated:
            q_in = q_in * scale
    if rotated:
        with jax.named_scope("rotary"):
            cos, sin = rotary.table({"rope_theta": cfg.rope_theta}, rope,
                                    q.dim_size(SEQUENCE))
            q_pe = rotary.rotate(q_in[..., nope:], cos, sin,
                                 cfg.rope_interleave)
            q_in = (jnp.concatenate([q_in[..., :nope].astype(jnp.float32),
                                     q_pe], -1) * scale).astype(kind)
            k_pe = rotary.rotate(k_pe, cos, sin, cfg.rope_interleave
                                 ).astype(kind)
    with jax.named_scope("proj"):
        k = jnp.concatenate([k_nope_v[..., :nope], jnp.broadcast_to(
            k_pe, k_nope_v.shape[:-1] + (rope,))], -1)
    with jax.named_scope("attention"):
        o = causal_attention(q_in, k, k_nope_v[..., nope:])
    with jax.named_scope("out"):
        return _project(args, "out_proj", NT(o, kv.names),
                        [heads, (MIXER_KEY, v_dim)], fdims
                        ).transpose_to(u.names)


# -- gqa ----------------------------------------------------------------------

def gqa(args: Args) -> NT:
    """Grouped-query attention.  With rotary positions (``use_rope``, the
    default) the first extra names the layer type, upstream's
    ``layer_types`` entry, which picks the rotary table
    (``rope_parameters[<layer type>]``) and, for ``sliding_attention``, the
    window (``sliding_window``); without them the part reads ``gqa-nope``:
    no table is built, nothing is rotated, every earlier position is seen.

        q = u W_q -> [heads, head_dim];  k, v = u W_k, u W_v -> [kv heads, .]
        qknorm: q, k = rms_head(q) g_q, rms_head(k) g_k   (over head_dim)
        q, k = rot(q, pos), rot(k, pos)          (ops/rotary.py, float32)
        query head h reads K/V head h // (heads / kv heads)
        o = concat_h softmax(q_h k^T / sqrt(head_dim) + mask) v
        y = o W_o,  gated (use_gqa_gate): y = (o * sigmoid(u W_g)) W_o
        mask: key <= row, and under a window also row - key < sliding_window;
          sparse: key in the row's kept set (``_indexed_attention``)

    No bias.  ``qknorm`` norms q and k per head (Qwen3's decoder, which has
    no key for it), in float32 and before the rotation.  The scale goes into
    ``q`` with the rotation, or alone, before the one rounding to the
    stream's type.  The gate is one number a channel of every query head,
    from the layer's input, in float32.  ``sparse`` reads ``sa_config``.
    The part's spelling and the config's keys say the same layer twice on
    purpose: a program that knows neither key (it would only warn of them)
    fails on the spelling at build.
    """
    cfg, ctx, u = args.cfg, args.ctx, args.tensor
    layer_types = [e for e in args.name_extras
                   if e not in ("gated", "qknorm", "sparse")]
    spelt = "-".join(["gqa"] + args.name_extras)
    if (layer_types == ["nope"]) == cfg.use_rope or (
            "gated" in args) != cfg.use_gqa_gate:
        raise ValueError(
            f"{spelt} beside use_rope={cfg.use_rope}, use_gqa_gate="
            f"{cfg.use_gqa_gate}: a layer without positions is spelt "
            f"gqa-nope and has use_rope false, a gated one ends in -gated "
            f"and has use_gqa_gate true")
    if ("sparse" in args) != (cfg.sa_config is not None):
        raise ValueError(f"{spelt} beside sa_config={cfg.sa_config}: a "
                         f"layer of learned sparse attention ends in -sparse "
                         f"and has an sa_config")
    width = (MIXER_KEY, cfg.head_dim)
    heads = (MIXER_HEADS, cfg.num_attention_heads or cfg.heads)
    kv_heads = (KV_HEADS, cfg.num_key_value_heads)
    if cfg.use_rope and (len(layer_types) != 1 or layer_types[0] not in (
            cfg.rope_parameters or {})):
        raise ValueError(f"{spelt}: rope_parameters names no such "
                         f"layer type: {cfg.rope_parameters}")
    if not kv_heads[1] or heads[1] % kv_heads[1]:
        raise ValueError(f"{heads[1]} query heads share no whole number of "
                         f"{kv_heads[1]} K/V heads")
    window = (cfg.sliding_window if layer_types == ["sliding_attention"]
              else None)
    fdims = _fdims(args)
    scale = width[1] ** -0.5
    with ctx.scope("proj"):
        q = _project(args, "q_proj", u, fdims, [heads, width])
        k = _project(args, "k_proj", u, fdims, [kv_heads, width])
        v = _project(args, "v_proj", u, fdims, [kv_heads, width])
        if "qknorm" in args:
            q, k = (NT(_rms(x.x, normal_var(args, [width], mean=1.0,
                                            name=f"{n}_norm").x,
                            cfg.rms_norm_eps), x.names)
                    for n, x in (("q", q), ("k", k)))
        if not cfg.use_rope:
            q_in = (q.x.astype(jnp.float32) * scale).astype(u.dtype)
            k_in = k.x.astype(u.dtype)
    if cfg.use_rope:
        with ctx.scope("rotary"):
            cos, sin = rotary.table(cfg.rope_parameters[layer_types[0]],
                                    width[1], q.dim_size(SEQUENCE))
            q_in = (rotary.rotate(q.x, cos, sin) * scale).astype(u.dtype)
            k_in = rotary.rotate(k.x, cos, sin).astype(u.dtype)
    if "sparse" in args:
        if window is not None:
            raise ValueError(f"{spelt}: a sparse layer's kept set takes no "
                             f"window")
        o = _indexed_attention(args, layer_types, q_in, k_in, v.x)
    else:
        with ctx.scope("attention"):
            o = causal_attention(q_in, k_in, v.x, window=window)
    if cfg.use_gqa_gate:
        with ctx.scope("gate"):
            gate = jax.nn.sigmoid(_project(args, "gate_proj", u, fdims,
                                           [heads, width]).x.astype(
                                               jnp.float32))
            o = (o.astype(jnp.float32) * gate).astype(u.dtype)
    with ctx.scope("out"):
        return _project(args, "out_proj", NT(o, q.names), [heads, width],
                        fdims).transpose_to(u.names)


def _sparse_layers(cfg) -> int:
    """How many ``gqa-...-sparse`` parts the schedule runs."""
    def sparse(spec: str) -> bool:
        name, *extras = spec.split("-")
        return name == "gqa" and "sparse" in extras
    return sum(any(sparse(s) for s in cfg.block_config[c].layer)
               for row in cfg.block_schedule for c in row)


def _indexed_attention(args: Args, layer_types, q, k, v):
    """Attention over the keys a learned indexer picks, DeepSeek Sparse
    Attention's as ``sa_config`` sizes it (``ops/sparse_attention.py``):

        qI = u' W_qI -> [NI, DI];  kI = rms(u' W_kI) g_kI -> [DI]  (one head)
        w = (u' W_w) NI^-1/2 DI^-1/2 -> [NI]         u' = u, no gradient
        qI, kI = rot(qI, pos), rot(kI, pos)  (the layer's table, DI wide)
        I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])           (s <= t)
        S_t = the topk s of the largest I[t, s] (every s <= t if fewer)
        o[t, h] = softmax over s in S_t of q[t, h] . k[s, h // group], of v
        L_I = mean_t KL(mean_h a[t, h, .] || softmax_{S_t} I[t, .])

    ``L_I`` joins ``ctx.aux_losses`` as the mean over the sparse layers; the
    heads' mean attention takes no gradient, so the indexer's weights learn
    from ``L_I`` alone and ``L_I`` teaches nothing else.  Per layer the kept
    pairs' share of the causal ones and ``L_I`` join ``ctx.dsa_kept`` and
    ``ctx.dsa_kl`` for the step's counters.  Sub-scopes: ``indexer`` (the
    projections), ``select`` (the triangle's scores, block by block, and the
    top-k), ``attention``, ``indexer_loss``.  ``q [B, S, H, D]`` (scaled),
    ``k``, ``v [B, S, H / group, D]``."""
    from ..ops import pallas_interpret
    from ..ops import sparse_attention as sa
    cfg, ctx, u = args.cfg, args.ctx, args.tensor
    conf = cfg.sa_config
    if conf.get("indexer_num_kv_heads", 1) != 1 or (
            conf["q_chunk_size"] != conf["kv_chunk_size"]):
        raise ValueError(f"sa_config {conf}: one key head of the indexer and "
                         f"square tiles are written")
    heads = (INDEX_HEADS, conf["indexer_num_heads"])
    width = (INDEX_KEY, conf["indexer_head_dim"])
    fdims = _fdims(args)
    seq = u.dim_size(SEQUENCE)
    block = sa.block_of(seq, conf["q_chunk_size"])
    interpret = pallas_interpret()
    f32 = jnp.float32
    with ctx.scope("indexer"):
        free = NT(jax.lax.stop_gradient(u.x), u.names)
        qi = _project(args, "q_proj", free, fdims, [heads, width])
        ki = _project(args, "k_proj", free, fdims, [width])
        w = _project(args, "weights_proj", free, fdims, [heads])
        norm = normal_var(args, [width], mean=1.0, name="k_norm")
        entry = {k_: v_ for k_, v_ in cfg.rope_parameters[
            layer_types[0]].items() if k_ != "mrope_section"}
        cos, sin = rotary.table(entry, width[1], seq)
        qi = jnp.swapaxes(rotary.rotate(qi.x, cos, sin).astype(u.dtype), 1, 2)
        ki = rotary.rotate(_rms(ki.x, norm.x, cfg.rms_norm_eps)[:, :, None],
                           cos, sin)[:, :, 0].astype(u.dtype)
        w = w.x.astype(f32) * (heads[1] * width[1]) ** -0.5
    with ctx.scope("select"):
        mask, lse_i, kept = sa.select(qi, ki, w, conf["topk"], block,
                                      interpret)
    q, k, v = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    with ctx.scope("attention"):
        o, lse = sa.attention(q, k, v, mask, block, interpret)
    with ctx.scope("indexer_loss"):
        kl = sa.indexer_kl(jax.lax.stop_gradient(q), jax.lax.stop_gradient(k),
                           jax.lax.stop_gradient(lse), qi, ki, w, mask, lse_i,
                           block, interpret)
        ctx.aux_losses.append(kl / _sparse_layers(cfg))
        if cfg.memory_reduction_strategy == "checkpoint":
            sa.say_kept(sa.kept_bytes(q, v, qi, ki, w), _sparse_layers(cfg))
        causal = q.shape[0] * seq * (seq + 1) / 2
        ctx.dsa_kept.append(kept.astype(f32) / causal)
        ctx.dsa_kl.append(jax.lax.stop_gradient(kl))
    return jnp.swapaxes(o, 1, 2)


# -- routed experts -----------------------------------------------------------

def routed_mixture_of_experts(args: Args) -> NT:
    """Top-k routed experts under expert parallelism.

    The router scores all ``cfg.experts`` (``sigmoid``, else softmax, in
    float32), picks the top k of score plus a selection ``bias`` that takes
    no gradient, and weighs each pick by its score over the picks' sum times
    ``routed_scaling_factor``.  This process holds experts ``expert_offset ..
    expert_offset + experts_held`` and computes their part of the result for
    every (token, expert) pair that fell on them, whatever the imbalance:
    nothing is dropped and no capacity exists.  What the other experts would
    have added is left out: the sum over all shares is the whole layer, and
    on one chip the layer runs without its exchange.  ``shared<n>`` adds n
    experts every token takes; ``gated`` makes every expert a gated
    feed-forward (three matrices), else ``in:<act>`` (relu) between two.

    With ``moe_balance_weight > 0`` a Switch-style balance term (1.0 at a
    uniform load) joins ``ctx.aux_losses``; the load of each held expert
    joins ``ctx.expert_load`` and the rows its products multiplied
    ``ctx.expert_rows``, for the step's counters.
    """
    cfg, ctx, t = args.cfg, args.ctx, args.tensor
    topk, shared = 1, 0
    for extra in args.name_extras:
        if extra.startswith("topk"):
            topk = int(extra[len("topk"):])
        elif extra.startswith("shared"):
            shared = int(extra[len("shared"):])
    topk = min(topk, cfg.experts)
    gated = "gated" in args
    act = _activation(args, "silu" if gated else "relu")
    fdims = _fdims(args)
    fnames = [n for n, _ in fdims]
    held = (ROUTED_EXPERTS, cfg.experts_held)
    inter = (EXPERT_INTERMEDIATE, cfg.moe_intermediate_size)
    f32 = jnp.float32

    token_axes = [n for n in t.names if n not in fnames]
    xt = t.transpose_to(token_axes + fnames)
    width = cfg.heads * cfg.features_per_head
    x = xt.x.reshape(-1, width)                                # [N, D]
    tokens = x.shape[0]

    gate_w = normal_var(args, fdims + [(ROUTED_EXPERTS, cfg.experts)],
                        cfg.embedding_stddev, name="router")
    bias = (normal_var(args, [(ROUTED_EXPERTS, cfg.experts)], 0.0,
                       name="router_bias") if "bias" in args else None)
    # one stack a matrix of the experts held: in (gate), [up], out (down)
    stacks = [_stack(args, [held] + fdims + [inter], fdims).x.reshape(
        held[1], width, -1) for _ in range(2 if gated else 1)]
    stacks.append(_stack(args, [held, inter] + fdims, [inter]).x.reshape(
        held[1], -1, width))

    with ctx.scope("router"):
        logits = nd.einsum_f32("nd,de->ne", x, gate_w.x.reshape(width, -1))
        scores = (jax.nn.sigmoid(logits) if "sigmoid" in args
                  else jax.nn.softmax(logits, -1))
        ranked = scores if bias is None else scores + jax.lax.stop_gradient(
            bias.x.astype(f32))
        _, picked = jax.lax.top_k(ranked, topk)                 # [N, k]
        weight = jnp.take_along_axis(scores, picked, -1)
        weight = weight / jnp.maximum(jnp.sum(weight, -1, keepdims=True),
                                      1e-9) * cfg.routed_scaling_factor
        if cfg.moe_balance_weight > 0:
            load = jnp.zeros((cfg.experts,), f32).at[picked.reshape(-1)].add(
                1.0) / tokens
            share = scores / jnp.maximum(jnp.sum(scores, -1, keepdims=True),
                                         1e-9)
            ctx.aux_losses.append(f32(cfg.moe_balance_weight) * cfg.experts
                                  * jnp.sum(load * jnp.mean(share, 0)) / topk)
    with ctx.scope("dispatch"):
        chunk = expert_chunk(tokens, topk, held[1], cfg.experts)
        tile = row_tile(chunk, held[1], width, inter[1], x.dtype.itemsize)
        routing = gf.route(picked, cfg.expert_offset, held[1])
        ctx.expert_load.append(routing.counts)
        ctx.expert_rows.append(gf.rows_multiplied(routing, chunk, tile))
    with ctx.scope("experts"):
        def expert(rows, sizes, *mats):
            hidden = PLAIN[act](grouped_dot(rows, mats[0], sizes))
            if gated:
                hidden = hidden * grouped_dot(rows, mats[1], sizes)
            return grouped_dot(hidden, mats[-1], sizes)

        y = gf.grouped_ffn(expert, chunk, tile, x, tuple(stacks), weight,
                           routing)
    out = NT(y.reshape(xt.x.shape), xt.names)
    if shared:
        with ctx.scope("shared"):
            every = _feed_forward(
                args(xt), (EXPERT_INTERMEDIATE, shared * inter[1]), act, gated)
        with ctx.scope("combine"):
            out = out + every.transpose_to(out.names)
    return out.transpose_to(t.names)


def _stack(args: Args, dims: typing.Sequence[Dim],
           fan_in: typing.Sequence[Dim]) -> NT:
    return args.ctx.scoped("orthogonal_var", orthogonal_var, args, dims,
                           fan_in)
