"""The layer library behind the block DSL.

Covers every entry of the reference registry (/root/reference/src/model/
frontend.py:58-75): feed_forward, attention, cummean, cumsum, norm, rezero,
activation, convolution, dropout, group_linear, split_path, product-key
memories, reduced_half_linear, transpose_sequence_features,
bottleneck_group_linear, sum_heads — re-expressed over named jnp axes.
"""
from __future__ import annotations

import math
import typing

import jax
import jax.numpy as jnp

from .. import nd
from ..config import (HEADS, INTERMEDIATE, KEY, PKM_AXES, PKM_VALUES,
                      SEQUENCE, anonymize_name)
from ..nd import NT
from ..ops.activations import ACTIVATIONS, activate
from .ctx import Args
from .embedding import embed, gather_embed
from .linear import (Dim, get_intermediate, linear, linear_shapes, normal_var,
                     orthogonal_var, scalar_var, wrapped_linear)

ATTENTION_DIM = typing.NamedTuple("AttentionDim", (("index", int), ("dim", str)))

# -- shape helpers ----------------------------------------------------------

def get_attention_dim(args: Args) -> ATTENTION_DIM:
    """Attention rotates over all non-feature, non-batch axes by a global
    counter — multi-axis attention for video (reference utils_mtf.py:418-422)."""
    cfg = args.cfg
    skip = set(cfg.feature_dims) | {INTERMEDIATE}
    dims = [n for n in args.tensor.names if n not in skip][1:]
    idx = args.ctx.attention_idx % len(dims)
    return ATTENTION_DIM(idx, dims[idx])


def is_masked(args: Args) -> bool:
    return get_attention_dim(args).index in args.cfg.masked_attention_dimensions


# -- simple layers ----------------------------------------------------------

def rezero(args: Args) -> NT:
    return args.tensor * scalar_var(args, 0.0, name="rezero_var")


def dropout(args: Args) -> NT:
    rate = 0.0
    for extra in args.name_extras:
        if extra.startswith("dropout_rate"):
            rate = float(extra[len("dropout_rate"):])
    return args.ctx.dropout(args.tensor, rate)


def norm(args: Args, feature_shape: typing.Optional[typing.List[Dim]] = None) -> NT:
    """Group/layer norm via named reductions (reference normalization.py:22-34).
    'group' keeps the head axis inside the normalized set; 'scale'/'shift' add
    learned affine parameters over the feature dims.

    HBM-lean formulation (docs/perf/README.md roofline: the norm family's
    backward dominates per-block traffic): both moments come from ONE pass
    over the input (var = E[x^2] - E[x]^2, f32 accumulators — more accurate
    than the previous bf16 two-pass), and centering folds into a per-position
    affine ``x*mul + add`` so no centered full-size temporary is ever
    materialized.  Measured on-chip at flagship width: 0.138 vs 0.257 GB per
    fwd+bwd norm call."""
    t = args.tensor
    if feature_shape is None:
        feature_shape = linear_shapes(args)[0]
    fnames = [n for n, _ in feature_shape]
    reduced = [n for n in fnames if not (n == HEADS and "group" in args)]
    cdtype = t.x.dtype
    xf = NT(t.x.astype(jnp.float32), t.names)
    m1 = nd.reduce_mean(xf, reduced=reduced)
    m2 = nd.reduce_mean(xf * xf, reduced=reduced)
    var = jnp.maximum(m2.x - jnp.square(m1.x), 0.0)
    mul = NT(jax.lax.rsqrt(var + 1e-5), m2.names)
    if "scale" in args:
        p = normal_var(args, feature_shape, mean=1.0, name="scale")
        mul = mul * NT(p.x.astype(jnp.float32), p.names)
    add = -m1 * mul
    if "shift" in args:
        p = normal_var(args, feature_shape, mean=0.0, name="shift")
        add = add + NT(p.x.astype(jnp.float32), p.names)
    out = xf * mul + add
    return NT(out.x.astype(cdtype), out.names).transpose_to(t.names)


# -- feed-forward family ----------------------------------------------------

def mixture_of_experts(args: Args) -> NT:
    """Dense soft-MoE: softmax gate over the expert axis contracted into a
    per-expert linear (reference basic.py:37-44)."""
    cfg = args.cfg
    old, new = linear_shapes(args)
    expert = (anonymize_name("experts") if "experts" in [n for n, _ in old + new]
              else "experts")
    gate = linear(args, old, [(expert, cfg.experts)])
    gate = gate - nd.stop_gradient(nd.reduce_max(gate, reduced=[expert]))
    gate = NT(jnp.exp(gate.x), gate.names)
    w = args.ctx.scoped("orthogonal_var", orthogonal_var, args,
                        list(old) + list(new) + [(expert, cfg.experts)], old)
    denom = NT(jnp.reciprocal(nd.reduce_sum(gate, reduced=[expert]).x),
               tuple(n for n in gate.names if n != expert))
    out_names = nd.dedup([n for n in args.tensor.names
                          if n not in {o for o, _ in old} - {f for f, _ in new}]
                         + [f for f, _ in new])
    return nd.einsum([denom, args.tensor, gate, w], out_names)


def activated_linear(args: Args, prefix: str) -> NT:
    args = args([a[len(prefix):] for a in args if a.startswith(prefix)])
    ff = mixture_of_experts if "mixture_of_experts" in args else wrapped_linear
    out = dropout(args(activate(args(ff(args)))))
    if "glu" in args or "glu_add" in args:
        out = out * NT(jax.nn.sigmoid(ff(args).x), out.names)
    if "glu_add" in args:
        out = out + activate(args(ff(args)))
    if "norm" in args:
        out = norm(args(out))
    return out


def activated_linear_in(args: Args) -> NT:
    return activated_linear(args, "in:")


def activated_linear_out(args: Args) -> NT:
    return activated_linear(args, "out:")


def feed_forward(args: Args) -> NT:
    return activated_linear_out(args(activated_linear_in(args)))


def group_linear(args: Args) -> NT:
    """Per-head square linear (reference basic.py:72-74)."""
    cfg = args.cfg
    fdims = [(n, cfg.dims[n]) for n in cfg.feature_dims]
    anon = [(HEADS, cfg.heads), (anonymize_name(KEY), cfg.features_per_head)]
    out = linear(args("group"), fdims, anon)
    return out.rename(anonymize_name(KEY), KEY).transpose_to(args.tensor.names)


def sum_heads(args: Args) -> NT:
    return nd.reduce_sum(args.tensor, reduced=[HEADS])


def transpose_sequence_features(args: Args) -> NT:
    """Token-mixing transpose: swap sequence and feature axes (reference
    basic.py:81-86; requires seq == features_per_head)."""
    cfg = args.cfg
    assert cfg.features_per_head == cfg.sequence_length, "seq must equal features_per_head"
    t = args.tensor
    swapped = tuple(KEY if n == SEQUENCE else SEQUENCE if n == KEY else n
                    for n in t.names)
    return NT(t.x, swapped).transpose_to(t.names)


def reduced_half_linear(args: Args) -> NT:
    """Head-summed input passed through a per-head linear back to feature
    shape (reference basic.py:89-90; the reference's trailing reshape is
    shape-inconsistent there, so we re-expand via a features linear)."""
    cfg = args.cfg
    reduced = nd.reduce_sum(args.tensor, reduced=[HEADS])
    fdims = [(n, cfg.dims[n]) for n in cfg.feature_dims]
    return linear(args(reduced), [(KEY, cfg.features_per_head)], fdims
                  ).transpose_to(args.tensor.names)


def product_key_memory(args: Args) -> NT:
    """PKM sparse memory: per-axis key assignment, stable softmax normalizer,
    top-1 per axis, gather from a f^2-entry value table (reference
    basic.py:93-115).  The reference does the normalizer in fp64; TPUs have no
    native f64 so we use f32 (documented divergence)."""
    cfg = args.cfg
    anon_key = anonymize_name(KEY)
    features = [(PKM_AXES, cfg.pkm_axes), (anon_key, cfg.features_per_head)]
    old, _ = linear_shapes(args)
    assignment = linear(args, old, [(HEADS, cfg.heads)] + features)
    assignment = norm(args(assignment), features)
    assignment = assignment.astype(jnp.float32)
    normalizer = nd.reduce_max(assignment, reduced=[anon_key])
    normalizer = nd.reduce_sum(normalizer, reduced=[PKM_AXES])
    assignment = assignment - nd.stop_gradient(normalizer)
    assignment = NT(jnp.exp(assignment.x), assignment.names)
    norm_sum = nd.reduce_sum(assignment, reduced=[anon_key])  # [..., pkm]
    ax = norm_sum.names.index(PKM_AXES)
    normalizer = NT(jnp.prod(norm_sum.x, axis=ax),
                    tuple(n for n in norm_sum.names if n != PKM_AXES))

    pk_ax = assignment.names.index(anon_key)
    val = jnp.max(assignment.x, axis=pk_ax)
    idx = jnp.argmax(assignment.x, axis=pk_ax)
    val_nt = NT(val, tuple(n for n in assignment.names if n != anon_key))
    idx_nt = NT(idx, val_nt.names)
    # combine per-axis indices into one flat value index: sum idx_i * f**i
    powers = (cfg.features_per_head ** jnp.arange(cfg.pkm_axes)).astype(jnp.int32)
    ax2 = idx_nt.names.index(PKM_AXES)
    flat_idx = jnp.tensordot(idx_nt.x.astype(jnp.int32),
                             powers, axes=([ax2], [0]))
    flat_idx_nt = NT(flat_idx, tuple(n for n in idx_nt.names if n != PKM_AXES))
    val_prod = NT(jnp.prod(val_nt.x, axis=ax2), flat_idx_nt.names)
    val_final = (val_prod / normalizer).astype(cfg.calculation_dtype)

    fdims = [(n, cfg.dims[n]) for n in cfg.feature_dims]
    out, _ = gather_embed(args(flat_idx_nt),
                          [(PKM_VALUES, cfg.product_key_value_vectors)] + fdims,
                          squeeze_dims=[HEADS])
    return out * val_final


def feed_forward_product_key_memory(args: Args) -> NT:
    return product_key_memory(args(activated_linear_in(args)))


def bottleneck_group_linear(args: Args) -> NT:
    """3-stage grouped MLP: dense bottleneck in, per-head widened mid, per-head
    out (reference basic.py:122-126)."""
    args = args(activated_linear_in(args))
    args.name_extras.extend(["group", "mid:group", "out:group"])
    args = args(activated_linear(args, "mid:"))
    return activated_linear_out(args)


# -- attention / spatial mixing --------------------------------------------

def _causal_mask(args: Args, dim: str, tmp: str, keep_ge: bool) -> NT:
    size = args.tensor.dim_size(dim)
    op = jnp.greater_equal if keep_ge else jnp.less
    return nd.compare_range(dim, size, tmp, size, op, args.cfg.calculation_dtype)


def _masked_map(args: Args) -> typing.Tuple[NT, typing.Union[NT, int]]:
    """Learned per-head position-pair bias map, optionally causal-masked
    (reference spatial.py:19-23)."""
    cfg = args.cfg
    dim = get_attention_dim(args).dim
    tmp = anonymize_name(dim)
    size = args.tensor.dim_size(dim)
    bias = embed(args, [(HEADS, cfg.heads), (dim, size), (tmp, size)])
    mask = _causal_mask(args, dim, tmp, keep_ge=True) if is_masked(args) else 1
    return bias, mask


def _ring_eligible(args: Args, dim: str) -> bool:
    """Sequence-parallel ring attention replaces the plain dot-product
    softmax path when the mesh has a sequence axis; the learned-bias-map
    variants keep the GSPMD path (their seq x seq parameters are row-sharded
    instead).  Inside a pipeline stage (ctx.mesh is None there) the real
    mesh arrives via ctx.outer_mesh and the ring nests (ops/ring.py)."""
    from ..parallel.mesh import SEQ_AXIS
    mesh = args.ctx.effective_mesh
    return (mesh is not None
            and args.ctx.params is not None
            and mesh.shape.get(SEQ_AXIS, 1) > 1
            and dim == SEQUENCE
            and "dot_product" in args
            # the ring kernel is rank-4 (batch, seq, heads, key); video
            # tensors with height/width axes keep the GSPMD path
            and set(args.tensor.names) == {args.tensor.names[0], dim,
                                           HEADS, KEY}
            and not any(f in args for f in ("biased_softmax",
                                            "biased_attention_map",
                                            "scale_attention_map")))


def _qkv(args: Args, base: typing.Optional[Args], dim: str
         ) -> typing.Tuple[typing.Optional[NT], typing.Optional[NT], NT]:
    """Q/K/V construction shared by the dense, ring, and KV-cached attention
    paths: key source selection (embedded/context/positional), query scaling,
    value source (shared_key_value/input_as_value/linear)."""
    cfg = args.cfg
    t = args.tensor
    dc = args.ctx.decode
    qry = key = None
    if "dot_product" in args:
        if "embedded" in args or "context" in args:
            key = activated_linear_out(base)
        if "embedded" in args or "positional" in args:
            from .embedding import positional_embed
            fdims = [(n, cfg.dims[n]) for n in cfg.feature_dims]
            pos = positional_embed(args, dim, t.dim_size(dim), fdims)
            key = pos if key is None else key + pos
        scale = (dc.seq if dc is not None else t.dim_size(dim)) ** -0.5
        qry = activated_linear_out(base) * scale
    if "dot_product" in args and "shared_key_value" in args:
        val = key
    elif "input_as_value" in args:
        val = t
    else:
        val = activated_linear_out(base)
    return qry, key, val


def _cached_attention(args: Args, qry: NT, key: NT, val: NT, dim: str) -> NT:
    """KV-cache decode (the fast path the reference lacks, SURVEY.md §7
    item 7): the layer sees ``R`` rows starting at absolute position
    ``ctx.decode.pos`` — R=1 for incremental decode, R=prompt length for the
    prefill pass that writes the whole prompt's K/V in one forward.

    Two families share this path:

    * ``dot_product``: the rows' K/V are written into the layer's cache and
      the dot-product runs against the cached prefix under a per-row causal
      mask.
    * learned maps (``biased_softmax`` / ``biased_attention_map`` /
      ``scale_attention_map`` — the flagship mixer,
      /root/reference/src/model/spatial.py:65-75, whose semantics are
      ``out[s] = sum_{t<=s} map[h,s,t] * v[t]``): only V is cached; the
      seq x seq map is built FULL-LENGTH (same scope walk and param shapes
      as training, like ``positional_embed``) and rows ``[pos, pos+R)`` are
      sliced out — O(seq * d) per decoded token instead of the rebuild
      sampler's O(seq * full forward).

    Greedy outputs match the rebuild-everything sampler because every
    output depends only on causally visible positions."""
    ctx = args.ctx
    cfg = args.cfg
    dc = ctx.decode
    t = args.tensor
    batch_axis = t.names[0]
    order = (batch_axis, dim, HEADS, KEY)
    tmp = anonymize_name(dim)
    cdtype = cfg.calculation_dtype
    has_dot = "dot_product" in args

    cache_id = f"attn{ctx.attention_idx}"
    v_cur = val.transpose_to(order).x.astype(cdtype)   # [b, R, h, dk]
    n_rows = v_cur.shape[1]
    # ``dc.pos`` is a scalar (one shared position — the serialized samplers
    # and the engine's prefill) or a [batch] vector (per-lane positions —
    # the continuous-batching decode step, serve/engine.py, where every
    # lane sits at its own depth in its own request); vector pos implies
    # R == 1 (one incremental row per lane per step)
    lanes = jnp.ndim(dc.pos) > 0
    if lanes and n_rows != 1:
        raise ValueError("per-lane decode positions require single-row "
                         f"steps (got {n_rows} rows)")
    if cache_id in dc.caches:
        cached = dc.caches[cache_id]
    else:  # template-building call: allocate zeroed full-length caches
        shape = (v_cur.shape[0], dc.seq) + v_cur.shape[2:]
        cached = tuple(jnp.zeros(shape, cdtype)
                       for _ in range(2 if has_dot else 1))
    if lanes:
        # per-lane scatter: lane b writes its row at absolute dc.pos[b]
        # (dynamic_update_slice cannot take per-batch starts)
        row_at = (jnp.arange(dc.seq)[None, :] == dc.pos[:, None])
        sel = row_at.reshape(row_at.shape + (1,) * (v_cur.ndim - 2))
    if has_dot:
        k_cache, v_cache = cached
        k_cur = key.transpose_to(order).x.astype(cdtype)
        k_cache = (jnp.where(sel, k_cur, k_cache) if lanes
                   else jax.lax.dynamic_update_slice_in_dim(k_cache, k_cur,
                                                            dc.pos, 1))
    else:
        v_cache, = cached
    v_cache = (jnp.where(sel, v_cur, v_cache) if lanes
               else jax.lax.dynamic_update_slice_in_dim(v_cache, v_cur,
                                                        dc.pos, 1))
    dc.caches[cache_id] = (k_cache, v_cache) if has_dot else (v_cache,)

    # per-row causal visibility: query row r (absolute position pos+r) sees
    # cached positions <= pos+r only; with per-lane pos the mask gains the
    # batch axis and every NT below broadcasts it by name
    if lanes:
        q_abs = dc.pos[:, None] + jnp.arange(n_rows)[None, :]
        vis = (jnp.arange(dc.seq)[None, None, :]
               <= q_abs[:, :, None]).astype(cdtype)
        vis_nt = NT(vis, (batch_axis, dim, tmp))
    else:
        q_abs = dc.pos + jnp.arange(n_rows)
        vis = (jnp.arange(dc.seq)[None, :] <= q_abs[:, None]).astype(cdtype)
        vis_nt = NT(vis, (dim, tmp))

    def map_rows(a: Args) -> NT:
        """Rows [pos, pos+R) of the learned per-head seq x seq map, causally
        zeroed when the axis is masked (dense-path ``bias * mask``)."""
        bias = embed(a, [(HEADS, cfg.heads), (dim, dc.seq), (tmp, dc.seq)])
        bx = bias.transpose_to((HEADS, dim, tmp)).x.astype(cdtype)
        if lanes:  # per-lane row gather: [h, B, R, seq]
            rows = NT(jnp.take(bx, q_abs, axis=1),
                      (HEADS, batch_axis, dim, tmp))
        else:
            rows = NT(jax.lax.dynamic_slice_in_dim(bx, dc.pos, n_rows, 1),
                      (HEADS, dim, tmp))
        return rows * vis_nt if is_masked(a) else rows

    logit: typing.Optional[NT] = None
    if has_dot:
        kn = NT(k_cache, (batch_axis, tmp, HEADS, KEY))
        logit = nd.einsum([qry.transpose_to(order), kn],
                          (batch_axis, dim, HEADS, tmp))
    if "biased_softmax" in args:
        b = map_rows(args)
        logit = b if logit is None else logit + b
    if logit is not None:
        logit = logit + NT((1 - vis) * jnp.asarray(-2e38, cdtype),
                           vis_nt.names)
        logit = logit - nd.stop_gradient(nd.reduce_max(logit, reduced=[tmp]))
        logit = NT(jnp.exp(logit.x), logit.names)
        logit = logit / nd.reduce_sum(logit, reduced=[tmp])
    if "biased_attention_map" in args:
        b = map_rows(args)
        logit = b if logit is None else logit + b
    if "scale_attention_map" in args:
        b = map_rows(args)
        logit = b if logit is None else logit * b
    out = nd.einsum([logit, NT(v_cache, (batch_axis, tmp, HEADS, KEY))],
                    t.names)
    return out


def _ring_attention(args: Args, qry: NT, key: NT, val: NT, dim: str) -> NT:
    """Dot-product attention over the sequence-parallel ring (ops/ring.py)."""
    from ..ops.ring import ring_attention
    from ..parallel.mesh import SEQ_AXIS
    from ..parallel.sharding import spec_for
    t = args.tensor
    order = (t.names[0], dim, HEADS, KEY)
    ctx = args.ctx
    mesh = ctx.effective_mesh
    spec = spec_for(order, mesh)
    out = ring_attention(qry.transpose_to(order).x, key.transpose_to(order).x,
                         val.transpose_to(order).x, mesh, SEQ_AXIS, spec,
                         causal=True)
    return NT(out, order).transpose_to(t.names)


def _blocked_map_rows(bias_x, val_x, depth: int):
    """Causal map-attention with the triangle decomposed into blocks:
    ``out[b,s,h,k] = sum_{t<=s} bias[h,s,t] * val[b,t,h,k]`` where the
    lower-left quadrant multiplies DENSE (no masked flops executed) and
    only the two shrinking diagonal quadrants recurse; leaves (<=256 rows
    or odd sizes) run the plain masked einsum.

    XLA executes a masked einsum as the FULL rectangle — the causal mask
    only zeroes operands — so at seq 2048 nearly half the seq^2 map FLOPs
    are wasted; depth 3 executes ~56% of the tile products and autodiff
    inherits the same saving in both backward contractions.  Measured
    on-chip at the 32ctx shape: ~25% faster per fwd+bwd call than the
    masked einsum (docs/perf/README.md round 5c); two hand-written pallas
    variants of the same skip LOSE to XLA here (docs/perf/README.md rounds
    2 and 5) — the win needs XLA's own schedule, just with the rectangle
    carved smaller.

    Partial sums accumulate in f32 (one cast at the top, strictly tighter
    than the single-einsum baseline's policy); plain jnp slicing/concat,
    so the decomposition composes with GSPMD sharding unchanged."""
    s = bias_x.shape[1]
    if depth <= 0 or s % 2 or s // 2 < 256:
        row = jax.lax.broadcasted_iota(jnp.int32, (s, s), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (s, s), 1)
        masked = bias_x * (row >= col).astype(bias_x.dtype)
        return jnp.einsum("hst,bthk->bshk", masked, val_x,
                          preferred_element_type=jnp.float32)
    half = s // 2
    top = _blocked_map_rows(bias_x[:, :half, :half], val_x[:, :half],
                            depth - 1)
    dense = jnp.einsum("hst,bthk->bshk", bias_x[:, half:, :half],
                       val_x[:, :half], preferred_element_type=jnp.float32)
    bot = dense + _blocked_map_rows(bias_x[:, half:, half:],
                                    val_x[:, half:], depth - 1)
    return jnp.concatenate([top, bot], axis=1)


def _blocked_map_eligible(args: Args, dim: str) -> bool:
    """The blocked decomposition replaces the pure learned-map path (no
    dot-product/softmax/scale combination) on the rank-4 text layout with
    a causally-masked sequence axis; any seq-sharding keeps the row-sharded
    einsum path (slicing the sequence would cross shard boundaries)."""
    from ..parallel.mesh import SEQ_AXIS
    ctx = args.ctx
    t = args.tensor
    mesh = ctx.effective_mesh
    return (args.cfg.blocked_causal_map > 0
            and is_masked(args)
            and ctx.decode is None
            and dim == SEQUENCE
            and t.names[1:] == (SEQUENCE, HEADS, KEY)
            and (mesh is None or mesh.shape.get(SEQ_AXIS, 1) == 1))


def attention(args: Args) -> NT:
    """Composable attention (reference spatial.py:42-81): optional QK^T
    softmax path, learned bias/scale attention maps, causal masking, and
    value source selection.  The product ``logit @ value`` and ``q @ k^T``
    are plain einsums -> MXU."""
    ctx = args.ctx
    cfg = args.cfg
    ctx.attention_idx += 1
    base = None
    if "dot_product" in args or "input_as_value" not in args:
        base = args(activated_linear_in(args))

    dim = get_attention_dim(args).dim
    qry, key, val_src = _qkv(args, base, dim)
    if ctx.decode is not None and dim == SEQUENCE and (
            "dot_product" in args
            or any(f in args for f in ("biased_softmax", "biased_attention_map",
                                       "scale_attention_map"))):
        return _cached_attention(args, qry, key, val_src, dim)
    if _ring_eligible(args, dim):
        return _ring_attention(args, qry, key, val_src, dim)
    tmp = anonymize_name(dim)
    t = args.tensor
    shape_names = t.names
    val = val_src.rename(dim, tmp)

    logit: typing.Optional[NT] = None

    def _biased(a: Args) -> NT:
        bias, mask = _masked_map(a)
        return bias * mask if isinstance(mask, NT) else bias

    if "dot_product" in args:
        old, _ = linear_shapes(args)
        contracted = [n for n, _ in old if n != HEADS]
        logit_names = tuple(n for n in shape_names if n not in contracted) + (tmp,)
        logit = nd.einsum([qry, key.rename(dim, tmp)], logit_names)
    if "biased_softmax" in args:
        b = _biased(args)
        logit = b if logit is None else logit + b
    if logit is not None:
        # the reference masks every softmax logit causally, regardless of
        # masked_attention_dimensions (spatial.py:68)
        logit = logit + _causal_mask(args, dim, tmp, keep_ge=False) * -2e38
        logit = logit - nd.stop_gradient(nd.reduce_max(logit, reduced=[tmp]))
        logit = NT(jnp.exp(logit.x), logit.names)
        logit = logit / nd.reduce_sum(logit, reduced=[tmp])
    if ("biased_attention_map" in args and logit is None
            and "scale_attention_map" not in args
            and _blocked_map_eligible(args, dim)):
        # pure learned-map path: same scope walk as _biased (the embed is
        # the next parameter either way), triangle applied by block
        # decomposition instead of a mask multiply
        bias, mask = _masked_map(args)
        order = (shape_names[0], dim, HEADS, KEY)
        out = _blocked_map_rows(bias.transpose_to((HEADS, dim, tmp)).x,
                                val_src.transpose_to(order).x,
                                args.cfg.blocked_causal_map)
        out = out.astype(args.cfg.calculation_dtype)
        return NT(out, order).transpose_to(shape_names)
    if "biased_attention_map" in args:
        b = _biased(args)
        logit = b if logit is None else logit + b
    if "scale_attention_map" in args:
        b = _biased(args)
        logit = b if logit is None else logit * b
    if logit is None:
        raise UserWarning(f"no spatial mixing in attention: {args.name_extras}")
    return nd.einsum([logit, val], shape_names)


def _cumsum_axis(args: Args) -> int:
    return args.tensor.names.index(get_attention_dim(args).dim)


def cumsum(args: Args) -> NT:
    return NT(jnp.cumsum(args.tensor.x, axis=_cumsum_axis(args)), args.tensor.names)


def cummean(args: Args) -> NT:
    dim = get_attention_dim(args).dim
    out = cumsum(args)
    denom = 1 + nd.arange(dim, args.tensor.dim_size(dim),
                          dtype=args.tensor.dtype)
    return out / denom


def convolution(args: Args) -> NT:
    """Causal 1D convolution over the rotating attention axis.  The
    reference's custom conv op is disabled in-tree ("Convolution is currently
    broken", reference convolution.py:129); this is a working TPU-native
    causal depthwise-style conv via lax.conv_general_dilated."""
    cfg = args.cfg
    dim = get_attention_dim(args).dim
    t = args.tensor
    ksize = cfg.convolution_size
    fdims = [(n, cfg.dims[n]) for n in cfg.feature_dims]
    w = orthogonal_var(args, [("_conv_kernel", ksize)] + fdims, name="conv_kernel")
    # causal depthwise conv: channels = all feature dims, window over `dim`
    feat_names = [n for n, _ in fdims if n in t.names]
    other = [n for n in t.names if n != dim and n not in feat_names]
    xt = t.transpose_to(other + [dim] + feat_names)
    lead = xt.x.shape[:len(other)]
    length = xt.x.shape[len(other)]
    chans = 1
    for s in xt.x.shape[len(other) + 1:]:
        chans *= s
    x2 = xt.x.reshape((-1, length, chans))  # N, W, C
    k = w.x.astype(t.dtype).reshape(ksize, 1, chans)  # W, I/group=1, C
    y = jax.lax.conv_general_dilated(
        x2, k, (1,), [(ksize - 1, 0)], feature_group_count=chans,
        dimension_numbers=("NWC", "WIO", "NWC"))
    y = y.reshape(lead + xt.x.shape[len(other):])
    return NT(y, tuple(other + [dim] + feat_names)).transpose_to(t.names)


# -- fused mixer block (pallas bytes lever) ---------------------------------

MIXER_FUSED_PATTERN = (
    "norm-shift-scale-features-group",
    "attention-biased_attention_map-absolute-input_as_value-shared",
    "norm-shift-scale-features-group",
    "activation-gelu",
    "attention-biased_attention_map-absolute-input_as_value-shared",
)


def fused_mixer_eligible(ctx, conf, x: NT) -> bool:
    """The fused kernel (ops/pallas_mixer.py) replaces exactly the mixer
    configs' block-2 chain, on an unsharded device, in apply mode, on the
    plain rank-4 text layout with the sequence axis causally masked."""
    cfg = ctx.cfg
    layer = conf.layer if isinstance(conf.layer, (list, tuple)) else None
    mesh = ctx.effective_mesh
    from ..ops import quant
    return (cfg.fused_mixer_block
            and not quant.pattern_quantized(cfg, MIXER_FUSED_PATTERN)
            and layer is not None and tuple(layer) == MIXER_FUSED_PATTERN
            and ctx.params is not None and ctx.decode is None
            and (mesh is None or mesh.size == 1)
            and x.names[1:] == (SEQUENCE, HEADS, KEY)
            and 0 in cfg.masked_attention_dimensions
            and x.dim_size(SEQUENCE) % 128 == 0
            and x.dim_size(KEY) % 128 == 0
            and jax.default_backend() in ("tpu", "cpu"))


def fused_mixer_block_part(conf, ctx, x: NT) -> NT:
    """Apply the 5-layer mixer block through the fused pallas kernel.

    The scope walk REPLAYS ``registry._get_block_part`` exactly — same
    ``ctx.scoped`` calls in the same order, same parameter constructors the
    unfused layers invoke — so parameter names, shapes, init and the
    attention-rotation counter are bit-identical to the unfused chain and
    checkpoints interchange freely between the two paths."""
    from ..ops import pallas_interpret
    from ..ops.pallas_mixer import fused_mixer_block

    cfg = ctx.cfg
    collected: typing.List[NT] = []

    def norm_params(args: Args) -> typing.Tuple[NT, NT]:
        # the scale/shift pair, built as the unfused norm() builds it
        fs = linear_shapes(args)[0]
        scale = normal_var(args, fs, mean=1.0, name="scale")
        shift = normal_var(args, fs, mean=0.0, name="shift")
        return scale, shift

    def attn_params(args: Args) -> NT:
        ctx.attention_idx += 1
        dim = get_attention_dim(args).dim
        tmp = anonymize_name(dim)
        size = args.tensor.dim_size(dim)
        return embed(args, [(HEADS, cfg.heads), (dim, size), (tmp, size)])

    specs = list(conf.layer)
    for idx, layer_spec in enumerate(specs, 1):
        name, *extras = layer_spec.split("-")
        args = Args(ctx, x, extras, idx == len(specs))
        if name == "norm":
            collected.append(ctx.scoped("norm_", norm_params, args))
        elif name == "attention":
            collected.append(ctx.scoped("attention_", attn_params, args))
        else:  # activation: consumes its scope slot, holds no parameters
            with ctx.scope("activation_"):
                pass

    (scale1, shift1), bias1, (scale2, shift2), bias2 = collected
    order = (x.names[0], SEQUENCE, HEADS, KEY)
    tmp_names = [n for n in bias1.names if n != HEADS]
    out_x = fused_mixer_block(
        x.transpose_to(order).x,
        bias1.transpose_to((HEADS,) + tuple(tmp_names)).x,
        bias2.transpose_to((HEADS,) + tuple(tmp_names)).x,
        scale1.transpose_to((HEADS, KEY)).x,
        shift1.transpose_to((HEADS, KEY)).x,
        scale2.transpose_to((HEADS, KEY)).x,
        shift2.transpose_to((HEADS, KEY)).x,
        pallas_interpret(),
    )
    return NT(out_x, order).transpose_to(x.names)
