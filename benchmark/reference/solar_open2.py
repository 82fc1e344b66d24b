"""Plain reference of a sparse-expert hybrid of delta-rule layers with
negative eigenvalues and gated softmax attention without positions
(Solar-Open2) and its update, for one chip's share of a slice whose hosts
share a layer's heads and whose chips share its experts.

Written from the published description (`config.json` of
upstage/Solar-Open2-250B), in float32 with `highest` matmul precision,
importing nothing of the program.  `x` is the stream `[B, S, D]`,
`rms(x) = x * rsqrt(mean(x^2) + eps) * w`:

    every layer   x = x + mixer(rms_1(x));  x = x + moe(rms_2(x))
    kda mixer     q, k, v = silu(conv4(u W_q)), silu(conv4(u W_k)), silu(conv4(u W_v))
                  q = q / |q| * d^-1/2, k = k / |k| per head
                  g = -exp(a_log) * softplus((u W_fa) W_fb + dt_bias)   per channel
                  beta = 2 sigmoid(u W_beta)     per head (kda_allow_neg_eigval)
                  S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
                  o_t = S_t^T q_t;  y = (rms_head(o) * sigmoid((u W_ga) W_gb)) W_o
    gqa mixer     q = u W_q -> [heads, d];  k, v = u W_k, u W_v -> [kv heads, .]
                  no rotation (use_rope false); query head h reads K/V head
                  h // (heads / kv heads); scores q k^T / sqrt(d), mask j <= i
                  o = concat_h softmax(scores) v
                  y = (o * sigmoid(u W_g)) W_o    (use_gqa_gate: W_g -> [heads, d])
    expert layer  s = sigmoid(u W_r); top-k of s + b (b takes no gradient);
                  w_e = s_e / sum_sel s * scaling;
                  y = sum_{e selected and held} w_e ffn_e(u) + ffn_shared(u)
                  ffn(u) = (silu(u W_gate) * (u W_up)) W_down
                  loss += moe_balance_weight * E / k * sum_e load_e mean_t(s_te / sum_e' s_te')
                  (load_e the share of tokens that picked e, no gradient;
                  the term is 1 at a uniform load; one batch is one block)
    output        logits = rms_f(x) W_head; loss = CE + z_loss * mean(log_z^2)
    update        adaptive_clip -> sm3 -> momentum (nesterov) -> lr, decay
                  lr * wd * w on every leaf of two axes or more that is no
                  norm weight, table or head; linear warm-up

The delta rule is the token-by-token recurrence, under a two-level scan
whose outer level is recomputed in the backward (a plain scan's backward
would hold one state a token); attention takes a block of query rows at a
time against every key, as whole masked rows, with the K/V heads repeated
plainly; each held expert is applied to every token under `jax.checkpoint`
and its result weighed by the token's combine weight, zero where the router
did not select it; the head and the loss take a block of positions at a
time, recomputed in the backward (the logits of 16,384 tokens and their
gradient would not fit beside 3.6 GB of weights and as much of gradients);
the batch is taken `rows` rows at a time.

Departures from the description, each on purpose:
- this chip's share: only experts `expert_offset .. + experts_held` are
  applied (the router scores all of them); the heads are the share the
  configuration states (`linear_attn_config.num_heads`,
  `num_attention_heads` with their `num_key_value_heads`): heads meet only
  in the sum the output matrix makes, so a share's result is its part of
  that sum; the vocabulary is the slice the configuration states; what the
  absent experts and heads would add is left out;
- `|q|`, `|k|` are `sqrt(sum x^2 + 1e-6)`: a zero row has a norm;
- weights are Gaussian from the seed (`_part_leaves`), `b = 0`;
- the state the configuration stores in a narrower type (momentum and SM3
  rows) is rounded to that type between steps;
- assumed, since the source says nothing of them: the gate's shape, no norm
  on q or k, sigmoid scores, the gate pairs' rank, the optimizer chain and
  initialisers.

`LOWER` names the cases `tests/read_controls.py` reads in the program's
place: the slices held in the nearest precision below the stated one, and
seven planted faults, each a traced flag of `SOUND`.
"""
from __future__ import annotations

import functools
import time
import typing

import jax
import jax.numpy as jnp
import numpy as np

Params = typing.Dict[str, jnp.ndarray]
_HI = jax.lax.Precision.HIGHEST

_TABLE = "gpt/input/gather/embed/embed_var"
_HEAD = "gpt/output/embed/embed_orth"
_FINAL = "gpt/output/lang_out0_/rms_norm_/scale"
_KINDS = ("kda", "gqa", "routed_moe")


class Sizes(typing.NamedTuple):
    """What the reference needs of a configuration file."""
    kinds: typing.Tuple[str, ...]                        # by block part
    parts: typing.Tuple[typing.Tuple[int, int], ...]     # (depth, block index)
    heads: int
    features_per_head: int
    sequence_length: int
    vocab_size: int
    kda_heads: int
    kda_dim: int
    kda_taps: int
    kda_rank: int
    q_heads: int
    kv_heads: int
    head_dim: int
    rope_theta: float
    experts: int
    held: int
    offset: int
    topk: int
    shared: int
    expert_width: int
    scaling: float
    balance: float
    eps: float
    embedding_stddev: float
    z_loss: float
    learning_rate: float
    warmup_steps: int
    weight_decay: float
    clip: float
    momentum: float
    optimizer_slice_dtype: str
    slice_dtype: str

    @property
    def hidden(self) -> int:
        return self.heads * self.features_per_head

    @classmethod
    def from_config(cls, raw: dict) -> "Sizes":
        kinds = []
        for block in raw["block_config"]:
            name, *extras = block["layer"][-1].split("-")
            if name not in _KINDS or not block.get("skip"):
                raise ValueError(f"reference knows no block {block}")
            kinds.append((name, extras))
        moe = next(e for n, e in kinds if n == "routed_moe")
        if not {"sigmoid", "bias", "gated"} <= set(moe):
            raise ValueError(f"reference knows no expert layer {moe}")
        gqa = next(e for n, e in kinds if n == "gqa")
        if sorted(gqa) != ["gated", "nope"] or raw["use_rope"] or not (
                raw["use_gqa_gate"] and raw["kda_allow_neg_eigval"]):
            raise ValueError(f"reference knows no attention {gqa} beside "
                             "these use_rope, use_gqa_gate, "
                             "kda_allow_neg_eigval")
        chain = raw["optimizer"].split("-")
        if [c.split(":")[0] for c in chain] != [
                "adaptive_clip", "sm3", "momentum", "learning_rate"]:
            raise ValueError(f"reference knows no optimizer {raw['optimizer']}")
        mom = chain[2].split(":")
        if mom[2:] != ["1", "1"]:
            raise ValueError("reference writes nesterov momentum only")
        parts = tuple((i, c) for i, row in enumerate(raw["block_schedule"])
                      for c in row)
        la = raw["linear_attn_config"]
        h, k = raw["heads"], raw["features_per_head"]
        return cls(
            kinds=tuple(kinds[c][0] for _, c in parts), parts=parts,
            heads=h, features_per_head=k,
            sequence_length=raw["sequence_length"],
            vocab_size=raw["vocab_size"],
            kda_heads=la["num_heads"], kda_dim=la["head_dim"],
            kda_taps=la["short_conv_kernel_size"],
            kda_rank=la["head_dim"],
            q_heads=raw["num_attention_heads"],
            kv_heads=raw["num_key_value_heads"], head_dim=raw["head_dim"],
            rope_theta=raw["rope_theta"],
            experts=raw["experts"], held=raw["experts_held"],
            offset=raw.get("expert_offset", 0),
            topk=next(int(e[4:]) for e in moe if e.startswith("topk")),
            shared=next(int(e[6:]) for e in moe if e.startswith("shared")),
            expert_width=raw["moe_intermediate_size"],
            scaling=raw["routed_scaling_factor"],
            balance=raw.get("moe_balance_weight", 0.0),
            eps=raw.get("rms_norm_eps", 1e-5),
            embedding_stddev=raw["embedding_stddev"],
            z_loss=raw.get("z_loss", 1e-4),
            learning_rate=raw["learning_rate"],
            warmup_steps=raw["learning_rate_config"]["linear_warmup"]
            ["final_step"],
            weight_decay=raw["weight_decay"],
            clip=float(chain[0].split(":")[1]), momentum=float(mom[1]),
            optimizer_slice_dtype=raw["optimizer_slice_dtype"],
            slice_dtype=raw["slice_dtype"])


def _matrix(shape, fan_in: int, stacked: int = 1):
    """A matrix leaf at 1 / sqrt(max(fan in, fan out)), the orthogonal
    initialiser's element variance; `stacked` of them in one leaf."""
    fan_out = int(np.prod(shape)) // stacked // fan_in
    return tuple(shape), (0.0, max(fan_in, fan_out) ** -0.5)


def _part_leaves(sz: Sizes, kind: str) -> typing.Dict[str, tuple]:
    """Leaves of one block part under `.../block_/`, by the program's names:
    (shape, (mean, stddev))."""
    h, k, d = sz.heads, sz.features_per_head, sz.hidden
    weight = (1.0, 0.02)
    out = {"rms_norm_/scale": ((h, k), weight)}
    if kind == "kda":
        n, w, r = sz.kda_heads, sz.kda_dim, sz.kda_rank
        for c in "qkv":
            out[f"kda_/conv/{c}_proj"] = _matrix((h, k, n, w), d)
            out[f"kda_/conv/{c}_conv"] = ((sz.kda_taps, n, w),
                                          (0.0, sz.kda_taps ** -0.5))
        out.update({
            "kda_/gates/decay_down": _matrix((h, k, r), d),
            "kda_/gates/decay_up": _matrix((r, n, w), r),
            "kda_/gates/dt_bias": ((n, w), (-2.0, 1.0)),
            "kda_/gates/a_log": ((n,), (0.0, 0.5)),
            "kda_/gates/beta": _matrix((h, k, n), d),
            "kda_/gates/out_down": _matrix((h, k, r), d),
            "kda_/gates/out_up": _matrix((r, n, w), r),
            "kda_/out/norm_scale": ((w,), weight),
            "kda_/out/proj": _matrix((n, w, h, k), n * w)})
    elif kind == "gqa":
        n, g, w = sz.q_heads, sz.kv_heads, sz.head_dim
        out.update({
            "gqa_/proj/q_proj": _matrix((h, k, n, w), d),
            "gqa_/proj/k_proj": _matrix((h, k, g, w), d),
            "gqa_/proj/v_proj": _matrix((h, k, g, w), d),
            "gqa_/gate/gate_proj": _matrix((h, k, n, w), d),
            "gqa_/out/out_proj": _matrix((n, w, h, k), n * w)})
    else:
        f, e, g = sz.expert_width, sz.held, sz.shared * sz.expert_width
        for i in range(3):
            leaf = f"orthogonal_var{i or ''}/orthogonal_var"
            out["routed_moe_/" + leaf] = (
                _matrix((e, h, k, f), d, e) if i < 2
                else _matrix((e, f, h, k), f, e))
            out["routed_moe_/shared/" + leaf] = (
                _matrix((h, k, g), d) if i < 2 else _matrix((g, h, k), g))
        out["routed_moe_/router"] = ((h, k, sz.experts), (0.0, d ** -0.5))
        out["routed_moe_/router_bias"] = ((sz.experts,), (0.0, 0.0))
    return out


def _leaves(sz: Sizes) -> typing.Dict[str, tuple]:
    h, k, v = sz.heads, sz.features_per_head, sz.vocab_size
    out = {_TABLE: ((v, h, k), (0.0, sz.embedding_stddev)),
           _HEAD: _matrix((h, k, 1, v), sz.hidden),
           _FINAL: ((h, k), (1.0, 0.02))}
    for (i, c), kind in zip(sz.parts, sz.kinds):
        for leaf, rule in _part_leaves(sz, kind).items():
            out[f"gpt/body/@d{i}_{c}/block_/{leaf}"] = rule
    return out


def shapes(sz: Sizes) -> typing.Dict[str, typing.Tuple[int, ...]]:
    """Every parameter by the program's checkpoint name, with its shape."""
    return {name: rule[0] for name, rule in _leaves(sz).items()}


def seed_key(seed: int, stream: int) -> jax.Array:
    """A PRNG key from any whole number (seeds pass 2**31)."""
    words = np.random.SeedSequence([int(seed), stream]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def _draw_weights(sz: Sizes):
    """key -> all weights in `slice_dtype`; leaves of one shape and rule are
    drawn together and cut apart."""
    groups: typing.Dict[tuple, typing.List[str]] = {}
    for name, (shape, rule) in sorted(_leaves(sz).items()):
        groups.setdefault((shape,) + rule, []).append(name)

    def make(key):
        out = {}
        for idx, ((shape, mean, std), names) in enumerate(groups.items()):
            draw = jax.random.normal(jax.random.fold_in(key, idx),
                                     (len(names),) + shape, jnp.float32)
            draw = (draw * std + mean).astype(sz.slice_dtype)
            out.update({name: draw[i] for i, name in enumerate(names)})
        return out

    return make


def init_weights(sz: Sizes, seed: int) -> Params:
    """All weights from the seed in ONE device program, in `slice_dtype`."""
    return jax.jit(_draw_weights(sz))(seed_key(seed, 0))


def change_since_seed(after: Params, sz: Sizes, seed: int):
    """`change_norms(after, init_weights(sz, seed))` in one device program:
    the seed's weights are drawn again inside it and live only in its
    scratch, so no second copy of the weights stands beside `after`."""
    make = _draw_weights(sz)
    return jax.jit(lambda now, key: change_norms(now, make(key)))(
        after, seed_key(seed, 0))


# -- forward ------------------------------------------------------------------

# a sound model's flags; a planted fault of `LOWER` moves one
SOUND = {"beta_scale": 2.0, "gate": 1.0, "gate_first": 1.0, "rotate": 0.0,
         "grouped": 1.0, "last_pick": 1.0, "renormalise": 1.0}


def _mm(spec: str, *xs):
    return jnp.einsum(spec, *xs, precision=_HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps
                             ) * w


def _conv(x, taps):
    """Causal depthwise convolution: x [B,S,N,W], taps [T,N,W]."""
    n = taps.shape[0]
    padded = jnp.pad(x, ((0, 0), (n - 1, 0), (0, 0), (0, 0)))
    return sum(padded[:, j:j + x.shape[1]] * taps[j] for j in range(n))


def _delta_rule(q, k, v, g, beta, inner: int = 64):
    """Token by token: all of [B,S,N,...], state [B,N,dk,dv]."""
    b, s, n, d = q.shape
    inner = min(inner, s)
    if s % inner:
        raise ValueError(f"sequence {s} is no multiple of {inner}")

    def token(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = state * jnp.exp(g_t)[..., None]
        seen = _mm("bnk,bnkv->bnv", k_t, state)
        state = state + _mm("bnk,bnv->bnkv", k_t * b_t[..., None], v_t - seen)
        return state, _mm("bnk,bnkv->bnv", q_t, state)

    @jax.checkpoint
    def run(state, xs):
        return jax.lax.scan(token, state, xs)

    xs = tuple(jnp.moveaxis(x, 1, 0).reshape((s // inner, inner) + x.shape[:1]
                                            + x.shape[2:])
               for x in (q, k, v, g, beta))
    _, out = jax.lax.scan(run, jnp.zeros((b, n, d, v.shape[-1]), jnp.float32),
                          xs)
    return jnp.moveaxis(out.reshape((s,) + out.shape[2:]), 0, 1)


def _kda(p, u, sz: Sizes, fault):
    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                                 + 1e-6)
    q, k, v = (jax.nn.silu(_conv(_mm("bshk,hknw->bsnw", u,
                                     p[f"kda_/conv/{c}_proj"]),
                                 p[f"kda_/conv/{c}_conv"])) for c in "qkv")
    low = _mm("bshk,hkr->bsr", u, p["kda_/gates/decay_down"])
    g = -jnp.exp(p["kda_/gates/a_log"])[:, None] * jax.nn.softplus(
        _mm("bsr,rnw->bsnw", low, p["kda_/gates/decay_up"])
        + p["kda_/gates/dt_bias"])
    # the eigenvalue of I - beta k k^T along k is 1 - beta, in (-1, 1)
    beta = fault["beta_scale"] * jax.nn.sigmoid(
        _mm("bshk,hkn->bsn", u, p["kda_/gates/beta"]))
    low = _mm("bshk,hkr->bsr", u, p["kda_/gates/out_down"])
    gate = jax.nn.sigmoid(_mm("bsr,rnw->bsnw", low, p["kda_/gates/out_up"]))
    o = _delta_rule(unit(q) * sz.kda_dim ** -0.5, unit(k), v, g, beta)
    o = _rms(o, p["kda_/out/norm_scale"], sz.eps) * gate
    return _mm("bsnw,nwhk->bshk", o, p["kda_/out/proj"])


def _rotate(x, theta: float):
    """x [B,S,N,W] rotated by the default table of `theta`, rotate-half over
    the whole head: what this model does NOT do (the fault `rotate`)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq[None]
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    low, high = x[..., :half], x[..., half:]
    return jnp.concatenate([low * cos - high * sin, high * cos + low * sin],
                           -1)


def _gqa(p, u, sz: Sizes, fault, rows: int = 256):
    q = _mm("bshk,hknw->bsnw", u, p["gqa_/proj/q_proj"])
    k = _mm("bshk,hkgw->bsgw", u, p["gqa_/proj/k_proj"])
    v = _mm("bshk,hkgw->bsgw", u, p["gqa_/proj/v_proj"])
    q = jnp.where(fault["rotate"] > 0, _rotate(q, sz.rope_theta), q
                  ) * sz.head_dim ** -0.5
    k = jnp.where(fault["rotate"] > 0, _rotate(k, sz.rope_theta), k)
    # query head n reads K/V head n // group (planted fault: n % kv heads)
    n = jnp.arange(sz.q_heads)
    mine = jnp.where(fault["grouped"] > 0, n // (sz.q_heads // sz.kv_heads),
                     n % sz.kv_heads)
    k, v = jnp.take(k, mine, axis=2), jnp.take(v, mine, axis=2)
    s = u.shape[1]
    rows = min(rows, s)
    if s % rows:
        raise ValueError(f"sequence {s} is no multiple of {rows}")
    col = jnp.arange(s)

    @jax.checkpoint
    def block(_, rows_of):
        q_rows, first = rows_of
        scores = _mm("brnw,btnw->bnrt", q_rows, k)
        seen = (first + jnp.arange(rows))[:, None] >= col[None, :]
        weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return None, _mm("bnrt,btnw->brnw", weights, v)

    # one block after the other: unrolled, the compiler keeps many blocks'
    # scores alive at once and 8,192 tokens do not fit beside the weights
    blocks = jnp.moveaxis(q.reshape((q.shape[0], s // rows, rows)
                                    + q.shape[2:]), 1, 0)
    _, o = jax.lax.scan(block, None, (blocks, jnp.arange(0, s, rows)))
    o = jnp.moveaxis(o, 0, 1).reshape(q.shape)
    # one gate a channel of every head, from the layer's input, before the
    # output matrix.  Planted faults: no gate at all (`gate` 0); the gate
    # after the output matrix (`gate_first` 0), its logits sent through W_o
    # as the result is, since only there do the shapes meet
    logits = _mm("bshk,hknw->bsnw", u, p["gqa_/gate/gate_proj"])
    gated, first = fault["gate"] > 0, fault["gate_first"] > 0
    out = lambda t: _mm("bsnw,nwhk->bshk", t, p["gqa_/out/out_proj"])
    before = jnp.where(gated & first, jax.nn.sigmoid(logits), 1.0)
    after = jnp.where(gated & ~first, jax.nn.sigmoid(out(logits)), 1.0)
    return out(o * before) * after


def _swiglu(u, gate, up, down):
    return _mm("bsf,fhk->bshk", jax.nn.silu(_mm("bshk,hkf->bsf", u, gate))
               * _mm("bshk,hkf->bsf", u, up), down)


def _experts(p, u, sz: Sizes, fault):
    names = [f"orthogonal_var{i}/orthogonal_var" for i in ("", 1, 2)]
    scores = jax.nn.sigmoid(_mm("bshk,hke->bse", u, p["routed_moe_/router"]))
    _, picked = jax.lax.top_k(scores + jax.lax.stop_gradient(
        p["routed_moe_/router_bias"]), sz.topk)
    weight = jnp.take_along_axis(scores, picked, -1)
    # the planted fault "top-7 for top-8" drops the last pick before the sum
    weight = weight * jnp.where(jnp.arange(sz.topk) == sz.topk - 1,
                                fault["last_pick"], 1.0)
    weight = weight / jnp.where(fault["renormalise"] > 0,
                                jnp.sum(weight, -1, keepdims=True), 1.0
                                ) * sz.scaling

    @jax.checkpoint
    def share(u, weight, e, gate, up, down):
        """Held expert `e` on every token, weighed: recomputed in the
        backward, so that only its weights outlive it, not its hidden rows."""
        mine = jnp.sum(jnp.where(picked == sz.offset + e, weight, 0.0), -1)
        return mine[..., None, None] * _swiglu(u, gate, up, down)

    out, _ = jax.lax.scan(
        lambda out, expert: (out + share(u, weight, *expert), None),
        _swiglu(u, *(p["routed_moe_/shared/" + n] for n in names)),
        (jnp.arange(sz.held), *(p["routed_moe_/" + n] for n in names)))
    # the balance term (1 at a uniform load): the share of the picks that
    # fell on each expert, which takes no gradient, times the mean of its
    # score over the scores' sum, over all experts and all tokens
    load = jnp.zeros((sz.experts,), jnp.float32).at[picked.reshape(-1)].add(
        1.0) / (picked.size // sz.topk)
    part_of = scores / jnp.sum(scores, -1, keepdims=True)
    balance = sz.balance * sz.experts / sz.topk * jnp.sum(
        load * jnp.mean(part_of, (0, 1)))
    return out, balance


def _head_loss(u, head, y_tok, z_loss: float, positions: int = 2048):
    """Summed token loss of `u` [B,S,D] under `head` [D,V], `positions` of a
    row at a time, each block's logits recomputed in the backward."""
    s = u.shape[1]
    positions = min(positions, s)
    if s % positions:
        raise ValueError(f"sequence {s} is no multiple of {positions}")

    @jax.checkpoint
    def block(total, part):
        u_part, y_part = part
        logits = _mm("bsd,dv->bsv", u_part, head)
        log_z = jax.nn.logsumexp(logits, -1)
        picked = jnp.take_along_axis(logits, y_part[..., None], -1)[..., 0]
        return total + jnp.sum(log_z - picked + z_loss * jnp.square(log_z)
                               ), None

    cut = lambda t: jnp.moveaxis(t.reshape((t.shape[0], s // positions,
                                            positions) + t.shape[2:]), 1, 0)
    total, _ = jax.lax.scan(block, jnp.float32(0.0), (cut(u), cut(y_tok)))
    return total


def loss_fn(params: Params, x_tok, y_tok, sz: Sizes, fault=SOUND):
    """Mean token loss of `x_tok`, `y_tok` [rows, S] (int)."""
    params = {k: v.astype(jnp.float32) for k, v in params.items()}
    x = params[_TABLE][x_tok]                                   # [B,S,H,K]
    flat = lambda t: t.reshape(t.shape[:2] + (-1,))
    extra = 0.0
    for (i, c), kind in zip(sz.parts, sz.kinds):
        head = f"gpt/body/@d{i}_{c}/block_/"
        p = {k[len(head):]: v for k, v in params.items() if k.startswith(head)}

        @jax.checkpoint
        def part(x, p, kind=kind):
            u = _rms(flat(x), p["rms_norm_/scale"].reshape(-1), sz.eps
                     ).reshape(x.shape)
            if kind == "kda":
                return x + _kda(p, u, sz, fault), 0.0
            if kind == "gqa":
                return x + _gqa(p, u, sz, fault), 0.0
            out, balance = _experts(p, u, sz, fault)
            return x + out, balance

        x, balance = part(x, p)
        extra = extra + balance
    u = _rms(flat(x), params[_FINAL].reshape(-1), sz.eps)
    head = params[_HEAD][:, :, 0].reshape(u.shape[-1], -1)
    return _head_loss(u, head, y_tok, sz.z_loss) / y_tok.size + extra


def loss_and_grads(params: Params, x_tok, y_tok, sz: Sizes, rows: int,
                   fault=SOUND):
    """Loss and float32 gradients of one batch, `rows` rows at a time."""
    n = x_tok.shape[0]
    rows = min(rows, n)
    if n % rows:
        raise ValueError(f"batch {n} is no multiple of the block {rows}")
    vg = jax.value_and_grad(lambda p, x, y: loss_fn(p, x, y, sz, fault))
    if n == rows:       # no sum over blocks: a second set of gradients less
        return vg(params, x_tok, y_tok)
    xs = x_tok.reshape(n // rows, rows, -1)
    ys = y_tok.reshape(n // rows, rows, -1)

    def block(acc, xy):
        loss, grads = vg(params, *xy)
        return jax.tree_util.tree_map(jnp.add, acc, grads), loss

    zeros = {k: jnp.zeros(v.shape, jnp.float32) for k, v in params.items()}
    total, losses = jax.lax.scan(block, zeros, (xs, ys))
    scale = rows / n
    return jnp.mean(losses), {k: g * scale for k, g in total.items()}


# -- update -------------------------------------------------------------------

def init_opt_state(params: Params, sz: Sizes) -> dict:
    dt = sz.optimizer_slice_dtype
    return {k: {"rows": [jnp.zeros((s,), dt) for s in v.shape],
                "momentum": jnp.zeros(v.shape, dt)}
            for k, v in params.items()}


def _decayed(name: str, ndim: int) -> bool:
    return ndim >= 2 and "norm" not in name and "embed" not in name


def _update_leaf(name, w, g, slots, lr, sz: Sizes):
    w32 = w.astype(jnp.float32)
    # adaptive gradient clipping: ||g|| <= clip * ||w||
    g_norm_recip = jnp.minimum(1 / jnp.sqrt(jnp.sum(jnp.square(g))), 1e6)
    w_norm = jnp.maximum(jnp.sqrt(jnp.sum(jnp.square(w32))), 1e-3)
    g = g * jnp.minimum(w_norm * g_norm_recip * sz.clip, 1.0)
    # SM3: the smallest of the per-axis row maxima stands for the accumulator
    nd = g.ndim
    rows = [r.astype(jnp.float32).reshape([-1 if a == i else 1
                                           for a in range(nd)])
            for i, r in enumerate(slots["rows"])]
    acc = functools.reduce(jnp.minimum, rows) + jnp.square(g)
    new_rows = [jnp.max(acc, tuple(a for a in range(nd) if a != i))
                for i in range(nd)]
    g = g / jnp.maximum(jnp.sqrt(acc), 1e-5)
    # nesterov momentum, then the learning rate, then decay
    mom = sz.momentum * slots["momentum"].astype(jnp.float32) + g
    step = (g + sz.momentum * mom) * lr
    if _decayed(name, nd):
        step = step + w32 * (lr * sz.weight_decay)
    dt = sz.optimizer_slice_dtype
    return ((w32 - step).astype(w.dtype),
            {"rows": [r.astype(dt) for r in new_rows],
             "momentum": mom.astype(dt)})


def learning_rate(step, sz: Sizes):
    stepf = jnp.asarray(step, jnp.float32)
    warm = stepf / max(sz.warmup_steps, 1)
    return sz.learning_rate * jnp.where(stepf < sz.warmup_steps, warm, 1.0)


def train_step(params: Params, opt_state: dict, step, x_tok, y_tok, fault,
               sz: Sizes, rows: int):
    """One update.  Returns (params, opt_state, loss, gradient norm,
    per-leaf gradient norms in the order of the sorted names)."""
    loss, grads = loss_and_grads(params, x_tok, y_tok, sz, rows, fault)
    lr = learning_rate(step, sz)
    new_p, new_s = {}, {}
    for name in params:
        new_p[name], new_s[name] = _update_leaf(
            name, params[name], grads[name], opt_state[name], lr, sz)
    per_leaf = leaf_norms(grads)
    return new_p, new_s, loss, jnp.sqrt(jnp.sum(jnp.square(per_leaf))), per_leaf


# -- what a run is compared on ------------------------------------------------

LOWER = {
    # case -> what stands in the program's place: the float32 slices held in
    # the nearest precision below, and seven planted faults
    "bf16_slices": {"slice_dtype": "bfloat16"},
    "beta_not_doubled": {"beta_scale": 1.0},
    "no_gate": {"gate": 0.0},
    "gate_after_out_proj": {"gate_first": 0.0},
    "default_rotation_on": {"rotate": 1.0},
    "kv_head_interleaved": {"grouped": 0.0},
    "top7_for_top8": {"last_pick": 0.0},
    "no_renormalisation": {"renormalise": 0.0},
}


def follow(sz: Sizes, seed: int, batches, n_steps: int, rows: int,
           lower: typing.Optional[str] = None, half_batch: bool = False
           ) -> dict:
    """Drive `n_steps` updates from the seed's weights over `batches`
    (a list of (x, y) int arrays, used in turn) and return what
    `compare.readings` wants: per-step loss and gradient norm, per-leaf SM3
    row mass after step 1, per-leaf gradient norm of step 1 and per-leaf
    norm of the parameters' change after the last step.

    `lower` names a case of `LOWER`; `half_batch` plants the fault of a step
    that leaves half of its rows out and takes the mean over the rest.
    """
    case = dict(LOWER[lower]) if lower else {}
    if "slice_dtype" in case:
        sz = sz._replace(slice_dtype=case.pop("slice_dtype"))
    fault = {k: jnp.float32(case.get(k, v)) for k, v in SOUND.items()}
    with jax.default_matmul_precision("highest"):
        step_fn = jax.jit(functools.partial(train_step, sz=sz, rows=rows),
                          donate_argnums=(0, 1))
        params = init_weights(sz, seed)
        state = init_opt_state(params, sz)
        out = {"loss": [], "grad_norm": [], "seconds": []}
        for i in range(n_steps):
            t0 = time.perf_counter()
            x, y = batches[i % len(batches)]
            if half_batch:
                x, y = x[:x.shape[0] // 2], y[:y.shape[0] // 2]
            params, state, loss, gnorm, per_leaf = step_fn(
                params, state, i, jnp.asarray(x), jnp.asarray(y), fault)
            out["loss"].append(float(loss))
            out["grad_norm"].append(float(gnorm))
            out["seconds"].append(time.perf_counter() - t0)
            if i == 0:
                out["grad_leaf"] = np.asarray(per_leaf)
                out["sm3_leaf"] = np.asarray(sm3_mass(
                    {k: v["rows"] for k, v in state.items()}))
        # the seed's weights are drawn again inside one program: a second
        # copy of 3.6 GB of weights would not fit beside the update
        out["change_leaf"] = np.asarray(change_since_seed(params, sz, seed))
    out["names"] = sorted(params)
    return out


@jax.jit
def leaf_norms(tree: Params):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
        tree[k].astype(jnp.float32)))) for k in sorted(tree)])


@jax.jit
def change_norms(after: Params, before: Params):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
        after[k].astype(jnp.float32) - before[k].astype(jnp.float32))))
        for k in sorted(after)])


@jax.jit
def sm3_mass(rows: typing.Dict[str, typing.Sequence[jnp.ndarray]]):
    """Per leaf, the root of the summed SM3 row maxima after one step: a
    norm of the clipped first gradient as the optimizer keeps it."""
    return jnp.stack([jnp.sqrt(sum(jnp.sum(r.astype(jnp.float32))
                                   for r in rows[k])) for k in sorted(rows)])
