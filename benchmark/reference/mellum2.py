"""Plain reference of a sparse-expert decoder with grouped-query attention,
rotary positions and window and full layers side by side (Mellum 2), and
its update, for one chip's share of an expert-parallel slice.

Written from the published description (`config.json` of
JetBrains/Mellum2-12B-A2.5B-Instruct), in float32 with `highest` matmul
precision, importing nothing of the program.  `x` is the stream `[B, S, D]`,
`rms(x) = x * rsqrt(mean(x^2) + eps) * w`:

    every layer   x = x + attn(rms_1(x));  x = x + moe(rms_2(x))
    attn          q = u W_q -> [heads, d];  k, v = u W_k, u W_v -> [kv heads, .]
                  q, k = rot(q, pos), rot(k, pos): rotate-half over the whole
                    head, rot(x) = x cos(p f) + [-x_hi, x_lo] sin(p f)
                    sliding layers (default): f_i = theta^(-2i/d)
                    full layers (YaRN): f_i blended between theta^(-2i/d) and
                      theta^(-2i/d) / factor by the linear ramp over the pairs
                      between the one that turns beta_fast times in
                      original_max_position_embeddings positions and the one
                      that turns beta_slow times; cos, sin times
                      attention_factor
                  query head h reads K/V head h // (heads / kv heads)
                  scores q k^T / sqrt(d); mask: j <= i, and on sliding layers
                    also i - j < sliding_window
                  y = concat_h softmax(scores) v  W_o
    expert layer  p = softmax(u W_r) over all experts; top-k;
                  w_e = p_e / sum_sel p;
                  y = sum_{e selected and held} w_e ffn_e(u)
                  ffn_e(u) = (silu(u W_gate,e) * (u W_up,e)) W_down,e
    output        logits = rms_f(x) W_head; loss = CE + z_loss * mean(log_z^2)
    update        adaptive_clip -> sm3 -> momentum (nesterov) -> lr, decay
                  lr * wd * w on every leaf of two axes or more that is no
                  norm weight, table or head; linear warm-up

Attention takes a block of query rows at a time against every key, as whole
masked rows, with the K/V heads repeated plainly; each held expert is
applied to every token and its result weighed by the token's combine weight,
zero where the router did not select it; the batch is taken `rows` rows at
a time.

Departures from the description, each on purpose:
- this chip's share: only experts `expert_offset .. + experts_held` are
  applied (the router scores all of them), the vocabulary is the slice the
  configuration states; what the absent experts would add is left out;
- weights are Gaussian from the seed (`_part_leaves`);
- the state the configuration stores in a narrower type (momentum and SM3
  rows) is rounded to that type between steps;
- assumed, since the source says nothing of them: no norm on q or k, no
  multi-token-prediction head, the optimizer chain and initialisers.

`LOWER` names the cases `tests/read_controls.py` reads in the program's
place: the slices held in the nearest precision below the stated one, and
seven planted faults, each a traced flag of `SOUND`.
"""
from __future__ import annotations

import functools
import math
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
_SLIDING, _FULL = "sliding_attention", "full_attention"


class Sizes(typing.NamedTuple):
    """What the reference needs of a configuration file."""
    kinds: typing.Tuple[str, ...]       # by block part: a layer type or moe
    parts: typing.Tuple[typing.Tuple[int, int], ...]     # (depth, block index)
    heads: int
    features_per_head: int
    sequence_length: int
    vocab_size: int
    q_heads: int
    kv_heads: int
    head_dim: int
    window: int
    rope: typing.Tuple[typing.Tuple[str, tuple], ...]    # layer type -> items
    experts: int
    held: int
    offset: int
    topk: int
    expert_width: int
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
            if name not in ("gqa", "routed_moe") or not block.get("skip"):
                raise ValueError(f"reference knows no block {block}")
            kinds.append((name, extras))
        moe = next(e for n, e in kinds if n == "routed_moe")
        if "gated" not in moe or {"sigmoid", "bias"} & set(moe) or any(
                e.startswith("shared") for e in moe):
            raise ValueError(f"reference knows no expert layer {moe}")
        chain = raw["optimizer"].split("-")
        if [c.split(":")[0] for c in chain] != [
                "adaptive_clip", "sm3", "momentum", "learning_rate"]:
            raise ValueError(f"reference knows no optimizer {raw['optimizer']}")
        mom = chain[2].split(":")
        if mom[2:] != ["1", "1"]:
            raise ValueError("reference writes nesterov momentum only")
        if raw.get("routed_scaling_factor", 1.0) != 1.0:
            raise ValueError("reference scales no combine weight")
        parts = tuple((i, c) for i, row in enumerate(raw["block_schedule"])
                      for c in row)
        h, k = raw["heads"], raw["features_per_head"]
        return cls(
            kinds=tuple(kinds[c][1][0] if kinds[c][0] == "gqa"
                        else "routed_moe" for _, c in parts), parts=parts,
            heads=h, features_per_head=k,
            sequence_length=raw["sequence_length"],
            vocab_size=raw["vocab_size"],
            q_heads=raw["num_attention_heads"],
            kv_heads=raw["num_key_value_heads"], head_dim=raw["head_dim"],
            window=raw["sliding_window"],
            rope=tuple(sorted((kind, tuple(sorted(entry.items())))
                              for kind, entry in
                              raw["rope_parameters"].items())),
            experts=raw["experts"], held=raw["experts_held"],
            offset=raw.get("expert_offset", 0),
            topk=next(int(e[4:]) for e in moe if e.startswith("topk")),
            expert_width=raw["moe_intermediate_size"],
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
    out = {"rms_norm_/scale": ((h, k), (1.0, 0.02))}
    if kind == "routed_moe":
        f, e = sz.expert_width, sz.held
        for i in range(3):
            out[f"routed_moe_/orthogonal_var{i or ''}/orthogonal_var"] = (
                _matrix((e, h, k, f), d, e) if i < 2
                else _matrix((e, f, h, k), f, e))
        out["routed_moe_/router"] = ((h, k, sz.experts), (0.0, d ** -0.5))
    else:
        n, g, w = sz.q_heads, sz.kv_heads, sz.head_dim
        out.update({
            "gqa_/proj/q_proj": _matrix((h, k, n, w), d),
            "gqa_/proj/k_proj": _matrix((h, k, g, w), d),
            "gqa_/proj/v_proj": _matrix((h, k, g, w), d),
            "gqa_/out/out_proj": _matrix((n, w, h, k), n * w)})
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
SOUND = {"window_cut": 0.0, "window": 1.0, "yarn": 1.0,
         "attention_factor": 1.0, "grouped": 1.0, "last_pick": 1.0,
         "renormalise": 1.0}


def _mm(spec: str, *xs):
    return jnp.einsum(spec, *xs, precision=_HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps
                             ) * w


def rotary_frequencies(entry: dict, dim: int):
    """(f [dim / 2] float64, attention factor) of one `rope_parameters`
    entry, by the direct formula."""
    theta = entry["rope_theta"]
    plain = np.array([theta ** (-2 * i / dim) for i in range(dim // 2)])
    if entry.get("rope_type", "default") == "default":
        return plain, 1.0
    factor, reach = entry["factor"], entry["original_max_position_embeddings"]
    # the pair whose wavelength fits `turns` times into `reach` positions
    pair = lambda turns: dim * math.log(reach / (turns * 2 * math.pi)) / (
        2 * math.log(theta))
    low = max(math.floor(pair(entry["beta_fast"])), 0)
    high = min(math.ceil(pair(entry["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (plain / factor * ramp + plain * (1 - ramp),
            entry.get("attention_factor", 0.1 * math.log(factor) + 1))


def _rotary_table(sz: Sizes, kind: str, length: int, fault):
    """(cos, sin) [length, dim / 2] of a layer type; the faults put the
    default table in a YaRN table's place, or leave its factor out."""
    plain, _ = rotary_frequencies({"rope_theta": dict(dict(sz.rope)[kind])
                                   ["rope_theta"]}, sz.head_dim)
    mine, factor = rotary_frequencies(dict(dict(sz.rope)[kind]), sz.head_dim)
    f32 = lambda x: jnp.asarray(np.asarray(x, np.float32))
    freq = jnp.where(fault["yarn"] > 0, f32(mine), f32(plain))
    factor = jnp.where((fault["yarn"] > 0) & (fault["attention_factor"] > 0),
                       jnp.float32(factor), 1.0)
    angle = jnp.arange(length, dtype=jnp.float32)[:, None] * freq[None]
    return jnp.cos(angle) * factor, jnp.sin(angle) * factor


def _rotate(x, cos, sin):
    """x [B,S,N,W] by cos, sin [S, W/2]: rotate-half over the whole head."""
    half = x.shape[-1] // 2
    low, high = x[..., :half], x[..., half:]
    cos, sin = cos[:, None], sin[:, None]
    return jnp.concatenate([low * cos - high * sin, high * cos + low * sin],
                           -1)


def _attention(p, u, sz: Sizes, kind: str, fault, rows: int = 256):
    q = _mm("bshk,hknw->bsnw", u, p["gqa_/proj/q_proj"])
    k = _mm("bshk,hkgw->bsgw", u, p["gqa_/proj/k_proj"])
    v = _mm("bshk,hkgw->bsgw", u, p["gqa_/proj/v_proj"])
    s = u.shape[1]
    cos, sin = _rotary_table(sz, kind, s, fault)
    q = _rotate(q, cos, sin) * sz.head_dim ** -0.5
    k = _rotate(k, cos, sin)
    # query head n reads K/V head n // group (planted fault: n % kv heads)
    n = jnp.arange(sz.q_heads)
    mine = jnp.where(fault["grouped"] > 0, n // (sz.q_heads // sz.kv_heads),
                     n % sz.kv_heads)
    k, v = jnp.take(k, mine, axis=2), jnp.take(v, mine, axis=2)
    window = jnp.float32(s)         # a full layer: no key is too far behind
    if kind == _SLIDING:
        window = jnp.where(fault["window"] > 0,
                           sz.window - fault["window_cut"], window)
    rows = min(rows, s)
    if s % rows:
        raise ValueError(f"sequence {s} is no multiple of {rows}")
    col = jnp.arange(s)

    @jax.checkpoint
    def block(_, rows_of):
        q_rows, first = rows_of
        scores = _mm("brnw,btnw->bnrt", q_rows, k)
        behind = (first + jnp.arange(rows))[:, None] - col[None, :]
        seen = (behind >= 0) & (behind < window)
        weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return None, _mm("bnrt,btnw->brnw", weights, v)

    # one block after the other: unrolled, the compiler keeps many blocks'
    # scores alive at once and 8,192 tokens do not fit beside the weights
    blocks = jnp.moveaxis(q.reshape((q.shape[0], s // rows, rows)
                                    + q.shape[2:]), 1, 0)
    _, o = jax.lax.scan(block, None, (blocks, jnp.arange(0, s, rows)))
    o = jnp.moveaxis(o, 0, 1).reshape(q.shape)
    return _mm("bsnw,nwhk->bshk", o, p["gqa_/out/out_proj"])


def _swiglu(u, gate, up, down):
    return _mm("bsf,fhk->bshk", jax.nn.silu(_mm("bshk,hkf->bsf", u, gate))
               * _mm("bshk,hkf->bsf", u, up), down)


def _experts(p, u, sz: Sizes, fault):
    stacks = [p[f"routed_moe_/orthogonal_var{i}/orthogonal_var"]
              for i in ("", 1, 2)]
    scores = jax.nn.softmax(_mm("bshk,hke->bse", u, p["routed_moe_/router"]),
                            -1)
    _, picked = jax.lax.top_k(scores, sz.topk)
    weight = jnp.take_along_axis(scores, picked, -1)
    # the planted fault "top-7 for top-8" drops the last pick before the sum
    weight = weight * jnp.where(jnp.arange(sz.topk) == sz.topk - 1,
                                fault["last_pick"], 1.0)
    weight = weight / jnp.where(fault["renormalise"] > 0,
                                jnp.sum(weight, -1, keepdims=True), 1.0)

    @jax.checkpoint
    def share(u, weight, e, gate, up, down):
        """Held expert `e` on every token, weighed: recomputed in the
        backward, so that only its weights outlive it, not its hidden rows."""
        mine = jnp.sum(jnp.where(picked == sz.offset + e, weight, 0.0), -1)
        return mine[..., None, None] * _swiglu(u, gate, up, down)

    out, _ = jax.lax.scan(
        lambda out, expert: (out + share(u, weight, *expert), None),
        jnp.zeros_like(u), (jnp.arange(sz.held), *stacks))
    return out


def loss_fn(params: Params, x_tok, y_tok, sz: Sizes, fault=SOUND):
    """Mean token loss of `x_tok`, `y_tok` [rows, S] (int)."""
    params = {k: v.astype(jnp.float32) for k, v in params.items()}
    x = params[_TABLE][x_tok]                                   # [B,S,H,K]
    flat = lambda t: t.reshape(t.shape[:2] + (-1,))
    for (i, c), kind in zip(sz.parts, sz.kinds):
        head = f"gpt/body/@d{i}_{c}/block_/"
        p = {k[len(head):]: v for k, v in params.items() if k.startswith(head)}

        @jax.checkpoint
        def part(x, p, kind=kind):
            u = _rms(flat(x), p["rms_norm_/scale"].reshape(-1), sz.eps
                     ).reshape(x.shape)
            if kind == "routed_moe":
                return x + _experts(p, u, sz, fault)
            return x + _attention(p, u, sz, kind, fault)

        x = part(x, p)
    u = _rms(flat(x), params[_FINAL].reshape(-1), sz.eps).reshape(x.shape)
    logits = _mm("bshk,hkv->bsv", u, params[_HEAD][:, :, 0])
    log_z = jax.nn.logsumexp(logits, -1)
    picked = jnp.take_along_axis(logits, y_tok[..., None], -1)[..., 0]
    return jnp.mean(log_z - picked) + sz.z_loss * jnp.mean(jnp.square(log_z))


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
    "window_one_short": {"window_cut": 1.0},
    "full_mask_on_sliding": {"window": 0.0},
    "default_table_on_full": {"yarn": 0.0},
    "no_attention_factor": {"attention_factor": 0.0},
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
        # copy of 2.4 GB of weights would not fit beside the update
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
