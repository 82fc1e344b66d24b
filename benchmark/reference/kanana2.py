"""Plain reference of a sparse-expert decoder whose every layer is latent
attention with rotated decoupled keys (Kanana-2-30B-A3B, DeepSeek-V3's
architecture without query low rank), and its update, for one chip's share
of an expert-parallel host.

Written from the published description (`config.json` of
kakaocorp/kanana-2-30b-a3b-instruct-2601, `model_type` `deepseek_v3`), in
float32 with `highest` matmul precision, importing nothing of the program.
The expert layer, the head and loss, and the optimizer are the Solar-Open2
reference's own, imported (sigmoid scores, a selection bias, renormalised
weights, shared experts and the balance term are the same there).  `x` is
the stream `[B, S, D]`, `rms(x) = x * rsqrt(mean(x^2) + eps) * w`:

    every layer   x = x + mla(rms_1(x));  x = x + ffn(rms_2(x))
    mla           q = u W_q -> [32, 128 + 64]:  q_nope, q_pe
                  c = u W_kva -> [512 + 64]:  c_kv = rms(c[:512]) g,
                    k_pe = c[512:] (one a token, shared by every head)
                  [k_nope, v] = c_kv W_kvb -> [32, 128 + 128]
                  q_pe, k_pe <- rot(., p): pair i = (x_2i, x_2i+1) turned by
                    p * rope_theta^(-2i / 64) (rope_interleave)
                  o_h = softmax([q_nope, q_pe]_h . [k_nope_h, k_pe]^T
                    / sqrt(192) + causal) v_h;  y = concat_h o_h W_o
    layer 0       (silu(u W_gate) * (u W_up)) W_down, 6,144 wide
    layers 1-4    s = sigmoid(u W_r); top-6 of s + b (b takes no gradient);
                  w_e = s_e / sum_sel s * 2.448;
                  y = sum_{e selected and held} w_e ffn_e(u) + ffn_shared(u)
                  (the two shared experts as one of 1,536); the balance term
                  at `moe_balance_weight` (Solar-Open2's)
    output        logits = rms_f(x) W_head; loss = CE + z_loss * mean(log_z^2)

Attention takes a block of `rows` query rows at a time against every key,
as whole masked rows, each block recomputed in the backward: the `[S, S]`
scores never exist whole, so a sequence of 32,768 fits the chip.  The
rotation is written pair by pair and put back in the published order; the
program turns the same pairs and keeps them rotate-half wise, which leaves
every dot product as it is.

Departures from the description, each on purpose: this chip's share (16 of
128 experts, an eighth of the vocabulary, layers 0-4); Gaussian weights from
the seed; the selection bias 0 (`noaux_tc` with one group is a plain top-k
of score plus bias); the narrower state rounded between steps; assumed,
since the source says nothing of them: the balance weight, the optimizer
chain and initialisers; left out: the multi-token prediction module (the
source has none).

`LOWER` names the cases `tests/read_controls.py` reads in the program's
place: the slices held in the nearest precision below the stated one, and
three planted faults, each a traced flag of `SOUND`.
"""
from __future__ import annotations

import functools
import importlib.util
import os
import time
import typing

import jax
import jax.numpy as jnp
import numpy as np


def _sibling(name: str):
    """A reference beside this one, loaded by its path (the harness loads
    references by path, not as a package)."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        name + ".py")
    spec = importlib.util.spec_from_file_location(f"_kanana_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_solar = _sibling("solar_open2")
_mm, _rms, _matrix, _swiglu = (_solar._mm, _solar._rms, _solar._matrix,
                               _solar._swiglu)
seed_key, init_opt_state, learning_rate = (
    _solar.seed_key, _solar.init_opt_state, _solar.learning_rate)
leaf_norms, change_norms, sm3_mass = (_solar.leaf_norms, _solar.change_norms,
                                      _solar.sm3_mass)

Params = typing.Dict[str, jnp.ndarray]
_TABLE, _HEAD, _FINAL = _solar._TABLE, _solar._HEAD, _solar._FINAL
_KINDS = ("mla", "gated_feed_forward", "routed_moe")


class Sizes(typing.NamedTuple):
    """What the reference needs of a configuration file."""
    kinds: typing.Tuple[str, ...]                        # by block part
    parts: typing.Tuple[typing.Tuple[int, int], ...]     # (depth, block index)
    heads: int
    features_per_head: int
    sequence_length: int
    vocab_size: int
    dense: int
    q_heads: int
    nope: int
    rope: int
    v_dim: int
    latent: int
    rope_theta: float
    interleaved: bool
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
            if name == "mla" and (extras != ["rope"]
                                  or raw.get("mla_use_nope", False)):
                raise ValueError("reference writes latent attention with "
                                 "rotated decoupled keys (mla-rope) only")
            kinds.append((name, extras))
        moe = next(e for n, e in kinds if n == "routed_moe")
        if not {"sigmoid", "bias", "gated"} <= set(moe):
            raise ValueError(f"reference knows no expert layer {moe}")
        if raw.get("rope_scaling") is not None:
            raise ValueError("reference writes the default rotary table only")
        chain = raw["optimizer"].split("-")
        if [c.split(":")[0] for c in chain] != [
                "adaptive_clip", "sm3", "momentum", "learning_rate"]:
            raise ValueError(f"reference knows no optimizer {raw['optimizer']}")
        mom = chain[2].split(":")
        if mom[2:] != ["1", "1"]:
            raise ValueError("reference writes nesterov momentum only")
        parts = tuple((i, c) for i, row in enumerate(raw["block_schedule"])
                      for c in row)
        h, k = raw["heads"], raw["features_per_head"]
        return cls(
            kinds=tuple(kinds[c][0] for _, c in parts), parts=parts,
            heads=h, features_per_head=k,
            sequence_length=raw["sequence_length"],
            vocab_size=raw["vocab_size"],
            dense=int(h * k * raw["intermediate_feed_forward_multiplier"]),
            q_heads=raw.get("num_attention_heads") or h,
            nope=raw["qk_nope_head_dim"], rope=raw["qk_rope_head_dim"],
            v_dim=raw["v_head_dim"], latent=raw["kv_lora_rank"],
            rope_theta=float(raw["rope_theta"]),
            interleaved=bool(raw.get("rope_interleave", True)),
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


def _part_leaves(sz: Sizes, kind: str) -> typing.Dict[str, tuple]:
    """Leaves of one block part under `.../block_/`, by the program's names:
    (shape, (mean, stddev))."""
    h, k, d = sz.heads, sz.features_per_head, sz.hidden
    n = sz.q_heads
    weight = (1.0, 0.02)
    out = {"rms_norm_/scale": ((h, k), weight)}
    if kind == "mla":
        out.update({
            "mla_/q_proj": _matrix((h, k, n, sz.nope + sz.rope), d),
            "mla_/kv_down": _matrix((h, k, sz.latent + sz.rope), d),
            "mla_/latent_norm": ((sz.latent,), weight),
            "mla_/kv_up": _matrix((sz.latent, n, sz.nope + sz.v_dim),
                                  sz.latent),
            "mla_/out_proj": _matrix((n, sz.v_dim, h, k), n * sz.v_dim)})
    elif kind == "gated_feed_forward":
        f = sz.dense
        for i, rule in enumerate((_matrix((h, k, f), d), _matrix((h, k, f), d),
                                  _matrix((f, h, k), f))):
            out[f"gated_feed_forward_/orthogonal_var{i or ''}/orthogonal_var"
                ] = rule
    else:
        out.update({name: rule for name, rule in _solar._part_leaves(
            sz, "routed_moe").items() if name.startswith("routed_moe_/")})
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

# a sound model's flags; a planted fault of `LOWER` moves one (the expert
# layer's are Solar-Open2's, which `_solar._experts` reads)
SOUND = {"rotate": 1.0, "interleave": 1.0, "shared": 1.0, "last_pick": 1.0,
         "renormalise": 1.0}


def rotate_pairs(x, theta: float, interleave=1.0):
    """`x [B, S, ..., d]` turned at positions `0 .. S - 1`: pair `i` is
    `(x_2i, x_2i+1)` (with `interleave` 0, the planted fault, `(x_i,
    x_i+d/2)`), turned by `p * theta^(-2i / d)` and put back in its place."""
    d = x.shape[-1]
    freq = theta ** (-np.arange(0, d, 2) / d)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * jnp.asarray(
        freq, jnp.float32)[None]
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 3) + (d // 2,)
    cos, sin = jnp.cos(angle).reshape(shape), jnp.sin(angle).reshape(shape)

    def turn(a, b):
        return a * cos - b * sin, b * cos + a * sin

    even, odd = turn(x[..., 0::2], x[..., 1::2])
    paired = jnp.stack([even, odd], -1).reshape(x.shape)
    low, high = turn(x[..., :d // 2], x[..., d // 2:])
    halves = jnp.concatenate([low, high], -1)
    return jnp.where(interleave > 0, paired, halves)


def _mla(p, u, sz: Sizes, fault, rows: int = 128, group: int = 8):
    """The layer, `group` heads at a time and each group's attention `rows`
    query rows at a time, both recomputed in the backward: at 32,768 tokens
    the float32 q, k, v and output of all 32 heads would not fit beside
    their gradients."""
    r, nope = sz.latent, sz.nope
    c = _mm("bshk,hkl->bsl", u, p["mla_/kv_down"])
    c_kv = _rms(c[..., :r], p["mla_/latent_norm"], sz.eps)
    # the planted fault "rotate-half pairs" takes the other pairs than the
    # configuration's rope_interleave names
    pairs = fault["interleave"] if sz.interleaved else 1.0 - fault[
        "interleave"]

    def turn(x):
        # the planted fault "no rotation" leaves both parts as they are
        return jnp.where(fault["rotate"] > 0,
                         rotate_pairs(x, sz.rope_theta, pairs), x)

    k_pe = turn(c[..., r:])
    s = u.shape[1]
    rows, group = min(rows, s), min(group, sz.q_heads)
    if s % rows or sz.q_heads % group:
        raise ValueError(f"sequence {s} is no multiple of {rows} or "
                         f"{sz.q_heads} heads of {group}")
    col = jnp.arange(s)

    @jax.checkpoint
    def heads(total, weights):
        w_q, w_kv, w_o = weights
        q = _mm("bshk,hknw->bsnw", u, w_q)
        q = jnp.concatenate([q[..., :nope], turn(q[..., nope:])], -1) * (
            nope + sz.rope) ** -0.5
        kv = _mm("bsl,lnw->bsnw", c_kv, w_kv)
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
            k_pe[:, :, None], kv.shape[:3] + (sz.rope,))], -1)
        v = kv[..., nope:]

        @jax.checkpoint
        def block(_, rows_of):
            q_rows, first = rows_of
            scores = _mm("brnw,btnw->bnrt", q_rows, k)
            seen = (first + jnp.arange(rows))[:, None] >= col[None, :]
            weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
            return None, _mm("bnrt,btnw->brnw", weights, v)

        blocks = jnp.moveaxis(q.reshape((q.shape[0], s // rows, rows)
                                        + q.shape[2:]), 1, 0)
        _, o = jax.lax.scan(block, None, (blocks, jnp.arange(0, s, rows)))
        o = jnp.moveaxis(o, 0, 1).reshape(q.shape[:2] + o.shape[3:])
        return total + _mm("bsnw,nwhk->bshk", o, w_o), None

    cut = lambda w, axis: jnp.moveaxis(w.reshape(
        w.shape[:axis] + (sz.q_heads // group, group) + w.shape[axis + 1:]),
        axis, 0)
    total, _ = jax.lax.scan(heads, jnp.zeros_like(u), (
        cut(p["mla_/q_proj"], 2), cut(p["mla_/kv_up"], 1),
        cut(p["mla_/out_proj"], 0)))
    return total


def _dense(p, u):
    names = [f"gated_feed_forward_/orthogonal_var{i}/orthogonal_var"
             for i in ("", 1, 2)]
    return _swiglu(u, *(p[n] for n in names))


def _experts(p, u, sz: Sizes, fault):
    """Solar-Open2's expert layer; the planted fault "no shared experts"
    takes their part out again."""
    out, balance = _solar._experts(p, u, sz, fault)
    names = [f"routed_moe_/shared/orthogonal_var{i}/orthogonal_var"
             for i in ("", 1, 2)]
    shared = _swiglu(u, *(p[n] for n in names))
    return out - (1.0 - fault["shared"]) * shared, balance


def loss_fn(params: Params, x_tok, y_tok, sz: Sizes, fault=SOUND):
    """Mean token loss of `x_tok`, `y_tok` [rows, S] (int), with the
    balance terms."""
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
            if kind == "mla":
                return x + _mla(p, u, sz, fault), 0.0
            if kind == "gated_feed_forward":
                return x + _dense(p, u), 0.0
            out, balance = _experts(p, u, sz, fault)
            return x + out, balance

        x, balance = part(x, p)
        extra = extra + balance
    u = _rms(flat(x), params[_FINAL].reshape(-1), sz.eps)
    head = params[_HEAD][:, :, 0].reshape(u.shape[-1], -1)
    return _solar._head_loss(u, head, y_tok, sz.z_loss) / y_tok.size + extra


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

def train_step(params: Params, opt_state: dict, step, x_tok, y_tok, fault,
               sz: Sizes, rows: int):
    """One update.  Returns (params, opt_state, loss, gradient norm,
    per-leaf gradient norms in the order of the sorted names)."""
    loss, grads = loss_and_grads(params, x_tok, y_tok, sz, rows, fault)
    lr = learning_rate(step, sz)
    new_p, new_s = {}, {}
    for name in params:
        new_p[name], new_s[name] = _solar._update_leaf(
            name, params[name], grads[name], opt_state[name], lr, sz)
    per_leaf = leaf_norms(grads)
    return new_p, new_s, loss, jnp.sqrt(jnp.sum(jnp.square(per_leaf))), per_leaf


# -- what a run is compared on ------------------------------------------------

LOWER = {
    # case -> what stands in the program's place: the float32 slices held in
    # the nearest precision below, and three planted faults
    "bf16_slices": {"slice_dtype": "bfloat16"},
    "no_rotation": {"rotate": 0.0},
    "rotate_half_pairs": {"interleave": 0.0},
    "no_shared_experts": {"shared": 0.0},
}


@functools.lru_cache(maxsize=None)
def _step_fn(sz: Sizes, rows: int):
    """One compiled update a size: the planted faults are traced flags, so
    every case of `LOWER` but the slices' precision runs the sound case's
    program."""
    return jax.jit(functools.partial(train_step, sz=sz, rows=rows),
                   donate_argnums=(0, 1))


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
        step_fn = _step_fn(sz, rows)
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
        # copy of 2.3 GB of weights would not fit beside the update
        out["change_leaf"] = np.asarray(change_since_seed(params, sz, seed))
    out["names"] = sorted(params)
    return out
