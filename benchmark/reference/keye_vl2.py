"""Plain reference of a sparse-expert decoder whose grouped-query attention
reads only the keys a learned indexer selects (Keye-VL-2.0's language model:
DeepSeek Sparse Attention over GQA, q/k norm, softmax experts with none
shared), and its update, for one chip's share of an expert-parallel host.

Written from the published description (`config.json` of
Kwai-Keye/Keye-VL-2.0-30B-A3B and its `sa_config`), in float32 with
`highest` matmul precision, importing nothing of the program.  The experts,
the head and loss, and the optimizer are the Mellum 2 and Solar-Open2
references' own, imported (ROADMAP D13).  `x` is the stream `[B, S, D]`,
`rms(x) = x * rsqrt(mean(x^2) + eps) * w`:

    every layer   x = x + attn(rms_1(x));  x = x + moe(rms_2(x))
    attn          q = u W_q -> [32, 128];  k, v = u W_k, u W_v -> [4, 128]
                  q, k = rms_head(q) g_q, rms_head(k) g_k    (over 128)
                  q, k = rot(q, pos), rot(k, pos): rotate-half, default table
                    at rope_theta (mrope_section: text positions, one table)
    indexer       u' = u without gradient
                  qI = u' W_qI -> [16, 64];  kI = rms(u' W_kI) g_kI -> [64]
                  w = (u' W_w) 16^-1/2 64^-1/2 -> [16]
                  qI, kI = rot(qI, pos), rot(kI, pos)        (64 wide)
                  I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]),  s <= t
                  S_t = the top-k s of I[t, .] (jax.lax.top_k: ties to the
                    lower position); every s <= t where there are fewer
    attention     o[t, h] = sum_{s in S_t} softmax_s(q[t,h] . k[s, h // 8]
                    / sqrt(128)) v[s, h // 8];  y = o W_o
    indexer loss  p[t] = mean_h softmax over S_t (no gradient)
                  L_I = mean_t KL(p[t] || softmax_{S_t} I[t, .]),
                  its mean over layers joins the loss at weight 1
    expert layer  softmax over all experts, top-8 renormalised, the held
                  ones applied (Mellum 2's); the balance term at
                  `moe_balance_weight` (Solar-Open2's)
    output        logits = rms_f(x) W_head; loss = CE + z_loss * mean(log_z^2)

Attention, scores and selection take a block of `rows` query rows at a time
against every key, as whole masked rows: the `[S, S]` scores never exist
whole.  The kept set is made by scattering `top_k`'s indices, not by the
program's threshold.

Departures from the description, each on purpose: this chip's share (16 of
128 experts, an eighth of the vocabulary); Gaussian weights from the seed;
the narrower state rounded between steps; assumed, since the source says
nothing of them: the q/k norm, the indexer's input, norm, rotary, scales and
loss, the balance weight, the optimizer chain and initialisers; left out:
the vision tower and image positions.

`LOWER` names the cases `tests/read_controls.py` reads in the program's
place: the slices held in the nearest precision below the stated one, and
seven planted faults, each a traced flag of `SOUND`.
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
    spec = importlib.util.spec_from_file_location(f"_keye_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_mellum = _sibling("mellum2")
_solar = _sibling("solar_open2")
_mm, _rms, _rotate, _matrix = (_mellum._mm, _mellum._rms, _mellum._rotate,
                               _mellum._matrix)
seed_key, init_opt_state, learning_rate = (
    _mellum.seed_key, _mellum.init_opt_state, _mellum.learning_rate)
leaf_norms, change_norms, sm3_mass = (_mellum.leaf_norms,
                                      _mellum.change_norms, _mellum.sm3_mass)

Params = typing.Dict[str, jnp.ndarray]
_TABLE, _HEAD, _FINAL = _mellum._TABLE, _mellum._HEAD, _mellum._FINAL


class Sizes(typing.NamedTuple):
    """What the reference needs of a configuration file."""
    kinds: typing.Tuple[str, ...]       # by block part: gqa or routed_moe
    parts: typing.Tuple[typing.Tuple[int, int], ...]     # (depth, block index)
    heads: int
    features_per_head: int
    sequence_length: int
    vocab_size: int
    q_heads: int
    kv_heads: int
    head_dim: int
    rope_theta: float
    index_heads: int
    index_dim: int
    index_topk: int
    experts: int
    held: int
    offset: int
    topk: int
    expert_width: int
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
        base = _mellum.Sizes.from_config(dict(
            raw, block_config=[_mellum_block(b) for b in raw["block_config"]],
            sliding_window=None, rope_parameters={}))
        kinds = tuple("routed_moe" if k == "routed_moe" else "gqa"
                      for k in base.kinds)
        sa = raw["sa_config"]
        if sa["indexer_num_kv_heads"] != 1:
            raise ValueError("reference writes one key head of the indexer")
        fields = {f: getattr(base, f) for f in cls._fields
                  if f in _mellum.Sizes._fields}
        return cls(**dict(
            fields, kinds=kinds, rope_theta=float(raw["rope_theta"]),
            index_heads=sa["indexer_num_heads"],
            index_dim=sa["indexer_head_dim"], index_topk=sa["topk"],
            balance=float(raw["moe_balance_weight"])))


def _mellum_block(block: dict) -> dict:
    """A block of this configuration as the Mellum reference's parser reads
    it: the attention part must be `gqa-full_attention-qknorm-sparse`."""
    spec = block["layer"][-1]
    if spec.startswith("gqa"):
        if spec != "gqa-full_attention-qknorm-sparse":
            raise ValueError(f"reference knows no attention part {spec}")
        return dict(block, layer=block["layer"][:-1] + ["gqa-full_attention"])
    return block


def _part_leaves(sz: Sizes, kind: str) -> typing.Dict[str, tuple]:
    """Leaves of one block part under `.../block_/`, by the program's names:
    (shape, (mean, stddev))."""
    out = _mellum._part_leaves(sz, kind)
    if kind == "gqa":
        h, k, d = sz.heads, sz.features_per_head, sz.hidden
        n, w = sz.index_heads, sz.index_dim
        out.update({
            "gqa_/proj/q_norm": ((sz.head_dim,), (1.0, 0.02)),
            "gqa_/proj/k_norm": ((sz.head_dim,), (1.0, 0.02)),
            "gqa_/indexer/q_proj": _matrix((h, k, n, w), d),
            "gqa_/indexer/k_proj": _matrix((h, k, w), d),
            "gqa_/indexer/weights_proj": _matrix((h, k, n), d),
            "gqa_/indexer/k_norm": ((w,), (1.0, 0.02))})
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

# a sound model's flags; a planted fault of `LOWER` moves one
SOUND = {"qknorm": 1.0, "grouped": 1.0, "last_rank": 1.0, "indexed": 1.0,
         "causal_index": 1.0, "detach": 1.0, "last_pick": 1.0,
         "renormalise": 1.0}


def _table(theta: float, dim: int, length: int):
    freq, _ = _mellum.rotary_frequencies({"rope_theta": theta}, dim)
    angle = jnp.arange(length, dtype=jnp.float32)[:, None] * jnp.asarray(
        freq, jnp.float32)[None]
    return jnp.cos(angle), jnp.sin(angle)


def _keep(scores, first, sz: Sizes, fault, chosen=None):
    """The kept set of a block of rows `[B, R, S]` from its indexer scores:
    the top-k's indices scattered; `chosen` puts a given set in its place."""
    b, rows, s = scores.shape
    behind = (first + jnp.arange(rows))[:, None] - jnp.arange(s)[None, :]
    causal = behind >= 0
    if chosen is not None:
        return chosen & causal
    ranked = jnp.where((fault["causal_index"] > 0) & ~causal, -jnp.inf,
                       scores)
    k = min(sz.index_topk, s)
    _, at = jax.lax.top_k(ranked, k)
    # the planted fault "top-(k-1) for top-k" leaves the last rank out
    mark = (jnp.arange(k) < k - 1) | (fault["last_rank"] > 0)
    picked = jnp.zeros(scores.shape, bool).at[
        jnp.arange(b)[:, None, None], jnp.arange(rows)[None, :, None],
        at].set(jnp.broadcast_to(mark, at.shape))
    # an indexer that also ranks later keys keeps fewer earlier ones; each
    # row keeps itself then, so that no row is left with nothing to read
    picked = picked | ((behind == 0) & (fault["causal_index"] <= 0))
    window = causal & (behind < sz.index_topk)
    return jnp.where(fault["indexed"] > 0, picked & causal, window)


def _attention(p, u, sz: Sizes, fault, rows: int = 256, chosen=None,
               keep_sets: bool = False):
    """The layer's output, its indexer loss and, with `keep_sets`, its kept
    sets `[B, S, S]` (bool, for the tests).  `chosen`: kept sets to use in
    the indexer's place (the program's, so that a key whose score rounds
    differently at the `topk`-th cannot hide an error in the attention)."""
    f = fault
    q = _mm("bshk,hknw->bsnw", u, p["gqa_/proj/q_proj"])
    k = _mm("bshk,hkgw->bsgw", u, p["gqa_/proj/k_proj"])
    v = _mm("bshk,hkgw->bsgw", u, p["gqa_/proj/v_proj"])
    q = jnp.where(f["qknorm"] > 0, _rms(q, p["gqa_/proj/q_norm"], sz.eps), q)
    k = jnp.where(f["qknorm"] > 0, _rms(k, p["gqa_/proj/k_norm"], sz.eps), k)
    s = u.shape[1]
    cos, sin = _table(sz.rope_theta, sz.head_dim, s)
    q = _rotate(q, cos, sin) * sz.head_dim ** -0.5
    k = _rotate(k, cos, sin)
    # query head n reads K/V head n // group (planted fault: n % kv heads)
    n = jnp.arange(sz.q_heads)
    mine = jnp.where(f["grouped"] > 0, n // (sz.q_heads // sz.kv_heads),
                     n % sz.kv_heads)
    k, v = jnp.take(k, mine, axis=2), jnp.take(v, mine, axis=2)
    # the indexer, on the layer's input without its gradient (planted
    # fault: with it, so that L_I teaches the trunk too)
    free = jnp.where(f["detach"] > 0, jax.lax.stop_gradient(u), u)
    qi = _mm("bshk,hknw->bsnw", free, p["gqa_/indexer/q_proj"])
    ki = _rms(_mm("bshk,hkw->bsw", free, p["gqa_/indexer/k_proj"]),
              p["gqa_/indexer/k_norm"], sz.eps)
    w = _mm("bshk,hkn->bsn", free, p["gqa_/indexer/weights_proj"]) * (
        sz.index_heads * sz.index_dim) ** -0.5
    cos_i, sin_i = _table(sz.rope_theta, sz.index_dim, s)
    qi = _rotate(qi, cos_i, sin_i)
    ki = _rotate(ki[:, :, None], cos_i, sin_i)[:, :, 0]
    rows = min(rows, s)
    if s % rows:
        raise ValueError(f"sequence {s} is no multiple of {rows}")

    @jax.checkpoint
    def block(_, rows_of):
        q_rows, qi_rows, w_rows, first, *given = rows_of
        scores = jnp.einsum("brn,bnrs->brs", w_rows, jax.nn.relu(
            _mm("brnw,bsw->bnrs", qi_rows, ki)), precision=_mellum._HI)
        keep = _keep(scores, first, sz, f, *given)
        logits = jnp.where(keep[:, None], _mm("brnw,bsnw->bnrs", q_rows, k),
                           -jnp.inf)
        attn = jax.nn.softmax(logits, -1)
        target = jax.lax.stop_gradient(jnp.mean(attn, 1))
        log_q = jax.nn.log_softmax(jnp.where(keep, scores, -jnp.inf), -1)
        kl = jnp.sum(jnp.where(keep & (target > 0), target * (
            jnp.log(jnp.where(target > 0, target, 1.0)) - log_q), 0.0))
        out = (_mm("bnrs,bsnw->brnw", attn, v), kl)
        return None, out + ((keep,) if keep_sets else ())

    cut = lambda t: jnp.moveaxis(t.reshape((t.shape[0], s // rows, rows)
                                           + t.shape[2:]), 1, 0)
    xs = (cut(q), cut(qi), cut(w), jnp.arange(0, s, rows))
    if chosen is not None:
        xs += (cut(chosen),)
    _, (o, kl, *keep) = jax.lax.scan(block, None, xs)
    o = jnp.moveaxis(o, 0, 1).reshape(q.shape)
    y = _mm("bsnw,nwhk->bshk", o, p["gqa_/out/out_proj"])
    kl = jnp.sum(kl) / (u.shape[0] * s)
    if keep_sets:
        return y, kl, jnp.moveaxis(keep[0], 0, 1).reshape(u.shape[0], s, s)
    return y, kl


def _experts(p, u, sz: Sizes, fault):
    """The held experts' part (Mellum 2's) and the balance term
    (Solar-Open2's, over softmax scores)."""
    out = _mellum._experts(p, u, sz, fault)
    scores = jax.nn.softmax(_mm("bshk,hke->bse", u, p["routed_moe_/router"]),
                            -1)
    _, picked = jax.lax.top_k(scores, sz.topk)
    load = jnp.zeros((sz.experts,), jnp.float32).at[picked.reshape(-1)].add(
        1.0) / (picked.size // sz.topk)
    return out, sz.balance * sz.experts / sz.topk * jnp.sum(
        load * jnp.mean(scores, (0, 1)))


def forward(params: Params, x_tok, sz: Sizes, fault=SOUND, chosen=None):
    """`(rms_f(x) [B, S, D], the indexer losses' mean over layers, the
    balance terms' sum)`; `chosen`: a kept set `[B, S, S]` a sparse layer,
    in the order of the parts, to use in the indexers' place."""
    params = {k: v.astype(jnp.float32) for k, v in params.items()}
    x = params[_TABLE][x_tok]                                   # [B,S,H,K]
    flat = lambda t: t.reshape(t.shape[:2] + (-1,))
    layers = sum(kind == "gqa" for kind in sz.kinds)
    index_loss, balance = 0.0, 0.0
    sets = iter(chosen or ())
    for (i, c), kind in zip(sz.parts, sz.kinds):
        head = f"gpt/body/@d{i}_{c}/block_/"
        p = {k[len(head):]: v for k, v in params.items() if k.startswith(head)}

        @jax.checkpoint
        def part(x, p, given, kind=kind):
            u = _rms(flat(x), p["rms_norm_/scale"].reshape(-1), sz.eps
                     ).reshape(x.shape)
            if kind == "routed_moe":
                out, extra = _experts(p, u, sz, fault)
                return x + out, 0.0, extra
            out, kl = _attention(p, u, sz, fault, chosen=given)
            return x + out, kl, 0.0

        x, kl, extra = part(x, p, next(sets) if chosen and kind == "gqa"
                            else None)
        index_loss, balance = index_loss + kl / layers, balance + extra
    return (_rms(flat(x), params[_FINAL].reshape(-1), sz.eps), index_loss,
            balance)


def loss_fn(params: Params, x_tok, y_tok, sz: Sizes, fault=SOUND):
    """Mean token loss of `x_tok`, `y_tok` [rows, S] (int), with the
    indexers' loss and the balance terms."""
    u, index_loss, balance = forward(params, x_tok, sz, fault)
    head = params[_HEAD][:, :, 0].astype(jnp.float32).reshape(
        u.shape[-1], -1)
    return (_solar._head_loss(u, head, y_tok, sz.z_loss) / y_tok.size
            + index_loss + balance)


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
        new_p[name], new_s[name] = _mellum._update_leaf(
            name, params[name], grads[name], opt_state[name], lr, sz)
    per_leaf = leaf_norms(grads)
    return new_p, new_s, loss, jnp.sqrt(jnp.sum(jnp.square(per_leaf))), per_leaf


# -- what a run is compared on ------------------------------------------------

LOWER = {
    # case -> what stands in the program's place: the float32 slices held in
    # the nearest precision below, and seven planted faults
    "bf16_slices": {"slice_dtype": "bfloat16"},
    "top_k_less_one": {"last_rank": 0.0},
    "window_for_indexer": {"indexed": 0.0},
    "indexer_not_causal": {"causal_index": 0.0},
    "indexer_not_detached": {"detach": 0.0},
    "no_qk_norm": {"qknorm": 0.0},
    "kv_head_interleaved": {"grouped": 0.0},
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
        out["change_leaf"] = np.asarray(change_since_seed(params, sz, seed))
    out["names"] = sorted(params)
    return out
