"""Plain reference of the reversible mixer language model and its update.

One file for the whole family (`32big_mixer`, `32mixer_group`, ...): the
sizes come from the configuration.  Written from the published description
(ClashLuke/HomebrewNLP-MTF, `src/model`, `src/optimizer`), in float32 with
`highest` matmul precision, importing nothing of the program:

    input   x = table[token] @ W_in                     [B,S,H,K]
    body    (x1, x2) = (x, x); per depth, per block f:  (x1, x2) = (x2, x1 + f(x2))
            f_0 = group-norm -> relu(W1) -> relu(W2) -> group-norm -> W3
            f_1 = group-norm -> causal map1 -> group-norm -> gelu -> causal map2
            (map1, map2 are shared by every depth)
    output  logits = (x1 + x2) @ W_out;  loss = CE + z_loss * mean(log_z^2)
    update  adaptive_clip:0.003 -> sm3 -> momentum:0.9 (nesterov) -> lr,
            decay lr*wd*w on the three bottleneck matrices, linear warm-up

The coupling is written forward only; `jax.grad` differentiates it.  Blocks
run under `lax.scan` with `jax.checkpoint` (same mathematics, bounded
memory), and the batch is taken in equal blocks of rows whose gradients add.

Departures from the description, each on purpose:
- weights are Gaussian with the orthogonal initialiser's element variance,
  not orthogonalised (`init_weights`): a QR of every matrix is set-up time
  that serves no request, and speed and agreement need only the scale;
- the state the configuration stores in a narrower type (momentum and SM3
  rows in `optimizer_slice_dtype`) is rounded to that type between steps,
  because that rounding is part of what the configuration states.

The `lower` argument turns this file into the control: the same mathematics
in the nearest precision below the stated one.
"""
from __future__ import annotations

import functools
import time
import typing

import jax
import jax.numpy as jnp
import numpy as np

Params = typing.Dict[str, jnp.ndarray]

_BODY0 = ("norm_/scale", "norm_/shift",
          "bottleneck_group_linear_/orthogonal_var/orthogonal_var",
          "bottleneck_group_linear_/orthogonal_var1/orthogonal_var",
          "bottleneck_group_linear_/scale", "bottleneck_group_linear_/shift",
          "bottleneck_group_linear_/orthogonal_var2/orthogonal_var")
_BODY1 = ("norm_/scale", "norm_/shift", "norm_1/scale", "norm_1/shift")
_MAP1 = "gpt/body/shared_1/block_/attention_/embed/embed_var"
_MAP2 = "gpt/body/shared_1/block_/attention_1/embed/embed_var"
_TABLE = "gpt/input/gather/embed/embed_var"
_W_IN = "gpt/input/orthogonal_var/orthogonal_var"
_W_OUT = "gpt/output/embed/embed_orth"


class Sizes(typing.NamedTuple):
    """What the reference needs of a configuration file's `model` group."""
    depth: int
    heads: int
    features_per_head: int
    sequence_length: int
    vocab_size: int
    group_linear_factor: int
    intermediate: int          # heads*features_per_head*multiplier
    embed: int                 # intermediate * vocab_weight_factorization
    embedding_stddev: float
    z_loss: float
    learning_rate: float
    warmup_steps: int
    weight_decay: float
    clip: float
    momentum: float
    optimizer_slice_dtype: str
    slice_dtype: str

    @classmethod
    def from_config(cls, raw: dict) -> "Sizes":
        h, k = raw["heads"], raw["features_per_head"]
        inter = int(h * k * raw["group_linear_factor"]
                    * raw["intermediate_feed_forward_multiplier_multiplier"]
                    / h)
        chain = raw["optimizer"].split("-")
        if [c.split(":")[0] for c in chain] != [
                "adaptive_clip", "sm3", "momentum", "learning_rate"]:
            raise ValueError(f"reference knows no optimizer {raw['optimizer']}")
        mom = chain[2].split(":")
        if mom[2:] != ["1", "1"]:
            raise ValueError("reference writes nesterov momentum only")
        return cls(
            depth=raw["depth"], heads=h, features_per_head=k,
            sequence_length=raw["sequence_length"],
            vocab_size=raw["vocab_size"],
            group_linear_factor=raw["group_linear_factor"],
            intermediate=inter,
            embed=int(inter * raw.get("vocab_weight_factorization", 0.125)),
            embedding_stddev=raw["embedding_stddev"],
            z_loss=raw.get("z_loss", 1e-4),
            learning_rate=raw["learning_rate"],
            warmup_steps=raw["learning_rate_config"]["linear_warmup"]
            ["final_step"],
            weight_decay=raw["weight_decay"],
            clip=float(chain[0].split(":")[1]), momentum=float(mom[1]),
            optimizer_slice_dtype=raw["optimizer_slice_dtype"],
            slice_dtype=raw["slice_dtype"])


def _block_key(i: int, c: int, leaf: str) -> str:
    return f"gpt/body/@d{i}_{c}/block_/{leaf}"


def shapes(sz: Sizes) -> typing.Dict[str, typing.Tuple[int, ...]]:
    """Every parameter by the program's checkpoint name, with its shape."""
    h, k, i, m = (sz.heads, sz.features_per_head, sz.intermediate,
                  sz.features_per_head * sz.group_linear_factor)
    s = sz.sequence_length
    body0 = ((h, k), (h, k), (h, k, i), (i, h, m), (h, m), (h, m), (h, m, k))
    out = {}
    for d in range(sz.depth):
        out.update({_block_key(d, 0, n): sh for n, sh in zip(_BODY0, body0)})
        out.update({_block_key(d, 1, n): (h, k) for n in _BODY1})
    out[_MAP1] = out[_MAP2] = (h, s, s)
    out[_TABLE] = (sz.vocab_size, sz.embed)
    out[_W_IN] = (1, sz.embed, h, k)
    out[_W_OUT] = (h, k, 1, sz.vocab_size)
    return out


def _init_rule(name: str, shape, sz: Sizes) -> typing.Tuple[float, float]:
    """(mean, stddev) of one leaf: scales 1 +- 0.02, shifts 0 +- 0.02,
    learned tables at `embedding_stddev`, matrices at the orthogonal
    initialiser's element variance 1/max(fan_in, fan_out), the last matrix
    of a block and the output head scaled by depth**-0.5."""
    leaf = name.rsplit("/", 1)[-1]
    if leaf == "scale":
        return 1.0, 0.02
    if leaf == "shift":
        return 0.0, 0.02
    if leaf == "embed_var":
        return 0.0, sz.embedding_stddev
    h, k = sz.heads, sz.features_per_head
    m = k * sz.group_linear_factor
    fans = {"orthogonal_var/orthogonal_var": (h * k, sz.intermediate),
            "orthogonal_var1/orthogonal_var": (sz.intermediate, h * m),
            "orthogonal_var2/orthogonal_var": (h * m, h * k),
            _W_IN: (sz.embed, h * k), _W_OUT: (h * k, sz.vocab_size)}
    for suffix, (fan_in, fan_out) in fans.items():
        if name.endswith(suffix):
            last = suffix.startswith("orthogonal_var2") or suffix == _W_OUT
            scale = sz.depth ** -0.5 if last else 1.0
            return 0.0, scale * max(fan_in, fan_out) ** -0.5
    raise KeyError(f"reference knows no parameter {name}")


def seed_key(seed: int, stream: int) -> jax.Array:
    """A PRNG key from any whole number (seeds pass 2**31)."""
    words = np.random.SeedSequence([int(seed), stream]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def _draw_weights(sz: Sizes):
    """key -> all weights in `slice_dtype`.  Leaves of one shape and rule are
    drawn together and cut apart, so the program holds a dozen random draws
    and not one per leaf."""
    shp = shapes(sz)
    groups: typing.Dict[tuple, typing.List[str]] = {}
    for name in sorted(shp):
        groups.setdefault((shp[name],) + _init_rule(name, shp[name], sz),
                          []).append(name)

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

def _round(x, dtype):
    """`x` as `dtype` would hold it, with the gradient passed straight
    through.  Eight-bit floats get one scale for the whole tensor (its
    largest magnitude onto the type's largest), as a quantised matmul would
    give them."""
    if dtype is None:
        return x
    scale = 1.0
    if jnp.dtype(dtype).itemsize == 1:
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / float(
            jnp.finfo(dtype).max)
    held = (x / scale).astype(dtype).astype(x.dtype) * scale
    return x + jax.lax.stop_gradient(held - x)


def _mm(spec: str, a, b, operand_dtype):
    return jnp.einsum(spec, _round(a, operand_dtype), _round(b, operand_dtype),
                      precision=jax.lax.Precision.HIGHEST)


def _group_norm(x, scale, shift):
    """Per head, over the last axis; eps 1e-5."""
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + 1e-5) * scale + shift


def _f0(p, x, od):
    sc0, sh0, w1, w2, sc1, sh1, w3 = p
    n = _group_norm(x, sc0, sh0)
    a = jax.nn.relu(_mm("bshk,hki->bsi", n, w1, od))
    b = jax.nn.relu(_mm("bsi,ihm->bshm", a, w2, od))
    return _mm("bshm,hmk->bshk", _group_norm(b, sc1, sh1), w3, od)


def _f1(p, maps, x, od):
    sc0, sh0, sc1, sh1 = p
    map1, map2 = maps
    a = _mm("hst,bthk->bshk", map1, _group_norm(x, sc0, sh0), od)
    g = jax.nn.gelu(_group_norm(a, sc1, sh1))
    return _mm("hst,bthk->bshk", map2, g, od)


def _stack(params: Params, depth: int, c: int, leaves) -> tuple:
    return tuple(jnp.stack([params[_block_key(d, c, n)] for d in range(depth)])
                 for n in leaves)


def loss_fn(params: Params, x_tok, y_tok, sz: Sizes, operand_dtype=None):
    """Mean token loss of `x_tok`, `y_tok` [rows, S] (int)."""
    od = operand_dtype
    params = {k: v.astype(jnp.float32) for k, v in params.items()}
    tril = jnp.tril(jnp.ones((sz.sequence_length,) * 2, jnp.float32))
    maps = (params[_MAP1] * tril, params[_MAP2] * tril)
    emb = params[_TABLE][x_tok]                                   # [B,S,E]
    src = _mm("bse,ehk->bshk", emb, params[_W_IN][0], od)

    @jax.checkpoint
    def depth_step(carry, p):
        x1, x2 = carry
        p0, p1 = p
        x1, x2 = x2, x1 + _f0(p0, x2, od)
        x1, x2 = x2, x1 + _f1(p1, maps, x2, od)
        return (x1, x2), None

    stacked = (_stack(params, sz.depth, 0, _BODY0),
               _stack(params, sz.depth, 1, _BODY1))
    (x1, x2), _ = jax.lax.scan(depth_step, (src, src), stacked)
    logits = _mm("bshk,hkv->bsv", x1 + x2, params[_W_OUT][:, :, 0], od)
    log_z = jax.nn.logsumexp(logits, -1)
    picked = jnp.take_along_axis(logits, y_tok[..., None], -1)[..., 0]
    return jnp.mean(log_z - picked) + sz.z_loss * jnp.mean(jnp.square(log_z))


def loss_and_grads(params: Params, x_tok, y_tok, sz: Sizes, rows: int,
                   operand_dtype=None):
    """Loss and float32 gradients of one batch, `rows` rows at a time."""
    n = x_tok.shape[0]
    if n % rows:
        raise ValueError(f"batch {n} is no multiple of the block {rows}")
    xs = x_tok.reshape(n // rows, rows, -1)
    ys = y_tok.reshape(n // rows, rows, -1)
    vg = jax.value_and_grad(
        lambda p, x, y: loss_fn(p, x, y, sz, operand_dtype))

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


def _decayed(name: str) -> bool:
    return "bottleneck_group_linear_/orthogonal_var" in name


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
    if _decayed(name):
        step = step + w32 * (lr * sz.weight_decay)
    dt = sz.optimizer_slice_dtype
    return ((w32 - step).astype(w.dtype),
            {"rows": [r.astype(dt) for r in new_rows],
             "momentum": mom.astype(dt)})


def learning_rate(step, sz: Sizes):
    stepf = jnp.asarray(step, jnp.float32)
    warm = stepf / max(sz.warmup_steps, 1)
    return sz.learning_rate * jnp.where(stepf < sz.warmup_steps, warm, 1.0)


def train_step(params: Params, opt_state: dict, step, x_tok, y_tok, sz: Sizes,
               rows: int, operand_dtype=None):
    """One update.  Returns (params, opt_state, loss, gradient norm,
    per-leaf gradient norms in the order of the sorted names)."""
    loss, grads = loss_and_grads(params, x_tok, y_tok, sz, rows,
                                 operand_dtype)
    lr = learning_rate(step, sz)
    new_p, new_s = {}, {}
    for name in params:
        new_p[name], new_s[name] = _update_leaf(
            name, params[name], grads[name], opt_state[name], lr, sz)
    per_leaf = leaf_norms(grads)
    return new_p, new_s, loss, jnp.sqrt(jnp.sum(jnp.square(per_leaf))), per_leaf


# -- what a run is compared on ------------------------------------------------

LOWER = {
    # control name -> (matmul operand type, slice type): the nearest
    # precision below bfloat16 calculation, and below float32 slices
    "fp8_operands": ("float8_e4m3fn", None),
    "bf16_slices": (None, "bfloat16"),
}


def follow(sz: Sizes, seed: int, batches, n_steps: int, rows: int,
           lower: typing.Optional[str] = None, half_batch: bool = False
           ) -> dict:
    """Drive `n_steps` updates from the seed's weights over `batches`
    (a list of (x, y) int arrays, used in turn) and return what
    `compare.readings` wants: per-step loss and gradient norm, per-leaf SM3
    row mass after step 1, per-leaf gradient norm of step 1 and per-leaf
    norm of the parameters' change after the last step.

    `lower` names a control of `LOWER`; `half_batch` plants the fault of a
    step that leaves half of its rows out and takes the mean over the rest.
    """
    operand, slices = LOWER[lower] if lower else (None, None)
    if slices:
        sz = sz._replace(slice_dtype=slices)
    p0 = init_weights(sz, seed)
    step_fn = jax.jit(functools.partial(
        train_step, sz=sz, rows=rows, operand_dtype=operand),
        donate_argnums=(0, 1))
    params = jax.tree_util.tree_map(jnp.copy, p0)
    state = init_opt_state(params, sz)
    out = {"loss": [], "grad_norm": [], "seconds": []}
    for i in range(n_steps):
        t0 = time.perf_counter()
        x, y = batches[i % len(batches)]
        if half_batch:
            x, y = x[:x.shape[0] // 2], y[:y.shape[0] // 2]
        params, state, loss, gnorm, per_leaf = step_fn(params, state, i, x, y)
        out["loss"].append(float(loss))
        out["grad_norm"].append(float(gnorm))
        out["seconds"].append(time.perf_counter() - t0)
        if i == 0:
            out["grad_leaf"] = np.asarray(per_leaf)
            out["sm3_leaf"] = np.asarray(sm3_mass(
                {k: v["rows"] for k, v in state.items()}))
    out["change_leaf"] = np.asarray(change_norms(params, p0))
    out["names"] = sorted(p0)
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
