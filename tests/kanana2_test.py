"""Kanana-2's block parts (models/hybrid.py::mla spelt `mla-rope`,
ops/rotary.py's interleaved pairs, the routed experts at a share of an
eighth with two shared ones) against the plain reference
(benchmark/reference/kanana2.py), at toy sizes on the CPU in float32; and
ops/pallas_mla.py's key-block kernels, interpreted, against the unrolled
tiles they replace past the resident kernels' VMEM.
"""
import contextlib
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from homebrewnlp_tpu.config import Config
from homebrewnlp_tpu.models.ctx import Args, Ctx
from homebrewnlp_tpu.models.registry import LAYER_FUNCTIONS
from homebrewnlp_tpu.nd import NT
from homebrewnlp_tpu.ops import block_attention, pallas_mla, rotary

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
MOE = "routed_moe-sigmoid-bias-topk6-gated-shared2-in:silu"
MLA = "mla-rope"


def toy(**over):
    """The benchmark's configuration at a toy width: the dense layer and an
    expert layer, each behind latent attention with rotated decoupled keys,
    8 attention heads over a stream of 4 x 8, 64 tokens."""
    raw = dict(
        model_mode="gpt", sequence_length=64, heads=4, features_per_head=8,
        vocab_size=128, depth=2, train_batch_size=2, calc_accuracy=False,
        memory_reduction_strategy="checkpoint", weight_decay=0.0001,
        optimizer="adaptive_clip:0.003-sm3-momentum:0.9:1:1-learning_rate",
        learning_rate=0.01, z_loss=1e-4, embedding_stddev=0.02,
        intermediate_feed_forward_multiplier=3.0, factorized_embedding=False,
        scale_by_depth=False, weight_centralisation=False,
        weight_standardisation=False, experts=32, experts_held=4,
        expert_offset=0, moe_intermediate_size=16, moe_balance_weight=1.0,
        routed_scaling_factor=2.448, rms_norm_eps=1e-6,
        num_attention_heads=8, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=8, rope_theta=100.0,
        rope_interleave=True, rope_scaling=None, tpu_size=1,
        calculation_dtype="float32", slice_dtype="float32",
        storage_dtype="float32", optimizer_slice_dtype="bfloat16",
        block_config=[
            {"layer": ["rms_norm-scale", MLA], "skip": True},
            {"layer": ["rms_norm-scale", "gated_feed_forward-in:silu"],
             "skip": True},
            {"layer": ["rms_norm-scale", MOE], "skip": True}],
        block_schedule=[[0, 1], [0, 2]],
        output_block_config=[{"layer": ["rms_norm-scale"]}],
        learning_rate_config={"linear_warmup": {"final_step": 64}})
    raw.update(over)
    return raw


NAMES = ("batch", "sequence", "heads", "features_per_head")
# float32 on both sides, the same products in another order: what is left
# is the order of float32 sums
LAYER = dict(rtol=1e-5, atol=1e-5)
# the toy cell's limits: the benchmark's comparison, at the toy width
TOY_LIMITS = {"loss3": 1e-5, "sm3_median": 4e-3, "change_median": 1e-3,
              "change_leaf": 4e-3}


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load(os.path.join(BENCH, "reference", "kanana2.py"), "kanana2_ref")
compare = _load(os.path.join(BENCH, "compare.py"), "compare")
flops = _load(os.path.join(BENCH, "flops_kanana2.py"), "flops_kanana2")
SOUND = {k: jnp.float32(v) for k, v in ref.SOUND.items()}


def run_layer(cfg, spec, params, x):
    """One layer of the DSL on `x`, its parameters keyed as under its own
    scope.  Returns (output array, ctx)."""
    name, *extras = spec.split("-")
    ctx = Ctx(cfg, params=params, train=True)
    out = ctx.scoped(name + "_", LAYER_FUNCTIONS[name],
                     Args(ctx, NT(x, NAMES), extras))
    return out.transpose_to(NAMES).x, ctx


def part_params(sz, kind, seed, prefix):
    """Seeded weights of one block part, by the reference's rules."""
    rng = np.random.default_rng(seed)
    return {k: jnp.asarray(rng.normal(mean, std, shape), jnp.float32)
            for k, (shape, (mean, std)) in ref._part_leaves(sz, kind).items()
            if k.startswith(prefix)}


def stream(seed):
    return jnp.asarray(np.random.default_rng(seed).normal(size=(2, 64, 4, 8)),
                       jnp.float32)


# -- (a) the whole update -----------------------------------------------------

@pytest.fixture(scope="module")
def followed():
    """Three updates of the toy model through `Trainer.step`, driven and read
    as the benchmark's runner does, and the reference's three (over the
    whole batch at once: the balance term is not a mean over rows)."""
    sys.path[:0] = [BENCH]
    runner = _load(os.path.join(BENCH, "runners", "train_step.py"),
                   "train_step")
    with open(os.path.join(BENCH, "traffic", "train.json")) as f:
        traffic = json.load(f)

    class Spans:
        @contextlib.contextmanager
        def span(self, name):
            yield

    config = dict(toy(), benchmark={"followed_steps": 3, "reference_rows": 2})
    program = runner.Program(config, traffic, 2 ** 31 + 4243, ref, Spans(),
                             lambda m: None)
    got = program.follow_and_warm_up()
    want = ref.follow(program.sizes, program.seed, program.host_batches, 3, 2)
    return got, want, program


def test_three_updates_match_the_reference(followed):
    got, want, _ = followed
    read = compare.readings(got, want)
    assert max(read["loss1"], read["loss2"], read["loss3"]) < 1e-5, read
    assert read["grad_norm1"] < 4e-3 and read["sm3_leaf"] < 4e-3, read
    assert read["change_leaf"] < 4e-3 and read["change_median"] < 1e-3, read
    # every leaf moved but the selection biases, which take no gradient
    moved = {n: g > 0 for n, g in zip(want["names"], want["grad_leaf"])}
    assert {n for n, m in moved.items() if not m} == {
        n for n in moved if n.endswith("router_bias")}


def test_the_runner_sees_the_parameters_the_reference_names(followed):
    _, want, program = followed
    assert sorted(ref.shapes(program.sizes)) == want["names"]
    # table, head, final norm; 2 attention parts of 6, 1 dense part of 4,
    # 1 expert part of 9
    assert len(want["names"]) == 3 + 2 * 6 + 4 + 9


@pytest.mark.parametrize("case", sorted(ref.LOWER))
def test_every_planted_fault_is_caught_by_the_toy_limits(followed, case):
    """Each case of `LOWER` in the program's place fails the toy cell's
    limits, which the program passes."""
    got, want, program = followed
    assert compare.correct(compare.against(compare.readings(got, want),
                                           TOY_LIMITS))
    fault = ref.follow(program.sizes, program.seed, program.host_batches, 3,
                       2, lower=case)
    assert not compare.correct(compare.against(
        compare.readings(fault, want), TOY_LIMITS)), case


# -- (b) the attention layer alone --------------------------------------------

def attention_case(seed, **over):
    """(cfg, sz, params, u) of the attention part at the toy width; `over`
    changes both configurations."""
    sz = ref.Sizes.from_config(toy(**over))
    return (Config(toy(**over)), sz, part_params(sz, "mla", seed, "mla_/"),
            stream(seed + 100))


@pytest.mark.parametrize("interleave", [True, False],
                         ids=["interleaved", "rotate_half"])
def test_mla_rope_forward_and_gradients_match_the_reference(interleave):
    """The layer's output and the gradients of every weight and of its input
    against the reference's; float32 on both sides (`LAYER`), the gradients
    through a softmax over 64 keys with a bound ten times looser, which a
    bfloat16 rounding of q or k (2**-9) would still exceed."""
    cfg, sz, params, u = attention_case(1, rope_interleave=interleave)
    cot = stream(2)
    with jax.default_matmul_precision("highest"):
        got, _ = run_layer(cfg, MLA, params, u)
        want = ref._mla(params, u, sz, SOUND, rows=16, group=4)
        np.testing.assert_allclose(got, want, **LAYER)
        grads = jax.grad(lambda p, x: jnp.sum(run_layer(cfg, MLA, p, x)[0]
                                              * cot), (0, 1))(params, u)
        wants = jax.grad(lambda p, x: jnp.sum(ref._mla(
            p, x, sz, SOUND, rows=16, group=4) * cot), (0, 1))(params, u)
    for name in params:
        np.testing.assert_allclose(grads[0][name], wants[0][name], rtol=1e-4,
                                   atol=1e-5, err_msg=name)
    np.testing.assert_allclose(grads[1], wants[1], rtol=1e-4, atol=1e-5)


def test_the_rotation_moves_the_output():
    """The reference without its rotation stands well outside `LAYER`: the
    comparison above sees the positions."""
    cfg, sz, params, u = attention_case(3)
    got, _ = run_layer(cfg, MLA, params, u)
    with jax.default_matmul_precision("highest"):
        unturned = ref._mla(params, u, sz, dict(SOUND, rotate=0.0), rows=16)
    assert float(jnp.max(jnp.abs(got - unturned))) > 1e-2


@pytest.mark.parametrize("positions", [1, 37])
def test_interleaved_rotation_turns_each_pair_as_a_loop_does(positions):
    """`rotary.rotate(..., interleaved=True)` against a loop over the pairs
    (x_2i, x_2i+1) of one head at every position: the turned pairs, laid out
    rotate-half wise (the evens' results, then the odds'); float32 on both
    sides, so only the order of the products differs."""
    d, theta = 8, 1e6
    x = np.random.default_rng(positions).normal(size=(1, positions, 2, d))
    cos, sin = rotary.table({"rope_theta": theta}, d, positions)
    got = np.asarray(rotary.rotate(jnp.asarray(x, jnp.float32), cos, sin,
                                   interleaved=True))
    want = np.empty_like(x)
    for p in range(positions):
        for i in range(d // 2):
            angle = p * theta ** (-2 * i / d)
            a, b = x[0, p, :, 2 * i], x[0, p, :, 2 * i + 1]
            want[0, p, :, i] = a * np.cos(angle) - b * np.sin(angle)
            want[0, p, :, d // 2 + i] = b * np.cos(angle) + a * np.sin(angle)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def _published_attention(u, wq, wkva, norm, wkvb, wo, sz):
    """Upstream's DeepSeek-V3 attention in numpy float64 on the published
    `nn.Linear` layouts (`[out, in]`): `q_proj` [H (nope + rope), D],
    `kv_a_proj_with_mqa` [latent + rope, D], `kv_b_proj` [H (nope + v),
    latent], `o_proj` [D, H v]; `apply_rotary_pos_emb_interleave` turns q_pe
    and k_pe."""
    s, h = u.shape[0], sz.q_heads
    nope, rope, v_dim, latent = sz.nope, sz.rope, sz.v_dim, sz.latent
    q = (u @ wq.T).reshape(s, h, nope + rope)
    c = u @ wkva.T
    c_kv = c[:, :latent]
    c_kv = c_kv / np.sqrt(np.mean(c_kv ** 2, -1, keepdims=True) + sz.eps
                          ) * norm
    kv = (c_kv @ wkvb.T).reshape(s, h, nope + v_dim)
    freq = sz.rope_theta ** (-np.arange(0, rope, 2) / rope)
    angle = np.arange(s)[:, None] * freq[None]
    cos = np.concatenate([np.cos(angle)] * 2, -1)[:, None]
    sin = np.concatenate([np.sin(angle)] * 2, -1)[:, None]

    def turn(x):
        x = x.reshape(x.shape[:-1] + (rope // 2, 2))
        x = np.swapaxes(x, -1, -2).reshape(x.shape[:-2] + (rope,))
        half = np.concatenate([-x[..., rope // 2:], x[..., :rope // 2]], -1)
        return x * cos + half * sin

    q_pe, k_pe = turn(q[..., nope:]), turn(c[:, None, latent:])
    qs = np.concatenate([q[..., :nope], q_pe], -1)
    ks = np.concatenate([kv[..., :nope], np.broadcast_to(
        k_pe, (s, h, rope))], -1)
    scores = np.einsum("shd,thd->hst", qs, ks) / np.sqrt(nope + rope)
    scores = np.where(np.tril(np.ones((s, s), bool)), scores, -np.inf)
    weights = np.exp(scores - scores.max(-1, keepdims=True))
    weights /= weights.sum(-1, keepdims=True)
    o = np.einsum("hst,thd->shd", weights, kv[..., nope:])
    return o.reshape(s, -1) @ wo.T


def test_the_published_layout_loads_as_it_stands():
    """Weights in the published checkpoint's layouts become the program's
    leaves by reshapes and transposes alone, no column moved, and the layer
    then gives upstream's attention (float64 here, float32 in the program:
    `LAYER`)."""
    cfg, sz, _, _ = attention_case(0)
    rng = np.random.default_rng(4)
    d, h = sz.hidden, sz.q_heads
    wq = rng.normal(0, d ** -0.5, (h * (sz.nope + sz.rope), d))
    wkva = rng.normal(0, d ** -0.5, (sz.latent + sz.rope, d))
    norm = rng.normal(1.0, 0.02, sz.latent)
    wkvb = rng.normal(0, sz.latent ** -0.5, (h * (sz.nope + sz.v_dim),
                                             sz.latent))
    wo = rng.normal(0, (h * sz.v_dim) ** -0.5, (d, h * sz.v_dim))
    hk = (sz.heads, sz.features_per_head)
    params = {k: jnp.asarray(v, jnp.float32) for k, v in {
        "mla_/q_proj": wq.T.reshape(hk + (h, sz.nope + sz.rope)),
        "mla_/kv_down": wkva.T.reshape(hk + (sz.latent + sz.rope,)),
        "mla_/latent_norm": norm,
        "mla_/kv_up": wkvb.T.reshape(sz.latent, h, sz.nope + sz.v_dim),
        "mla_/out_proj": wo.T.reshape((h, sz.v_dim) + hk)}.items()}
    u = np.asarray(stream(5), np.float64)
    with jax.default_matmul_precision("highest"):
        got, _ = run_layer(cfg, MLA, params, jnp.asarray(u, jnp.float32))
    for row in range(u.shape[0]):
        want = _published_attention(u[row].reshape(u.shape[1], -1),
                                     wq, wkva, norm, wkvb, wo, sz)
        np.testing.assert_allclose(np.asarray(got[row]).reshape(want.shape),
                                   want, **LAYER)


@pytest.mark.parametrize("spelling,over,match", [
    ("mla", {}, "mla_use_nope"),
    ("mla-rope", {"mla_use_nope": True}, "mla_use_nope"),
    ("mla-rope", {"rope_scaling": {"rope_type": "yarn", "factor": 4.0}},
     "rope_scaling")])
def test_the_spelling_and_the_keys_must_agree(spelling, over, match):
    _, _, params, u = attention_case(0)
    with pytest.raises(ValueError, match=match):
        run_layer(Config(toy(**over)), spelling, params, u)


def test_the_layer_has_its_sub_scopes():
    """`proj`, `rotary`, `attention` and `out` name the trace's operations,
    and the attention's scope holds the softmax alone."""
    cfg, _, params, u = attention_case(0)
    text = jax.jit(lambda p, x: run_layer(cfg, MLA, p, x)[0]).lower(
        params, u).as_text(debug_info=True)
    for scope in ("proj", "rotary", "attention", "out"):
        assert f"mla_/{scope}/" in text, scope
    rotary_ops = [line for line in text.splitlines()
                  if "mla_/attention/" in line and ("cosine" in line
                                                    or "sine" in line)]
    assert not rotary_ops, rotary_ops[:2]


# -- (c) the expert layer -----------------------------------------------------

def test_the_expert_shares_add_up_to_the_uncut_layer():
    """Eight chips of 4 experts each: their parts, with the shared experts
    (which every chip computes alike) counted once, add up to the layer that
    holds all 32."""
    raw = toy(experts_held=32)
    sz = ref.Sizes.from_config(raw)
    params = part_params(sz, "routed_moe", 11, "routed_moe_/")
    u = stream(12)
    whole, _ = run_layer(Config(raw), MOE, params, u)
    shared, _ = run_layer(Config(toy()), "gated_feed_forward-in:silu", {
        f"gated_feed_forward_/orthogonal_var{i}/orthogonal_var":
            params[f"routed_moe_/shared/orthogonal_var{i}/orthogonal_var"]
        for i in ("", 1, 2)}, u)
    total = -7 * shared
    for share in range(8):
        mine = dict(params)
        for i in ("", 1, 2):
            key = f"routed_moe_/orthogonal_var{i}/orthogonal_var"
            mine[key] = params[key][4 * share:4 * share + 4]
        part, _ = run_layer(Config(toy(experts_held=4,
                                       expert_offset=4 * share)),
                            MOE, mine, u)
        total = total + part
    np.testing.assert_allclose(total, whole, **LAYER)


# -- (d) the key-block kernels ------------------------------------------------

def _attention_inputs(b, h, g, s, d, d_v, kind, seed=0):
    keys = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(keys[0], (b, h, s, d), jnp.float32) * 0.5
    k = jax.random.normal(keys[1], (b, g, s, d), jnp.float32) * 0.5
    v = jax.random.normal(keys[2], (b, g, s, d_v), jnp.float32)
    do = jax.random.normal(keys[3], (b, h, s, d_v), jnp.float32)
    return tuple(x.astype(kind) for x in (q, k, v)), do


KEY_BLOCK_CASES = [
    # (query heads, K/V heads, key tiles a cell, window)
    (2, 2, 1, None),                  # causal, a cell a tile
    (2, 2, 2, None),                  # causal, two tiles a cell
    (2, 2, 4, None),                  # causal, the whole sequence a cell
    (4, 2, 2, None),                  # grouped K/V heads
    (2, 2, 2, 300),                   # a window across cells
    (4, 1, 1, 128),                   # grouped, a window of one tile
]


@pytest.mark.parametrize("h,g,tiles,window", KEY_BLOCK_CASES)
def test_key_block_kernels_match_the_unrolled_tiles(h, g, tiles, window):
    """The output and `dq`, `dk`, `dv` of the key-block kernels (interpreted)
    against `_unrolled_tiles` at 4 tiles of 128 a sequence.  float32 inputs:
    both sum in float32 in another order, so they agree to 2e-5 of the
    largest entry; float32 sums rounded through bfloat16 (2**-9) anywhere
    would stand a hundred times further off."""
    block, s = 128, 512
    (q, k, v), do = _attention_inputs(1, h, g, s, 64, 64, jnp.float32)
    mine = jax.vjp(lambda *a: pallas_mla.key_block_attention(
        *a, block, tiles * block, window, True), q, k, v)
    tiles_ = jax.vjp(lambda *a: block_attention._unrolled_tiles(
        *a, block, window), q, k, v)
    for got, want in zip((mine[0],) + mine[1](do),
                         (tiles_[0],) + tiles_[1](do)):
        scale = float(jnp.max(jnp.abs(want)))
        np.testing.assert_allclose(got, want, rtol=0, atol=2e-5 * scale)


def test_key_block_kernels_round_no_lower_than_the_tiles():
    """bfloat16 operands at the latent attention's widths (192 and 128):
    the key-block kernels stand no further from the float32 result than the
    unrolled tiles do (their rounding points are the same, and sums stay
    float32 across cells)."""
    block, s = 128, 512
    (q, k, v), do = _attention_inputs(1, 2, 2, s, 192, 128, jnp.bfloat16, 3)
    exact = jax.vjp(lambda *a: block_attention._unrolled_tiles(
        *a, block), *(x.astype(jnp.float32) for x in (q, k, v)))
    want = (exact[0],) + exact[1](do)

    def off(f):
        out, vjp = jax.vjp(f, q, k, v)
        got = (out,) + vjp(do.astype(jnp.bfloat16))
        return [float(jnp.sqrt(jnp.mean((x.astype(jnp.float32) - w) ** 2)))
                for x, w in zip(got, want)]

    kernels = off(lambda *a: pallas_mla.key_block_attention(
        *a, block, 2 * block, None, True))
    tiles = off(lambda *a: block_attention._unrolled_tiles(*a, block))
    for mine, theirs in zip(kernels, tiles):
        assert mine <= 1.1 * theirs, (kernels, tiles)


@pytest.mark.parametrize("s,d,d_v,path", [
    (8192, 192, 128, "resident"),       # the Kimi cell's shape
    (10752, 192, 128, "resident"),      # the longest that fits VMEM whole
    (11264, 192, 128, "key_blocks"),
    (32768, 192, 128, "key_blocks"),    # this configuration's cell
    (32768 + 8, 192, 128, "unrolled"),
    (512, 12, 8, "unrolled")])
def test_the_shape_alone_chooses_the_walk(s, d, d_v, path):
    q = jax.ShapeDtypeStruct((1, s, 32, d), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, s, 32, d_v), jnp.bfloat16)
    assert block_attention.walk(q, v) == path


def test_each_call_is_counted_by_its_walk():
    """`hbnlp_attention_path_total` counts the calls as they are traced:
    `causal_attention` emits `WALK_EVENT` and the installed compile log
    counts it."""
    from homebrewnlp_tpu.obs import compile_log
    from homebrewnlp_tpu.obs.registry import REGISTRY
    compile_log.install()
    counter = REGISTRY.counter("hbnlp_attention_path_total",
                               labelnames=("path",))

    def count(path):
        return counter.value(path=path)

    before = {p: count(p) for p in block_attention.WALKS}
    shapes = ((1, 32768, 32, 192), (1, 32768, 32, 128))
    jax.eval_shape(lambda q, v: block_attention.causal_attention(q, q, v),
                   *(jax.ShapeDtypeStruct(x, jnp.bfloat16) for x in shapes))
    jax.eval_shape(lambda q: block_attention.causal_attention(q, q, q),
                   jax.ShapeDtypeStruct((1, 48, 2, 8), jnp.float32))
    assert {p: count(p) - before[p] for p in block_attention.WALKS} == {
        "resident": 0, "key_blocks": 1, "unrolled": 1}


def test_a_cell_of_keys_divides_the_sequence():
    assert pallas_mla.key_chunk(32768) == pallas_mla.KEYS
    assert pallas_mla.key_chunk(11264) == 1024        # 22 tiles: 2 a cell
    assert pallas_mla.key_chunk(12288) == 4096


# -- (e) the configuration and its yardstick ----------------------------------

def _cell_config() -> dict:
    with open(os.path.join(BENCH, "configs", "kanana2_30b.json")) as f:
        return json.load(f)


def test_the_cell_holds_the_parameters_the_deployment_gives_a_chip():
    """575.96 M parameters: 64.1 M in layer 0, 111.55 M in each expert
    layer, 65.7 M in the table and head."""
    raw = _cell_config()
    sz = ref.Sizes.from_config({k: v for k, v in raw.items()
                                if k != "benchmark"})
    sizes = {k: int(np.prod(v)) for k, v in ref.shapes(sz).items()}
    layer = lambda i: sum(n for k, n in sizes.items() if f"/@d{i}_" in k)
    assert layer(0) == pytest.approx(64.1e6, rel=1e-3)
    assert all(layer(i) == pytest.approx(111.55e6, rel=1e-4)
               for i in range(1, 5))
    assert sum(sizes.values()) == pytest.approx(575.96e6, rel=1e-5)
    assert raw["vocab_size"] * 8 == raw["benchmark"]["published"][
        "vocab_size"]


def test_the_flops_count_every_product_at_the_cell():
    """The yardstick of `step_mfu.kanana2` and `mla_attention_roofline` by
    hand at the cell's sizes: 20,480 flops a key a layer for the attention
    proper over the triangle, twice that backward; q, the latent and its
    key, its expansion, the output; the dense layer; the router, the shared
    experts and the held ones at their load; the head."""
    model = _cell_config()
    d, s, h = 2048, 32768, 32
    pairs = s * (s + 1) // 2
    work = flops.attention(model)
    assert work["forward"]["flops"] == 20480 * pairs
    assert work["backward"]["flops"] == 2 * 20480 * pairs
    assert len(flops.attention_passes(model)) == 2 * 5
    mla = (d * h * 192 + d * 576 + 512 * h * 256 + h * 128 * d
           + h * (s + 1) / 2 * 320)
    moe = d * 128 + 2 * 3 * d * 768 + 6 * 16 / 128 * 3 * d * 768
    forward = 5 * mla + 3 * d * 6144 + 4 * moe + d * 16032
    assert flops.train_step_flops(model) == pytest.approx(6 * s * forward,
                                                          rel=1e-12)
    share = sum(w["flops"] for w in flops.attention_passes(model))
    assert share / flops.train_step_flops(model) == pytest.approx(0.767,
                                                                  abs=1e-3)
